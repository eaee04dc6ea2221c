"""``levels="auto"``: the executor's cheapest depth by its traffic model.

On the fused path every ``"auto"`` resolves through
``strassen_fused.auto_levels``: the argmin over depths 0..AUTO_MAX_LEVELS
of ``cost_model.pipelined_bytes_score`` of the kind's traffic model, a
tie within ``AUTO_TIE_SHARE`` going to the shallower program.  The
reference recursion keeps its natural depth.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import ata, strassen_matmul
from repro.core.ata import ata_levels_for, resolve_levels
from repro.core.cost_model import pipelined_bytes_score
from repro.core.distributed import distributed_gram
from repro.core.strassen import AUTO_MAX_LEVELS
from repro.gram import stream
from repro.kernels import ops
from repro.kernels import strassen_fused as sf


def _rand(shape, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def _model(kind, m, n, levels, *, k=None, gram_of="cols", in_bytes=4,
           out_bytes=4, tile=256):
    if kind == "ata":
        return sf.ata_traffic_model(m, n, levels=levels, in_bytes=in_bytes,
                                    out_bytes=out_bytes, bk=tile, bn=tile)
    if kind == "aat":
        return sf.aat_traffic_model(m, n, levels=levels, in_bytes=in_bytes,
                                    out_bytes=out_bytes, bm=tile, bk=tile)
    if kind == "rank_k":
        return sf.rank_k_traffic_model(m, n, levels=levels, gram_of=gram_of,
                                       in_bytes=in_bytes,
                                       state_bytes=out_bytes, bk=tile,
                                       bn=tile)
    return sf.matmul_traffic_model(m, k, n, levels=levels,
                                   in_bytes=in_bytes, out_bytes=out_bytes,
                                   bm=tile, bk=tile, bn=tile)


def _argmin(scores):
    best = min(scores)
    return next(lv for lv, s in enumerate(scores)
                if s <= best * (1 + sf.AUTO_TIE_SHARE))


@pytest.mark.parametrize("kind,m,n,k,gram_of,in_dt", [
    ("ata", 10000, 10000, None, "cols", jnp.float32),
    ("ata", 3000, 700, None, "cols", jnp.bfloat16),
    ("aat", 4096, 2048, None, "cols", jnp.float32),
    ("rank_k", 4096, 16384, None, "cols", jnp.bfloat16),
    ("rank_k", 4096, 8192, None, "rows", jnp.float32),
    ("matmul", 5000, 5000, 5000, "cols", jnp.float32),
    ("matmul", 1024, 3072, 2048, "cols", jnp.bfloat16),
])
@pytest.mark.parametrize("tile", [256, 128])
def test_auto_is_the_traffic_model_argmin(kind, m, n, k, gram_of, in_dt,
                                          tile):
    """At the default tile and at a block size the autotuner passes; the
    depths tried stop where the fan-in or SMEM clamp stops them."""
    in_bytes = jnp.dtype(in_dt).itemsize
    scores = []
    for lv in range(sf._fan_in_depth(kind, AUTO_MAX_LEVELS, "strassen")
                    + 1):
        t = _model(kind, m, n, lv, k=k, gram_of=gram_of, in_bytes=in_bytes,
                   tile=tile)
        scores.append(pipelined_bytes_score(
            t["read_bytes"] + t["intermediate_bytes"], t["write_bytes"],
            t["flops"], grid_steps=t["grid_steps"]))
    got = sf.auto_levels(kind, m, n, k=k, gram_of=gram_of,
                         operand_dtype=in_dt, bm=tile, bk=tile, bn=tile)
    assert got == _argmin(scores)


@pytest.mark.parametrize("scores,want", [
    ([100.0, 100.0, 150.0, 300.0], 0),     # exact tie: the shallower
    ([100.0, 99.5, 150.0, 300.0], 0),      # within 1 %: still the shallower
    ([100.0, 98.0, 150.0, 300.0], 1),      # 2 % cheaper: the deeper wins
    ([400.0, 300.0, 200.0, 199.0], 2),     # L3 within 1 % of L2
    ([400.0, 300.0, 200.0, 150.0], 3),     # a schedule cheaper in bytes
])
def test_auto_tie_goes_to_fewer_levels(monkeypatch, scores, want):
    """The rule, on a traffic model that makes Strassen cheaper: the
    same function picks the deeper program without another edit."""
    def fake(m, n, *, levels, **kw):
        return {"read_bytes": scores[levels], "write_bytes": 0.0,
                "intermediate_bytes": 0.0, "flops": 0.0, "grid_steps": 1}
    monkeypatch.setattr(sf, "ata_traffic_model", fake)
    sf._auto_levels.cache_clear()
    try:
        assert sf.auto_levels("ata", 4096, 4096) == want
    finally:
        sf._auto_levels.cache_clear()


@pytest.mark.parametrize("kind,m,n,gram_of,in_dt", [
    ("ata", 10000, 10000, "cols", jnp.float32),      # paper-gram-10k.loop
    ("ata", 5000, 10000, "cols", jnp.float32),       # mesh4's shard
    ("rank_k", 4096, 16384, "cols", jnp.bfloat16),   # normal-eq-16k.stream
    ("rank_k", 4096, 8192, "rows", jnp.float32),     # shampoo L, 8192 wide
    ("rank_k", 8192, 4096, "rows", jnp.float32),     # shampoo L, 4096 wide
    ("rank_k", 8192, 4096, "cols", jnp.float32),     # shampoo R, 4096 wide
    ("rank_k", 4096, 8192, "cols", jnp.float32),     # shampoo R, 8192 wide
])
def test_the_cells_shapes_resolve_to_classical_blocking(kind, m, n, gram_of,
                                                        in_dt):
    """At 256 tiles a deeper program reads more bytes on every benchmark
    shape."""
    assert sf.auto_levels(kind, m, n, gram_of=gram_of,
                          operand_dtype=in_dt) <= 1


def test_reference_mode_keeps_the_natural_depth():
    a = _rand((96, 80), seed=1)
    want = min(ata_levels_for(96, 80, 16), AUTO_MAX_LEVELS)
    assert want == 3
    assert resolve_levels("auto", a.shape, a.dtype, mode="reference",
                          leaf=16) == want
    np.testing.assert_array_equal(
        np.asarray(ata(a, levels="auto", leaf=16, mode="reference")),
        np.asarray(ata(a, levels=want, leaf=16, mode="reference")))


def test_explicit_levels_pass_through():
    for mode in ("fused", "reference"):
        assert resolve_levels(3, (10000, 10000), jnp.float32,
                              mode=mode) == 3


def _spy(monkeypatch, module, name):
    seen = []
    real = getattr(module, name)

    def spy(*args, **kw):
        seen.append(kw["levels"])
        return real(*args, **kw)
    monkeypatch.setattr(module, name, spy)
    return seen


def test_fused_ata_runs_the_policy_depth(monkeypatch):
    seen = _spy(monkeypatch, ops, "ata_fused")
    a = _rand((512, 512), seed=2)
    got = ata(a, levels="auto", mode="fused", interpret=True)
    assert seen == [sf.auto_levels("ata", 512, 512)]
    np.testing.assert_allclose(np.asarray(got), np.tril(np.asarray(a.T @ a)),
                               rtol=1e-4, atol=1e-3)


def test_fused_strassen_matmul_runs_the_policy_depth(monkeypatch):
    seen = _spy(monkeypatch, ops, "matmul_fused")
    a, b = _rand((40, 24), seed=3), _rand((24, 56), seed=4)
    got = strassen_matmul(a, b, levels="auto", mode="fused", block=8,
                          interpret=True)
    assert seen == [sf.auto_levels("matmul", 40, 56, k=24, bm=8, bk=8,
                                   bn=8)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("gram_of", ["cols", "rows"])
def test_stack_update_runs_the_policy_depth(monkeypatch, gram_of):
    seen = _spy(monkeypatch, ops, "rank_k_update")
    st = stream.stack_init(64, block=16)
    chunk = _rand((64, 40) if gram_of == "rows" else (40, 64), seed=5)
    st = stream.stack_update(st, chunk, levels="auto", block=8,
                             gram_of=gram_of, interpret=True)
    assert seen == [sf.auto_levels("rank_k", 40, 64, gram_of=gram_of, bk=8,
                                   bn=16)]
    c = np.asarray(chunk, np.float64)
    want = c @ c.T if gram_of == "rows" else c.T @ c
    np.testing.assert_allclose(np.asarray(stream.stack_finalize(st, 64)),
                               want, rtol=1e-4, atol=1e-3)


def test_distributed_gram_default_follows_the_policy(monkeypatch):
    """``distributed_gram``'s default depth is ``"auto"``, resolved on
    each shard's own shape by the same rule as ``ata``."""
    assert inspect.signature(distributed_gram).parameters["levels"] \
        .default == "auto"
    seen = _spy(monkeypatch, ops, "ata_fused")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    a = _rand((512, 256), seed=6)
    got = distributed_gram(a, mesh, scheme="allreduce", mode="fused",
                           out_dtype=jnp.float32, interpret=True)
    assert seen == [sf.auto_levels("ata", 512, 256)]
    c = np.asarray(a, np.float64)
    np.testing.assert_allclose(np.asarray(got), c.T @ c, rtol=1e-4,
                               atol=1e-3)
