"""Shampoo's statistics on the packed rank-k path: the scaled and rows-side
accumulating program (``C <- beta C + alpha tril(A^t A)`` or of ``A A^t``)
in the IR interpreter and the executor against float64 numpy, the plain
accumulation unchanged bit for bit, ``shampoo.update_stats`` against the
plain reference, the blocks of a weight against the reference's blockwise
statistics of the whole weight, and the step's span and counter."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.leaf_ir import compile_program, interpret_program
from repro.core.symmetry import unpack_tril_blocks
from repro.gram import stream
from repro.kernels.strassen_fused import (fused_aat_packed, fused_ata_packed,
                                          fused_rank_k_update)
from repro.obs import metrics, trace
from repro.optim import shampoo_reference as ref

shampoo = importlib.import_module("repro.optim.shampoo")

SCALES = [(1.0, 1.0), (1.0, 0.1), (0.9, 1.0), (0.9, 0.1)]
TILE = 8


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _gram64(a, gram_of):
    a = np.asarray(a, np.float64)
    return a @ a.T if gram_of == "rows" else a.T @ a


def _dense(stack, n_pad):
    return np.tril(np.asarray(unpack_tril_blocks(stack, n_pad, TILE,
                                                 symmetrize=False),
                              np.float64))


@pytest.mark.parametrize("gram_of", ["cols", "rows"])
@pytest.mark.parametrize("beta,alpha", SCALES)
@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_ir_scaled_accumulate(levels, beta, alpha, gram_of):
    """The interpreter's rank_k program on either side:
    beta * c0 + alpha * tril(Gram)."""
    prog = compile_program("rank_k", levels, gram_of=gram_of)
    assert prog.gram_of == gram_of and prog.out_spec.accumulate
    b = prog.blocks
    rng = np.random.RandomState(levels)
    a = rng.randn(3 * b, 2 * b)
    n = a.shape[0] if gram_of == "rows" else a.shape[1]
    c0 = np.tril(rng.randn(n, n))
    got = interpret_program(prog, a, c0=c0, beta=beta, alpha=alpha)
    want = beta * c0 + alpha * np.tril(_gram64(a, gram_of))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("gram_of", ["cols", "rows"])
@pytest.mark.parametrize("beta,alpha", SCALES)
@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_executor_scaled_accumulate(levels, beta, alpha, gram_of):
    """The executor (pipelined walk, depth 2) on a ragged operand whose
    Gram side is narrower than the stack: beta * C + alpha * tril(Gram)
    against float64 numpy."""
    t = 8
    a = _rand((40, 56), seed=levels)
    c0 = _rand((t * (t + 1) // 2 * TILE, TILE), seed=10 + levels)
    out = fused_rank_k_update(c0, a, levels=levels, gram_of=gram_of,
                              beta=beta, alpha=alpha, bk=TILE,
                              interpret=True, pipeline_depth=2)
    g = _gram64(a, gram_of)
    want = beta * _dense(c0, t * TILE)
    want[:g.shape[0], :g.shape[0]] += alpha * np.tril(g)
    got = _dense(out, t * TILE)
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-6


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("gram_of", ["cols", "rows"])
@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_unit_scales_are_the_plain_accumulation_bit_for_bit(levels,
                                                            gram_of, depth):
    """beta = alpha = 1 is the plain rank-k update: from a zero stack it
    is the non-accumulating program's stack bit for bit (ata for the
    columns side, aat for the rows side), and from any stack it is the
    update with the scales left at their defaults."""
    a = _rand((64, 64), seed=levels + 20)
    t = 8
    zero = jnp.zeros((t * (t + 1) // 2 * TILE, TILE), jnp.float32)
    kw = dict(levels=levels, interpret=True, pipeline_depth=depth)
    got = fused_rank_k_update(zero, a, gram_of=gram_of, beta=1.0,
                              alpha=1.0, bk=TILE, **kw)
    if gram_of == "rows":
        plain, _ = fused_aat_packed(a, bm=TILE, bk=TILE, **kw)
    else:
        plain, _ = fused_ata_packed(a, bk=TILE, bn=TILE, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
    c0 = _rand(zero.shape, seed=levels + 30)
    np.testing.assert_array_equal(
        np.asarray(fused_rank_k_update(c0, a, gram_of=gram_of, beta=1.0,
                                       alpha=1.0, bk=TILE, **kw)),
        np.asarray(fused_rank_k_update(c0, a, gram_of=gram_of, bk=TILE,
                                       **kw)))


@pytest.mark.parametrize("gram_of", ["cols", "rows"])
def test_stack_update_rows_side_and_ema(gram_of):
    """stream.stack_update folds a chunk in on either side, as an EMA,
    and counts the contraction vectors it streamed."""
    a = _rand((24, 40), seed=3)
    n = a.shape[0] if gram_of == "rows" else a.shape[1]
    st = stream.stack_init(n, block=TILE)
    want = np.zeros((n, n))
    for i in range(2):
        st = stream.stack_update(st, a * (i + 1), levels=1, block=TILE,
                                 gram_of=gram_of, beta=0.5, alpha=0.25,
                                 interpret=True)
        want = 0.5 * want + 0.25 * _gram64(a * (i + 1), gram_of)
    got = np.asarray(stream.stack_finalize(st, n), np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert int(st.rows) == 2 * (a.shape[1] if gram_of == "rows"
                                else a.shape[0])
    with pytest.raises(ValueError):
        stream.stack_update(st, _rand((n + TILE * 8, n), seed=1),
                            gram_of="rows", block=TILE)


@pytest.mark.parametrize("mode", ["fused", "reference"])
@pytest.mark.parametrize("beta2", [0.9, 1.0])
def test_update_stats_matches_the_reference(beta2, mode):
    """Three steps with distinct gradients, block 32, tile 8: the packed
    statistics against shampoo_reference's dense EMA."""
    shapes = [(32, 16), (16, 32), (32, 32)]
    stats = shampoo.init_stats(shapes, block=TILE)
    want = ref.init(shapes)
    for step in range(3):
        grads = [_rand(s, seed=100 * step + i) for i, s in enumerate(shapes)]
        stats = shampoo.update_stats(stats, grads, beta2, levels="auto",
                                     leaf=TILE, mode=mode, block=TILE,
                                     interpret=True)
        want = ref.update(want, grads, beta2)
    got = shampoo.dense_stats(stats, shapes)
    for side in ("l", "r"):
        for g, w in zip(got[side], want[side]):
            w = np.asarray(w, np.float64)
            assert np.abs(np.asarray(g, np.float64) - w).max() \
                / np.abs(w).max() < 1e-5


@pytest.mark.parametrize("committed", [True, False])
def test_every_step_shares_one_executable(committed):
    """Fresh statistics are placed as a step's result is: the first step
    and the later ones compile one executable, with gradients committed
    to the device or not."""
    shapes = [(24, 40), (40, 24)]
    grads = [_rand(s, seed=i) for i, s in enumerate(shapes)]
    if committed:
        grads = jax.device_put(grads, jax.devices()[0])
    stats = shampoo.init_stats(shapes, block=TILE)
    before = shampoo._stats_step._cache_size()
    for _ in range(3):
        stats = shampoo.update_stats(stats, grads, 0.9, leaf=TILE,
                                     mode="reference", block=TILE)
    assert shampoo._stats_step._cache_size() == before + 1


@pytest.mark.parametrize("weight", [(64, 32), (32, 64), (64, 64)])
def test_block_share_is_the_weights_blockwise_statistics(weight):
    """The blocks a chip holds of a weight (cut as ``_to_blocks`` cuts
    it, here at block 32 like 16384 x 4096 at 8192), updated together by
    update_stats, are the reference's blockwise statistics of the whole
    weight."""
    grads = [_rand(weight, seed=7 + s) for s in range(2)]
    blocks = [ref.weight_blocks(g, 32) for g in grads]
    shapes = [b.shape for b in blocks[0]]
    assert len(shapes) == (weight[0] // 32) * (weight[1] // 32)
    stats = shampoo.init_stats(shapes, block=TILE)
    for bl in blocks:
        stats = shampoo.update_stats(stats, bl, 0.999, levels="auto",
                                     leaf=TILE, mode="fused", block=TILE,
                                     interpret=True)
    got = shampoo.dense_stats(stats, shapes)
    want = ref.weight_stats(grads, 32, 0.999)
    for side in ("l", "r"):
        assert len(got[side]) == len(want[side])
        for g, w in zip(got[side], want[side]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-5)


def test_stats_step_is_a_span_and_counts_its_grams():
    """Outside a trace, a step is one live ``shampoo_stats`` span with
    its block count and sides, and counts a Gram a block a side; traced
    inside a jitted step it records neither."""
    shapes = [(16, 8), (8, 16)]
    grads = [_rand(s, seed=i) for i, s in enumerate(shapes)]
    counter = metrics.counter("shampoo_stat_grams_total")
    before = {side: counter.value(side=side) for side in ("l", "r")}
    saved = trace.get_tracer()
    tracer = trace.set_tracer(trace.Tracer(enabled=True))
    try:
        stats = shampoo.update_stats(shampoo.init_stats(shapes, block=TILE),
                                     grads, 0.99, mode="reference")
        jax.jit(lambda st, g: shampoo.update_stats(
            st, g, 0.99, mode="reference"))(stats, grads)
    finally:
        trace.set_tracer(saved)
    spans = [e for e in tracer.events() if e.name == "shampoo_stats"]
    assert len(spans) == 1
    assert spans[0].attrs == {"blocks": 2, "sides": "l,r"}
    for side in ("l", "r"):
        assert counter.value(side=side) == before[side] + 2


@pytest.mark.parametrize("gram_of", ["cols", "rows"])
def test_scaled_accumulate_gradients(gram_of):
    """jax.grad through the scaled update in the stack and the operand,
    against the same update written in jax.numpy (the stack's diagonal
    tiles hold their full Gram tile)."""
    from repro.core.symmetry import pack_tril_blocks
    t = 4
    a = _rand((24, 16), seed=41)
    c0 = _rand((t * (t + 1) // 2 * TILE, TILE), seed=42)
    w = _rand(c0.shape, seed=43)

    def kernel(c, x, beta, alpha):
        out = fused_rank_k_update(c, x, levels=1, gram_of=gram_of,
                                  beta=beta, alpha=alpha, bk=TILE,
                                  interpret=True)
        return jnp.vdot(w, out)

    def plain(c, x, beta, alpha):
        g = x @ x.T if gram_of == "rows" else x.T @ x
        pad = t * TILE - g.shape[0]
        g = jnp.pad(g, ((0, pad), (0, pad)))
        return jnp.vdot(w, beta * c + alpha * pack_tril_blocks(g, TILE))

    args = (c0, a, jnp.float32(0.9), jnp.float32(0.1))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(plain, argnums=(0, 1))(*args)
    got = jax.grad(kernel, argnums=(0, 1))(*args)
    for g, wv in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wv),
                                   rtol=1e-4, atol=1e-3)
