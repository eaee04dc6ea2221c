"""ISSUE 10: pipelined execution + the low-precision operand path.

Three contracts:

* the double-buffered DMA pipeline (``pipeline_depth > 1``) is BIT-EXACT
  vs the depth-1 schedule on every kind — same tiles, same signed sums,
  same accumulate seeding, only the fetch schedule differs;
* fp8/bf16 operand tiles quantize once (after padding) and accumulate in
  fp32, so the output matches the quantized-operand oracle to fp32
  accuracy and still satisfies the Freivalds identity vs the ORIGINAL
  operand at the precision-scaled tolerance;
* the new knobs persist and replay: autotune winners carry
  ``pipeline_depth``/``operand_dtype`` through a cache round-trip, the
  engine buckets quantized requests separately from native ones, and the
  candidate dedupe collapses identically-scored duplicates.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gram import GramEngine
from repro.gram import autotune as at
from repro.gram.verify import default_rtol, freivalds_gram
from repro.kernels import ops


def _rand(seed, m, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, n), jnp.float32)


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "gram_autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    return path


# --------------------------------------------------------------------------
# pipeline_depth parity: depth>1 must be bit-exact vs depth=1, all kinds
# --------------------------------------------------------------------------

def _kind_call(kind, levels, gram="strassen"):
    """(fn, args) of one fused call of ``kind`` at ``levels`` with 32-edge
    tiles, shaped so that no level is clamped away: at levels >= 2 the
    leaf programs pad their contribution and term slots."""
    m, n = (96, 32 << levels) if levels < 3 else (160, 256)
    a = _rand(0, m, n)
    if kind == "ata":
        return (lambda a, depth: ops.ata_fused(
            a, levels=levels, gram=gram, bk=32, bn=32,
            pipeline_depth=depth)), (a,)
    if kind == "aat":
        return (lambda a, depth: ops.aat_fused(
            a, levels=levels, gram=gram, bm=32, bk=32,
            pipeline_depth=depth)), (a,)
    if kind == "matmul":
        b = _rand(1, n, m)
        return (lambda a, b, depth: ops.matmul_fused(
            a, b, levels=levels, bm=32, bk=32, bn=32,
            pipeline_depth=depth)), (a, b)
    if kind == "symm":
        s_packed = ops.ata_fused_packed(a, levels=levels, bk=32, bn=32)
        x = _rand(2, 48, n)
        return (lambda x, s, depth: ops.symm_matmul(
            x, s, levels=levels, bm=32, pipeline_depth=depth)), (x, s_packed)
    assert kind == "rank_k"
    t = n // 32
    stack = jnp.asarray(np.random.default_rng(3).standard_normal(
        (t * (t + 1) // 2 * 32, 32)).astype(np.float32))
    return (lambda c, a, depth: ops.rank_k_update(
        c, a, levels=levels, gram=gram, bk=32, donate=False,
        pipeline_depth=depth)), (stack, a)


@functools.lru_cache(maxsize=None)
def _run_kind(kind, depth, levels, gram="strassen"):
    """Run the call, asserting its kernel ran at the requested levels
    (cached: the depth-1 baseline serves every depth it is compared
    with)."""
    fn, args = _kind_call(kind, levels, gram)
    lowered = jax.jit(functools.partial(fn, depth=depth)).lower(*args)
    scope = f"fused:{kind}:l{levels}:strassen:{gram}"
    assert scope in lowered.as_text(debug_info=True), scope
    return np.asarray(lowered.compile()(*args))


@pytest.mark.parametrize("kind", ["ata", "aat", "matmul", "symm", "rank_k"])
@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("levels", [1, 2, 3])
def test_pipeline_depth_bit_exact_parity(kind, depth, levels):
    """The pipelined kernel walks only the real contributions and terms;
    the grid walk multiplies the padded slots by 0.  Both sum the real
    terms in the same order, so the results agree bit for bit."""
    base = _run_kind(kind, 1, levels)
    got = _run_kind(kind, depth, levels)
    assert np.array_equal(base, got), (
        f"{kind} levels={levels}: depth={depth} differs from depth=1 "
        f"(max abs {np.abs(base - got).max()})")


@pytest.mark.parametrize("kind", ["ata", "aat", "rank_k"])
def test_pipeline_depth_bit_exact_parity_dps(kind):
    """The rational-coefficient gram algebra (+-1/2, +-1/4) at levels 2."""
    base = _run_kind(kind, 1, 2, gram="dps")
    got = _run_kind(kind, 2, 2, gram="dps")
    assert np.array_equal(base, got), (
        f"{kind} dps: depth=2 differs from depth=1 "
        f"(max abs {np.abs(base - got).max()})")


@pytest.mark.parametrize("kind,depth", [("ata", 2), ("ata", 3), ("aat", 2)])
def test_pipeline_depth_parity_ragged_rect(kind, depth):
    """257x511: every padding/clamping path live at once (ragged in both
    dims, rectangular) — the pipeline must still be bit-exact."""
    a = _rand(7, 257, 511)
    fn = ops.ata_fused if kind == "ata" else ops.aat_fused
    kw = (dict(bk=64, bn=64) if kind == "ata" else dict(bm=64, bk=64))
    base = np.asarray(fn(a, levels=1, pipeline_depth=1, **kw))
    got = np.asarray(fn(a, levels=1, pipeline_depth=depth, **kw))
    assert np.array_equal(base, got)


def test_pipeline_depth_validated():
    a = _rand(0, 64, 64)
    with pytest.raises(ValueError):
        ops.ata_fused(a, levels=1, bk=32, bn=32, pipeline_depth=0)


# --------------------------------------------------------------------------
# fp8 / bf16 operand tiles
# --------------------------------------------------------------------------

@pytest.mark.parametrize("od", ["bfloat16", "float8_e4m3fn", "float8_e5m2"])
def test_operand_tile_parity_512(od):
    """The kernel's quantize-after-pad + fp32-accumulate semantics: the
    output matches the quantized-operand float64 oracle to fp32-Strassen
    accuracy (the quantized values are exact in fp32, so the only error
    left is accumulation), and the end-to-end result still satisfies the
    Freivalds identity vs the ORIGINAL operand at default_rtol(od)."""
    a = _rand(11, 512, 512)
    got = np.asarray(ops.ata_fused(a, levels=2, bk=128, bn=128,
                                   operand_dtype=od), np.float64)
    aq = np.asarray(a.astype(jnp.dtype(od)).astype(jnp.float32), np.float64)
    want = np.tril(aq.T @ aq)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() / scale < 1e-4, od
    ok, err = freivalds_gram(np.asarray(a), got, probes=4, full=False,
                             rtol=default_rtol(od))
    assert ok, (od, err, default_rtol(od))


def test_operand_dtype_rejects_unknown():
    a = _rand(0, 64, 64)
    with pytest.raises(ValueError):
        ops.ata_fused(a, levels=1, bk=32, bn=32, operand_dtype="int8")


def test_precision_scaled_rtol_ordering():
    """Tolerance must widen with the quantization step: fp32 < bf16 <
    e4m3 (eps 2^-3) < e5m2 (eps 2^-2)."""
    assert (default_rtol("float32") < default_rtol("bfloat16")
            < default_rtol("float8_e4m3fn") < default_rtol("float8_e5m2"))


# --------------------------------------------------------------------------
# autotune: dedupe + cache round-trip of the new knobs
# --------------------------------------------------------------------------

def test_candidate_dedupe_collapses_aat_square_duplicates():
    """For aat at bm == bk the (bm, bk) and (bk, bm) candidates are the
    same program; dedupe keeps one."""
    cands = at.candidate_space(64, 64, kind="aat", blocks=(32, 64),
                               levels=(1,), modes=("fused",))
    sigs = [(c["levels"], c["variant"], c.get("gram"), c["bm"], c["bk"],
             c.get("pipeline_depth"), c.get("operand_dtype"))
            for c in cands]
    assert len(sigs) == len(set(sigs)), "duplicate candidates survived"


def test_candidate_space_carries_pipeline_and_operand_axes():
    cands = at.candidate_space(64, 64, blocks=(32,), levels=(1,),
                               modes=("fused",),
                               pipeline_depths=(1, 2),
                               operand_dtypes=(None, "bfloat16"))
    fused = [c for c in cands if c["mode"] == "fused"]
    assert {c["pipeline_depth"] for c in fused} == {1, 2}
    assert {c["operand_dtype"] for c in fused} == {None, "bfloat16"}


def test_autotune_cache_roundtrips_new_knobs(tmp_cache):
    """The persisted winner carries pipeline_depth/operand_dtype and a
    fresh lookup (new process simulated by a cache reload) replays them."""
    entry = at.autotune(64, 64, blocks=(32,), levels=(1,),
                        modes=("fused",), measure=False,
                        pipeline_depths=(1, 2), operand_dtypes=(None,))
    assert entry["pipeline_depth"] in (1, 2)
    assert "operand_dtype" in entry
    # load_cache memoizes on (path, mtime): lookup below re-reads the
    # persisted file, i.e. what a fresh process would see
    hit = at.lookup(64, 64)
    assert hit is not None
    assert hit["pipeline_depth"] == entry["pipeline_depth"]
    assert hit["operand_dtype"] == entry["operand_dtype"]


def test_model_score_prefers_pipelined_on_balanced_shapes():
    """With the roofline term live, depth=2 overlap can only help (score
    is max+fill vs sum), so at fixed everything-else the pd=2 candidate
    never scores WORSE than pd=1."""
    base = {"mode": "fused", "variant": "strassen", "gram": "strassen",
            "levels": 1, "bk": 64, "bn": 64, "operand_dtype": None}
    s1 = at.model_score(512, 512, {**base, "pipeline_depth": 1})
    s2 = at.model_score(512, 512, {**base, "pipeline_depth": 2})
    assert s2 <= s1


# --------------------------------------------------------------------------
# engine: quantized buckets are separate, guarded at the scaled rtol
# --------------------------------------------------------------------------

def test_engine_buckets_quantized_requests_separately():
    eng = GramEngine(slots=2, levels=1, leaf=8, min_bucket=16)
    a = np.random.default_rng(0).standard_normal((64, 32)).astype(np.float32)
    k_native = eng._bucket_key(a.shape, a.dtype)
    k_fp8 = eng._bucket_key(a.shape, a.dtype,
                            operand_dtype="float8_e4m3fn")
    assert len(k_native) == 5 and k_native[4] == "native"
    assert k_fp8[4] == "float8_e4m3fn"
    assert k_native != k_fp8
    # native label keeps the historical format (drift keys pin it)
    assert eng._blabel(k_native) == "64x32/float32/cols"
    assert eng._blabel(k_fp8) == "64x32/float32/cols/float8_e4m3fn"


def test_engine_serves_fp8_request_verified():
    """A quantized submit serves through its own bucket, passes the
    precision-scaled Freivalds guard, and lands within default_rtol of
    the true gram."""
    rng = np.random.default_rng(5)
    eng = GramEngine(slots=2, levels=1, leaf=8, min_bucket=16)
    a = rng.standard_normal((64, 32)).astype(np.float32)
    eng.submit(a)                                    # native
    r8 = eng.submit(a, operand_dtype="float8_e4m3fn")
    done = {r.uid: r for r in eng.run_to_completion()}
    want = a.astype(np.float64).T @ a.astype(np.float64)
    scale = max(np.abs(want).max(), 1.0)
    err8 = np.abs(done[r8.uid].result - want).max() / scale
    assert err8 < default_rtol("float8_e4m3fn")
    assert err8 > 1e-4          # it really quantized (not native served)


def test_engine_pipeline_depth_bit_exact_serving():
    """Engine-level depth-2 serving returns bit-identical grams to the
    depth-1 engine (the knob changes scheduling, never numerics)."""
    rng = np.random.default_rng(6)
    a = rng.standard_normal((48, 24)).astype(np.float32)
    outs = []
    for depth in (1, 2):
        eng = GramEngine(slots=2, levels=1, leaf=8, min_bucket=16,
                         pipeline_depth=depth)
        eng.submit(a)
        (r,) = eng.run_to_completion()
        outs.append(np.asarray(r.result))
    assert np.array_equal(outs[0], outs[1])
