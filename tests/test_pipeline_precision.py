"""The executor's one walk, and the low-precision operand path.

Three contracts:

* the real-entry walk (one grid step per output tile, double-buffered
  tile copies) matches float64 numpy on every kind, level and gram
  algebra, on tile-aligned and ragged operands; interpret mode runs the
  walk the chip runs, under the same kernel name, and under ``vmap`` the
  walk runs the batch elements one after another, bit for bit as the
  unbatched calls;
* fp8/bf16 operand tiles quantize once (after padding) and accumulate in
  fp32, so the output matches the quantized-operand oracle to fp32
  accuracy and still satisfies the Freivalds identity vs the ORIGINAL
  operand at the precision-scaled tolerance;
* the operand knob persists and replays: autotune winners carry
  ``operand_dtype`` through a cache round-trip, the engine buckets
  quantized requests separately from native ones, and the candidate
  dedupe collapses identically-scored duplicates.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.symmetry import unpack_tril_blocks
from repro.gram import GramEngine
from repro.gram import autotune as at
from repro.gram.verify import default_rtol, freivalds_gram
from repro.kernels import ops

KINDS = ["ata", "aat", "matmul", "symm", "rank_k"]
_SCOPE = re.compile(r"fused:\w+:l\d+:\w+:\w+(?::pd\d+)?")


def _rand(seed, m, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, n), jnp.float32)


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "gram_autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    return path


# --------------------------------------------------------------------------
# the walk against float64 numpy, all kinds
# --------------------------------------------------------------------------

def _f64(x):
    return np.asarray(x, np.float64)


def _kind_call(kind, levels, gram="strassen", ragged=False, tile=None,
               interpret=None):
    """(fn, args, oracle) of one fused call of ``kind`` at ``levels``.

    Tile-aligned: 32-edge tiles, shaped so that no level is clamped away
    (at levels >= 2 the leaf programs pad their contribution and term
    slots).  Ragged: a 257 x 511 operand on 64-edge tiles, so every
    padding path is live at once.  ``oracle(out)`` returns the result and
    its float64 reference as comparable arrays."""
    tile = tile or (64 if ragged else 32)
    if ragged:
        m, n = 257, 511
    else:
        m, n = (96, 32 << max(levels, 1)) if levels < 3 else (160, 256)
    a = _rand(0, m, n)
    kw = dict(levels=levels, interpret=interpret)
    if kind == "ata":
        return (lambda a: ops.ata_fused(a, gram=gram, bk=tile, bn=tile,
                                        **kw)), (a,), \
            lambda out: (_f64(out), np.tril(_f64(a).T @ _f64(a)))
    if kind == "aat":
        return (lambda a: ops.aat_fused(a, gram=gram, bm=tile, bk=tile,
                                        **kw)), (a,), \
            lambda out: (_f64(out), np.tril(_f64(a) @ _f64(a).T))
    if kind == "matmul":
        b = _rand(1, n, m)
        return (lambda a, b: ops.matmul_fused(a, b, bm=tile, bk=tile,
                                              bn=tile, **kw)), (a, b), \
            lambda out: (_f64(out), _f64(a) @ _f64(b))
    n_pad = -(-n // tile) * tile
    if kind == "symm":
        s_packed = ops.ata_fused_packed(a, levels=levels, bk=tile, bn=tile)
        x = _rand(2, 48, n)
        sym = _f64(unpack_tril_blocks(s_packed, n_pad, tile))
        xp = np.pad(_f64(x), ((0, 0), (0, n_pad - n)))
        return (lambda x, s: ops.symm_matmul(x, s, bm=tile, **kw)), \
            (x, s_packed), lambda out: (_f64(out), xp @ sym)
    assert kind == "rank_k"
    t = n_pad // tile
    stack = jnp.asarray(np.random.default_rng(3).standard_normal(
        (t * (t + 1) // 2 * tile, tile)).astype(np.float32))

    def dense(s):
        return np.tril(_f64(unpack_tril_blocks(s, n_pad, tile,
                                               symmetrize=False)))
    g = np.pad(_f64(a).T @ _f64(a), ((0, n_pad - n), (0, n_pad - n)))
    return (lambda c, a: ops.rank_k_update(c, a, gram=gram, bk=tile,
                                           donate=False, **kw)), \
        (stack, a), lambda out: (dense(out), dense(stack) + np.tril(g))


_WALK_CASES = (
    [(k, lv, "strassen", False) for k in KINDS for lv in range(4)]
    + [(k, lv, "dps", False) for k in ("ata", "aat", "rank_k")
       for lv in (1, 2)]
    # ata's ragged operand is test_fused_ata.py::test_fused_ata_odd_rectangular
    + [(k, lv, "strassen", True) for k in KINDS if k != "ata"
       for lv in (0, 1)])


@pytest.mark.parametrize("kind,levels,gram,ragged", _WALK_CASES)
def test_walk_matches_float64_oracle(kind, levels, gram, ragged):
    """The walk visits only the real contributions and terms of each
    destination; its result is the operation's, to fp32 accuracy, at the
    requested levels (the kernel's name says which ran)."""
    fn, args, oracle = _kind_call(kind, levels, gram, ragged)
    lowered = jax.jit(fn).lower(*args)
    scope = f"fused:{kind}:l{levels}:strassen:{gram}:pd2"
    assert scope in lowered.as_text(debug_info=True), scope
    got, want = oracle(lowered.compile()(*args))
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() / scale < 1e-5, (
        f"{kind} levels={levels} gram={gram} ragged={ragged}: max rel "
        f"{np.abs(got - want).max() / scale}")


@pytest.mark.parametrize("kind", KINDS)
def test_interpret_runs_the_chip_walk(kind):
    """Interpret mode lowers the same kernel the chip compiles: the
    kernel names of an interpret lowering and of a TPU lowering (built
    here without a chip; 128-edge tiles, as Mosaic requires of dense
    outputs) agree, double-buffered walk included."""
    names = {}
    for interpret in (True, False):
        fn, args, _ = _kind_call(kind, 1, ragged=True, tile=128,
                                 interpret=interpret)
        traced = jax.jit(fn).trace(*args)
        lowered = (traced.lower() if interpret
                   else traced.lower(lowering_platforms=("tpu",)))
        names[interpret] = set(_SCOPE.findall(
            lowered.as_text(debug_info=True)))
    assert names[True] == names[False], names
    assert names[True] and all(s.endswith(":pd2") for s in names[True]), \
        names


@pytest.mark.parametrize("kind", KINDS)
def test_walk_under_vmap(kind):
    """A batch runs the walk once per element in a ``lax.map`` (Pallas
    cannot batch a kernel whose operands stay in HBM), bit for bit as
    the unbatched calls; an unbatched operand (symm's stack) is shared."""
    fn, args, _ = _kind_call(kind, 1)
    batched = [jnp.stack([x, 2 * x]) for x in args]
    axes = (0, None) if kind == "symm" else 0
    if kind == "symm":
        batched[1] = args[1]
    vfn = jax.jit(jax.vmap(fn, in_axes=axes))
    assert "scan" in str(jax.make_jaxpr(vfn)(*batched))
    got = np.asarray(vfn(*batched))
    for i in range(2):
        one = [x[i] if x.ndim == 3 else x for x in batched]
        np.testing.assert_array_equal(got[i], np.asarray(jax.jit(fn)(*one)))


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
             c.get("operand_dtype"))
            for c in cands]
    assert len(sigs) == len(set(sigs)), "duplicate candidates survived"


def test_candidate_space_carries_pipeline_and_operand_axes():
    cands = at.candidate_space(64, 64, blocks=(32,), levels=(1,),
                               modes=("fused",),
                               operand_dtypes=(None, "bfloat16"))
    fused = [c for c in cands if c["mode"] == "fused"]
    assert {c["operand_dtype"] for c in fused} == {None, "bfloat16"}
    assert not any("pipeline_depth" in c for c in cands)


def test_autotune_cache_roundtrips_new_knobs(tmp_cache):
    """The persisted winner carries operand_dtype and a fresh lookup (new
    process simulated by a cache reload) replays it; an entry persisted
    with the buffer-depth key older versions wrote still loads, and its
    score ignores that key."""
    entry = at.autotune(64, 64, blocks=(32,), levels=(1,),
                        modes=("fused",), measure=False,
                        operand_dtypes=(None,))
    assert "operand_dtype" in entry and "pipeline_depth" not in entry
    # load_cache memoizes on (path, mtime): lookup below re-reads the
    # persisted file, i.e. what a fresh process would see
    hit = at.lookup(64, 64)
    assert hit is not None
    assert hit["operand_dtype"] == entry["operand_dtype"]
    old = {k: v for k, v in entry.items() if k != "measured_s"}
    old["pipeline_depth"] = 1
    at._save_entry(at._key(jax.default_backend(), "float32", "ata", 128,
                           128), old, tmp_cache)
    hit = at.lookup(100, 100)
    assert hit is not None and hit["pipeline_depth"] == 1
    assert at.resolve_block_defaults("ata", 100, 100, jnp.float32,
                                     bk=None, bn=None) == {"bk": 32, "bn": 32}
    plain = {k: v for k, v in hit.items() if k != "pipeline_depth"}
    assert at.model_score(128, 128, hit) == at.model_score(128, 128, plain)


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
