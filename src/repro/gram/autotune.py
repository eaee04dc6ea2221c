"""Per-bucket autotuning for the Gram service.

The Benson–Ballard observation carried into this repo: a fast-matmul
variant only pays off when the variant *and* blocking are selected per
shape.  This module searches ``mode x levels x variant x gram x
(bm, bk, bn)`` per (shape bucket, dtype, backend) — the variant and
gram-algebra axes enumerate the live leaf-IR registries, so registering
a new algebra automatically enters it in the contest — ranks candidates
with the analytic HBM
traffic model (``kernels.strassen_fused.ata_traffic_model`` — exact for
the fused kernel on hardware), optionally times the top-K on the current
device, and persists the winner to a JSON cache under
``artifacts/autotune/``.

``kernels/ops.py`` and the ``core`` recursions consult this cache for
their block-size defaults (``resolve_block_defaults``) instead of the
historical hardcoded 256s; ``gram.engine.GramEngine`` consults it for the
full per-bucket config (mode + levels + blocks).

Cache file format (``gram_autotune.json``)::

    {"version": 2,
     "entries": {
       "<backend>/jax-<version>/<dtype>/<kind>/<M>x<N>": {
          "mode": "fused", "levels": 2, "variant": "strassen",
          "gram": "strassen", "bm": 256, "bk": 256, "bn": 256,
          "model_bytes": 1234, "measured_s": null, "source": "model",
          "jax": "<version>", "backend": "<backend>"}}}

Keys are *bucketed* shapes (``bucket_shape``), so one entry serves every
request shape that rounds up to the same bucket — and they pin the
persist-time (backend, jax version) pair: winners are measurements of
one toolchain, and before v2 a stale winner from a different jax
silently applied after an upgrade (pre-v2 files are ignored wholesale;
see ``load_cache``).  Invalidation: the file is re-read whenever its
mtime changes (delete it, or re-run ``autotune`` with ``refresh=True``,
to invalidate).  Set ``REPRO_AUTOTUNE_CACHE`` to relocate the cache
(tests point it at a tmp dir).

Kinds: ``ata`` (forward column gram), ``aat`` (row gram,
``gram_of="rows"``), ``rank_k`` (the accumulating streamed update),
``ata_bwd`` (the Gram backward) — all scored by the one IR-driven
traffic core in ``kernels.strassen_fused``.
"""
from __future__ import annotations

import json
import math
import os
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import metrics as _metrics

__all__ = [
    "bucket_shape", "candidate_space", "dedupe_candidates", "model_score",
    "autotune", "lookup", "resolve_block_defaults", "load_cache",
    "default_cache_path", "invalidate", "DEFAULT_BLOCK",
]

def _cache_event(outcome: str, amount: float = 1.0) -> None:
    """Count one cache-lifecycle event: "hit"/"miss" per lookup,
    "corrupt" (unparseable file degraded to empty), "stale_dropped"
    (pre-v2 entries dropped wholesale at load), "persist" (entry
    written), "invalidate" (winner dropped — drift findings land here).
    Resolved from the live registry per call so a test-time registry
    reset cannot orphan the counter."""
    _metrics.counter("gram_autotune_cache_total",
                     "autotune cache events by outcome").inc(
        amount, outcome=outcome)

DEFAULT_BLOCK = 256
# v2: cache keys gained the jax-version segment (see _key) — a winner
# measured under one jax/backend silently applying after an upgrade was a
# real bug; v1 files are ignored wholesale (stale by construction).
_CACHE_VERSION = 2

# (path, mtime) -> parsed entries; re-read on mtime change (invalidation).
_memo: dict = {}


def default_cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    # src/repro/gram/autotune.py -> repo root is parents[3]
    root = Path(__file__).resolve().parents[3]
    return root / "artifacts" / "autotune" / "gram_autotune.json"


def next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def bucket_shape(m: int, n: int, *, min_side: int = 32) -> tuple[int, int]:
    """Round a request shape up to the service bucket (powers of two,
    floored at ``min_side``).  Exact for Gram: zero-padding rows of A adds
    nothing to A^tA and zero columns are sliced away by the caller."""
    return (max(next_pow2(m), min_side), max(next_pow2(n), min_side))


def _key(backend: str, dtype: str, kind: str, M: int, N: int) -> str:
    """Cache key for one tuned bucket.

    Includes the *persist-time* backend name AND the jax version: tuned
    winners are measurements of one (jax, backend) pair — block sizes
    and mode crossovers move across jax upgrades, and before v2 a stale
    winner from a different jax silently applied after an upgrade
    (lookups key on the same two values, so mismatched entries simply
    never match).
    """
    return f"{backend}/jax-{jax.__version__}/{dtype}/{kind}/{M}x{N}"


# ---------------------------------------------------------------------------
# Search space + model scoring
# ---------------------------------------------------------------------------

def _variant_axis(kind: str) -> list:
    """(variant, gram) pairs ``kind`` can execute, from the live
    registries.  Gram kinds need square 2x2 variants for the off-diagonal
    table expansion; the gram-algebra axis only exists for them (matmul
    and everything else runs any registered split with the fixed
    placeholder gram)."""
    from ..core import leaf_ir
    if kind in ("ata", "aat", "rank_k", "ata_bwd"):
        variants = [v for v in leaf_ir.registered_algebras()
                    if leaf_ir.algebra_dims(v) == (2, 2, 2)]
        return [(v, g) for v in variants
                for g in leaf_ir.registered_gram_algebras()]
    return [(v, "strassen") for v in leaf_ir.registered_algebras()]


def candidate_space(M: int, N: int, *, backend: Optional[str] = None,
                    blocks=(128, 256, 512), levels=(0, 1, 2),
                    modes=("fused", "reference"), kind: str = "ata",
                    pipeline_depths=(1, 2), operand_dtypes=(None,)):
    """Enumerate (mode, levels, variant, gram, bm/bk/bn, pipeline_depth,
    operand_dtype) candidates for an (M, N) bucket.

    The variant/gram axes come from the live leaf-IR registries
    (``_variant_axis``), so registering a new algebra automatically puts
    it in contention — the historical hardcoded ``"strassen"`` meant even
    the registered winograd table could never win.  Blocks larger than
    the bucket only add padding, so they are dropped (keeping at least
    the smallest candidate).  The grid only varies the knobs ``kind``
    actually uses — ``aat`` ties bm=bk and ignores bn, and at levels=0
    every (variant, gram) compiles the identical classical program, so
    only one candidate is emitted there.  ``pipeline_depths`` /
    ``operand_dtypes`` (DESIGN.md §16) are fused-kernel knobs: the
    reference recursion pins depth 1 / native operands.
    """
    usable = [b for b in blocks if b <= max(M, N)] or [min(blocks)]
    axis = _variant_axis(kind)
    out = []
    for mode in modes:
        for lv in levels:
            if mode == "reference":
                # blocking/pipelining are fused-kernel knobs; the
                # reference recursion leaves tiling to XLA — one
                # candidate per level.
                out.append({"mode": "reference", "levels": lv,
                            "variant": "strassen", "gram": "strassen",
                            "bm": min(usable), "bk": min(usable),
                            "bn": min(usable), "pipeline_depth": 1,
                            "operand_dtype": None})
                continue
            pairs = axis if lv > 0 else [("strassen", "strassen")]
            for variant, gram in pairs:
                for bk in usable:
                    bns = [bk] if kind == "aat" else usable
                    for bn in bns:
                        for pd in pipeline_depths:
                            for od in operand_dtypes:
                                out.append({
                                    "mode": "fused", "levels": lv,
                                    "variant": variant, "gram": gram,
                                    "bm": bk, "bk": bk, "bn": bn,
                                    "pipeline_depth": int(pd),
                                    "operand_dtype": od})
    return dedupe_candidates(out, kind=kind)


def dedupe_candidates(cands, kind: str = "ata"):
    """Drop candidates that bind the identical executable config.

    The enumeration axes overshoot the kernel's real degrees of freedom:
    ``aat`` ties bm=bk and never reads bn (the historical tie-duplication
    that filled the measured top-K with re-timings of one config),
    levels=0 compiles the same classical program for every (variant,
    gram) pair, and reference candidates ignore blocking and the fused
    perf knobs entirely.  Keyed on the knobs ``kind`` actually uses,
    first occurrence wins (order — and therefore model ranking — is
    preserved)."""
    seen, out = set(), []
    for c in cands:
        lv = c["levels"]
        alg = ((c["variant"], c.get("gram", "strassen")) if lv > 0
               else ("classical", "classical"))
        if c["mode"] == "reference":
            sig = ("reference", lv, alg)
        else:
            blocks = ((c["bm"], c["bk"]) if kind == "aat"
                      else (c["bk"], c["bn"]))
            sig = ("fused", lv, alg, blocks,
                   int(c.get("pipeline_depth") or 1),
                   c.get("operand_dtype"))
        if sig in seen:
            continue
        seen.add(sig)
        out.append(c)
    return out


def _pipelined_side_score(side: dict, cand: dict, in_bytes: int) -> float:
    """Score one traffic-model side dict for a candidate.

    Legacy byte-sum (read + write + intermediate) unless the candidate
    carries the §16 perf knobs AND they deviate from the unpipelined
    native-operand baseline — entries tuned before those axes existed
    (and the depth-1/native candidates) keep their historical scores
    bit-for-bit.  ``operand_dtype`` rescales operand read traffic by the
    quantized itemsize; ``pipeline_depth`` >= 2 swaps the byte sum for
    ``cost_model.pipelined_bytes_score`` (max(mem, compute) + amortized
    fill instead of their sum)."""
    reads = float(side["read_bytes"])
    writes = float(side["write_bytes"])
    inter = float(side.get("intermediate_bytes", 0))
    if "pipeline_depth" not in cand and "operand_dtype" not in cand:
        return reads + writes + inter           # legacy candidate
    od = cand.get("operand_dtype")
    pd = int(cand.get("pipeline_depth") or 1)
    if od is not None:
        reads *= jnp.dtype(od).itemsize / float(in_bytes)
    from ..core.cost_model import pipelined_bytes_score
    # Depth 1 and depth >= 2 are both scored on the roofline (sum vs
    # max + fill) so the depth axis is an apples-to-apples contest; the
    # legacy byte-sum above has no compute term and would misrank
    # compute-bound shapes against pipelined candidates.
    return pipelined_bytes_score(
        reads + inter, writes, float(side.get("flops", 0)),
        pipeline_depth=pd, grid_steps=int(side.get("grid_steps", 1)))


def model_score(m: int, n: int, cand: dict, *, in_bytes: int = 4,
                out_bytes: int = 4, kind: str = "ata") -> float:
    """HBM-bytes score (lower is better) used to seed the search.

    Fused candidates use the exact analytic kernel models — all thin
    wrappers over the one IR-driven traffic core in
    ``kernels.strassen_fused`` (``_traffic`` over a bound program spec),
    so every kind (``ata``, ``aat``, ``rank_k``, ``ata_bwd``) is scored
    by the same machinery the executor is built on rather than a
    per-kind closed form.  Reference candidates use a closed-form upper
    estimate of what the recursion (or the relevant dense baseline)
    materializes — a deliberate heuristic.  Because the reference score
    is a heuristic while the fused score is exact, model-only search
    ranks fused candidates only — reference candidates compete through
    ``measure=True`` wall clock (see :func:`autotune`).

    Candidates carrying the §16 perf knobs (``pipeline_depth`` >= 2 or a
    quantized ``operand_dtype``) are scored with the pipelined roofline
    term (``_pipelined_side_score``); legacy candidates keep the
    historical byte sum.
    """
    if kind == "ata_bwd":
        from ..kernels.strassen_fused import ata_bwd_traffic_model
        # cotangent="dense": score the same entry point the measured
        # runner (and the ata() consumer the winner applies to) drives —
        # jax.grad through the dense forward packs the cotangent first.
        t = ata_bwd_traffic_model(m, n, levels=cand["levels"],
                                  variant=cand["variant"],
                                  gram=cand.get("gram", "strassen"),
                                  bk=cand["bk"],
                                  bn=cand["bn"], in_bytes=in_bytes,
                                  cotangent="dense")
        side = t if cand["mode"] == "fused" else t["dense_baseline"]
        return _pipelined_side_score(side, cand, in_bytes)
    if kind == "rank_k":
        from ..kernels.strassen_fused import rank_k_traffic_model
        t = rank_k_traffic_model(m, n, levels=cand["levels"],
                                 variant=cand["variant"],
                                 gram=cand.get("gram", "strassen"),
                                 bk=cand["bk"],
                                 bn=cand["bn"], in_bytes=in_bytes,
                                 state_bytes=out_bytes)
        # "reference" = the status-quo streamed update (delta stack +
        # gather-add) the accumulating kernel replaces
        side = t if cand["mode"] == "fused" else t["baseline"]
        return _pipelined_side_score(side, cand, in_bytes)
    if cand["mode"] == "fused":
        from ..kernels.strassen_fused import (aat_traffic_model,
                                              ata_traffic_model)
        if kind == "aat":
            t = aat_traffic_model(m, n, levels=cand["levels"],
                                  variant=cand["variant"],
                                  gram=cand.get("gram", "strassen"),
                                  bm=cand["bm"],
                                  bk=cand["bk"], in_bytes=in_bytes,
                                  out_bytes=out_bytes)
        else:
            t = ata_traffic_model(m, n, levels=cand["levels"],
                                  variant=cand["variant"],
                                  gram=cand.get("gram", "strassen"),
                                  bk=cand["bk"],
                                  bn=cand["bn"], in_bytes=in_bytes,
                                  out_bytes=out_bytes)
        return _pipelined_side_score(t, cand, in_bytes)
    lv = cand["levels"]
    amplification = (7.0 / 4.0) ** lv
    d = m if kind == "aat" else n          # gram output dimension
    reads = m * n * in_bytes * max(amplification, 1.0)
    writes = d * d * out_bytes
    intermediates = (m * n + d * d) * in_bytes * (amplification - 1.0) * 2
    return float(reads + writes + intermediates)


# ---------------------------------------------------------------------------
# Cache IO
# ---------------------------------------------------------------------------

def load_cache(path: Optional[os.PathLike] = None) -> dict:
    """Entries dict from the JSON cache ({} when absent/corrupt).
    Memoized on (path, mtime): touching the file invalidates.

    A corrupt file (truncated by a crash mid-write outside our atomic
    path, or bit rot) must never take serving down: it degrades to an
    empty cache — untuned defaults — with ONE warning per file snapshot
    (the mtime memo dedups it; the next ``_save_entry`` rewrites the
    file whole, which is the repair)."""
    p = Path(path) if path is not None else default_cache_path()
    # chaos hook: an armed cache_corrupt fault truncates the file first,
    # exercising exactly the recovery path below (tests/chaos CI)
    if p.exists():
        from ..runtime import faults as _faults
        _faults.corrupt_file("gram.autotune.cache", p)
    try:
        mtime = p.stat().st_mtime_ns
    except OSError:
        return {}
    memo_key = (str(p), mtime)
    if memo_key in _memo:
        return _memo[memo_key]
    try:
        with open(p) as f:
            raw = json.load(f)
        entries = raw.get("entries", {}) if isinstance(raw, dict) else {}
        # pre-v2 files keyed without the jax version — every entry is
        # potentially a stale winner from another jax; drop them all and
        # let autotune repopulate (the migration path)
        if not isinstance(raw, dict) or raw.get("version", 0) \
                < _CACHE_VERSION:
            _cache_event("stale_dropped", len(entries) or 1)
            entries = {}
    except OSError:
        entries = {}
    except ValueError as e:
        import warnings
        warnings.warn(
            f"autotune cache {p} is corrupt ({e}); ignoring it and "
            f"serving with untuned defaults — the next autotune run "
            f"rewrites it", stacklevel=2)
        _cache_event("corrupt")
        entries = {}
    _memo.clear()           # one live file snapshot is enough
    _memo[memo_key] = entries
    return entries


def _save_entry(key: str, entry: dict, path: Optional[os.PathLike]) -> Path:
    p = Path(path) if path is not None else default_cache_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    entries = dict(load_cache(p))
    entries[key] = entry
    tmp = p.with_suffix(".tmp")
    with open(tmp, "w") as f:
        json.dump({"version": _CACHE_VERSION, "entries": entries}, f,
                  indent=1, sort_keys=True)
    os.replace(tmp, p)
    _cache_event("persist")
    return p


def invalidate(m: int, n: int, *, dtype: str = "float32",
               kind: str = "ata", backend: Optional[str] = None,
               min_side: int = 32,
               cache_path: Optional[os.PathLike] = None) -> bool:
    """Drop the persisted winner for the bucket containing (m, n) —
    the action a cost-model drift finding maps to
    (``GramEngine.invalidate_drifted``): the entry was a measurement of
    conditions that no longer hold, so the next autotune re-measures.
    Returns whether an entry existed."""
    backend = backend or jax.default_backend()
    M, N = bucket_shape(m, n, min_side=min_side)
    key = _key(backend, str(dtype), kind, M, N)
    p = Path(cache_path) if cache_path is not None else default_cache_path()
    entries = dict(load_cache(p))
    if key not in entries:
        return False
    del entries[key]
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.with_suffix(".tmp")
    with open(tmp, "w") as f:
        json.dump({"version": _CACHE_VERSION, "entries": entries}, f,
                  indent=1, sort_keys=True)
    os.replace(tmp, p)
    _cache_event("invalidate")
    return True


def lookup(m: int, n: int, *, dtype: str = "float32", kind: str = "ata",
           backend: Optional[str] = None, min_side: int = 32,
           cache_path: Optional[os.PathLike] = None) -> Optional[dict]:
    """Winner entry for the bucket containing (m, n), or None.
    ``min_side`` must match the bucketing used when tuning (the engine
    threads its ``min_bucket`` here)."""
    backend = backend or jax.default_backend()
    M, N = bucket_shape(m, n, min_side=min_side)
    hit = load_cache(cache_path).get(_key(backend, str(dtype), kind, M, N))
    _cache_event("hit" if hit is not None else "miss")
    return hit


def resolve_block_defaults(kind: str, m: int, n: int, dtype,
                           **blocks) -> dict:
    """Fill ``None`` block sizes from the autotune cache (256 fallback).

    The hook through which ``kernels/ops.py`` / ``core`` consult the
    cache: explicit caller values always win; a missing or unreadable
    cache file (``load_cache``) degrades to the historical hardcoded
    default.  Only
    ``mode="fused"`` winners carry meaningful block sizes (blocking is a
    fused-kernel knob — reference entries hold placeholders), so other
    entries are ignored here.
    """
    if all(v is not None for v in blocks.values()):
        return blocks
    best = None
    if kind in ("ata", "matmul", "ata_bwd", "aat", "rank_k"):
        best = lookup(m, n, dtype=jnp.dtype(dtype).name, kind=kind)
        if best is not None and best.get("mode") != "fused":
            best = None
    return {k: int(v if v is not None
                   else (best or {}).get(k) or DEFAULT_BLOCK)
            for k, v in blocks.items()}


# ---------------------------------------------------------------------------
# The search
# ---------------------------------------------------------------------------

def _build_runner(M: int, N: int, dtype, cand: dict, interpret,
                  kind: str = "ata"):
    from ..core.ata import ata

    galg = cand.get("gram", "strassen")
    # §16 perf knobs — fused-kernel only; the reference recursion takes
    # pipeline_depth=None (a no-op there) and quantizes via ata()'s
    # operand_dtype oracle path.
    pdepth = cand.get("pipeline_depth")
    odtype = cand.get("operand_dtype")
    if kind == "aat":
        def fn(a):
            return ata(a, gram_of="rows", levels=cand["levels"],
                       variant=cand["variant"], gram=galg,
                       mode=cand["mode"], block=cand["bk"],
                       out_dtype=jnp.float32, interpret=interpret,
                       pipeline_depth=pdepth, operand_dtype=odtype)
        return jax.jit(fn)

    if kind == "rank_k":
        # fused: the accumulating kernel on a live stack; reference: the
        # status-quo element-packed streamed update it replaces.
        if cand["mode"] == "fused":
            from ..kernels.ops import rank_k_update

            def fn(a):
                t = -(-N // cand["bn"])
                stack = jnp.zeros((t * (t + 1) // 2 * cand["bn"],
                                   cand["bn"]), jnp.float32)
                return rank_k_update(stack, a, levels=cand["levels"],
                                     variant=cand["variant"], gram=galg,
                                     bk=cand["bk"], interpret=interpret,
                                     donate=False, pipeline_depth=pdepth,
                                     operand_dtype=odtype)
            return jax.jit(fn)

        from . import stream as _stream

        def fn(a):
            state = _stream.init(N)
            return _stream.update(state, a, levels=cand["levels"],
                                  variant=cand["variant"], mode="auto",
                                  interpret=interpret).packed
        return fn                      # stream.update jits internally

    if kind == "ata_bwd":
        # time jax.grad through the fused forward; the candidate mode
        # picks the VJP engine ("reference" = the dense-dot baseline).
        bwd = "fused" if cand["mode"] == "fused" else "dense"

        def fn(a):
            return jax.grad(lambda x: ata(
                x, levels=cand["levels"], variant=cand["variant"],
                gram=galg, mode="fused", bwd=bwd, block=cand["bk"],
                out_dtype=jnp.float32, interpret=interpret,
                pipeline_depth=pdepth).sum())(a)
        return jax.jit(fn)

    def fn(a):
        return ata(a, levels=cand["levels"], variant=cand["variant"],
                   gram=galg, mode=cand["mode"], block=cand["bk"],
                   out_dtype=jnp.float32, interpret=interpret,
                   pipeline_depth=pdepth, operand_dtype=odtype)
    return jax.jit(fn)


def _time_candidate(fn, a, iters: int = 2) -> float:
    jax.block_until_ready(fn(a))            # compile + warm
    best = math.inf
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(a))
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(m: int, n: int, *, dtype: str = "float32", kind: str = "ata",
             backend: Optional[str] = None, measure: bool = False,
             top_k: int = 3, blocks=(128, 256, 512), levels=(0, 1, 2),
             modes=("fused", "reference"), min_side: int = 32,
             pipeline_depths=(1, 2), operand_dtypes=(None,),
             cache_path: Optional[os.PathLike] = None,
             interpret: Optional[bool] = None,
             refresh: bool = False) -> dict:
    """Pick (and persist) the best config for the bucket containing (m, n).

    Model-only by default: ranks *fused* candidates by ``model_score``
    (the model is exact for the fused kernel; the reference estimate is a
    heuristic, so it never decides a contest).  With ``measure=True`` the
    top-K fused candidates plus the reference candidates are compiled and
    timed on the current device and wall clock picks the winner.  Returns
    the cached entry when one exists unless ``refresh``.

    ``kind="ata_bwd"`` tunes the *backward*: candidates are scored with
    ``ata_bwd_traffic_model`` (mode "fused" = the packed-cotangent symm
    kernel, "reference" = the dense-dot ``A (S + S^t)`` baseline) and
    measured — when requested — as ``jax.grad`` wall clock through the
    fused forward with the corresponding ``bwd=`` engine.
    """
    backend = backend or jax.default_backend()
    M, N = bucket_shape(m, n, min_side=min_side)
    key = _key(backend, str(dtype), kind, M, N)
    if not refresh:
        hit = load_cache(cache_path).get(key)
        if hit is not None:
            return hit

    in_bytes = jnp.dtype(dtype).itemsize
    cands = candidate_space(M, N, backend=backend, blocks=blocks,
                            levels=levels, modes=modes, kind=kind,
                            pipeline_depths=pipeline_depths,
                            operand_dtypes=operand_dtypes)
    score = lambda c: model_score(M, N, c, in_bytes=in_bytes,  # noqa: E731
                                  kind=kind)
    fused = sorted((c for c in cands if c["mode"] == "fused"), key=score)
    refs = sorted((c for c in cands if c["mode"] == "reference"), key=score)
    winner, measured = (fused or refs)[0], None
    if measure:
        a = jax.random.normal(jax.random.PRNGKey(0), (M, N)).astype(dtype)
        timed = []
        for cand in fused[:top_k] + refs:
            try:
                timed.append((_time_candidate(
                    _build_runner(M, N, dtype, cand, interpret, kind), a),
                    cand))
            except Exception:
                continue            # unrunnable candidate (e.g. VMEM clamp)
        if timed:
            measured, winner = min(timed, key=lambda tc: tc[0])

    entry = {**winner,
             "model_bytes": model_score(M, N, winner, in_bytes=in_bytes,
                                        kind=kind),
             "measured_s": measured,
             "source": "measured" if measured is not None else "model",
             # introspection copies of what the key already pins: the
             # (jax, backend) pair this winner was tuned under
             "jax": jax.__version__,
             "backend": backend}
    _save_entry(key, entry, cache_path)
    return entry
