"""Public jit'd wrappers around the Pallas kernels.

Handle arbitrary shapes by zero-padding to block multiples (exact for
matmul/syrk/transpose/combine) and slicing back. ``interpret`` defaults to
True off-TPU so the same call sites validate on CPU and run compiled on TPU.

Block sizes default to ``None`` = "consult the gram autotune cache"
(``gram/autotune.py``; winners persisted per shape bucket under
``artifacts/autotune/``), falling back to 256 when untuned.  Explicit
block arguments bypass the cache entirely.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from . import matmul as _matmul
from . import syrk as _syrk
from . import combine as _combine
from . import transpose as _transpose
from ..core.symmetry import unpack_tril_blocks

_log = logging.getLogger(__name__)

# Backends with a native Pallas lowering for the pltpu primitives the
# kernels use (scalar prefetch, DMA semaphores).  GPU has no Triton port
# of those yet, so off-TPU backends run the interpreter.
_COMPILED_BACKENDS = ("tpu",)

# (site, backend, decision) triples already logged — the decision is
# per-call-site but only logs once per distinct combination, so hot
# serving loops don't spam.
_INTERPRET_LOGGED: set = set()


def _auto_interpret(interpret, site=None):
    """Resolve the ``interpret`` knob for one kernel call site.

    An explicit argument wins; otherwise the platform decides: compiled
    on TPU, interpret on CPU/GPU where the kernels are unsupported.
    Each distinct (site, backend) decision is logged once.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    decision = backend not in _COMPILED_BACKENDS
    key = (site, backend, decision)
    if key not in _INTERPRET_LOGGED:
        _INTERPRET_LOGGED.add(key)
        _log.info("pallas interpret=%s at %s [backend=%s: %s]",
                  decision, site or "<unnamed site>", backend,
                  "kernel unsupported off-TPU" if decision
                  else "native pallas lowering")
    return decision


def _resolve_blocks(kind, m, n, dtype, **blocks):
    """Fill ``None`` block sizes from the gram autotune cache
    (``artifacts/autotune/gram_autotune.json``; see gram/autotune.py)
    instead of the historical hardcoded 256s.  Explicit values win; a
    missing or unreadable cache file degrades to 256 (inside
    ``autotune.load_cache``)."""
    if all(v is not None for v in blocks.values()):
        return blocks
    from ..gram.autotune import resolve_block_defaults
    return resolve_block_defaults(kind, m, n, dtype, **blocks)


def _pad_to(x, mults):
    pads = [(-d) % m for d, m in zip(x.shape, mults)]
    if any(pads):
        x = jnp.pad(x, [(0, p) for p in pads])
    return x


def matmul(a, b, *, bm=None, bk=None, bn=None, interpret=None):
    """``a @ b`` via the tiled MXU kernel; any shapes, any float dtype.
    Block sizes default to the autotune-cache winner for this shape
    bucket (256 when untuned)."""
    bs = _resolve_blocks("matmul", a.shape[0], b.shape[1], a.dtype,
                         bm=bm, bk=bk, bn=bn)
    return _matmul_jit(a, b, bm=bs["bm"], bk=bs["bk"], bn=bs["bn"],
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def _matmul_jit(a, b, *, bm, bk, bn, interpret=None):
    interpret = _auto_interpret(interpret)
    m, n = a.shape[0], b.shape[1]
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    out = _matmul.matmul_padded(ap, bp, bm=bm, bk=bk, bn=bn,
                                interpret=interpret)
    return out[:m, :n]


def syrk_packed(a, *, bk=None, bn=None, interpret=None):
    """Packed lower-tri block stack of ``a.T @ a`` (padded N -> caller keeps
    block layout; use :func:`syrk` for a dense result at original size)."""
    bs = _resolve_blocks("ata", a.shape[0], a.shape[1], a.dtype, bk=bk, bn=bn)
    return _syrk_packed_jit(a, bk=bs["bk"], bn=bs["bn"], interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bk", "bn", "interpret"))
def _syrk_packed_jit(a, *, bk, bn, interpret=None):
    interpret = _auto_interpret(interpret)
    ap = _pad_to(a, (bk, bn))
    return _syrk.syrk_packed(ap, bk=bk, bn=bn, interpret=interpret)


def syrk(a, *, bk=None, bn=None, symmetrize=False, interpret=None):
    """Dense ``tril(a.T @ a)`` (or full symmetric) via the packed kernel."""
    bs = _resolve_blocks("ata", a.shape[0], a.shape[1], a.dtype, bk=bk, bn=bn)
    return _syrk_jit(a, bk=bs["bk"], bn=bs["bn"], symmetrize=symmetrize,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bk", "bn", "symmetrize", "interpret"))
def _syrk_jit(a, *, bk, bn, symmetrize=False, interpret=None):
    interpret = _auto_interpret(interpret)
    n = a.shape[1]
    ap = _pad_to(a, (bk, bn))
    packed = _syrk.syrk_packed(ap, bk=bk, bn=bn, interpret=interpret)
    dense = unpack_tril_blocks(packed, ap.shape[1], bn, symmetrize=symmetrize)
    if not symmetrize:
        # diagonal blocks are computed full (bn x bn) — drop their upper halves
        dense = jnp.tril(dense)
    return dense[:n, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def strassen_combine(m1, m2, m3, m4, m5, m6, m7, *, bm=256, bn=256,
                     interpret=None):
    """Fused Strassen recombination -> (c11, c12, c21, c22).
    (No autotune-cache consultation: recombination blocking is not part
    of the tuned search space.)"""
    interpret = _auto_interpret(interpret)
    m, n = m1.shape
    ms = [_pad_to(x, (bm, bn)) for x in (m1, m2, m3, m4, m5, m6, m7)]
    outs = _combine.strassen_combine(*ms, bm=bm, bn=bn, interpret=interpret)
    return tuple(o[:m, :n] for o in outs)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def transpose(a, *, bm=256, bn=256, interpret=None):
    """``a.T`` via the tiled transpose kernel."""
    interpret = _auto_interpret(interpret)
    m, n = a.shape
    ap = _pad_to(a, (bm, bn))
    return _transpose.transpose_padded(ap, bm=bm, bn=bn,
                                       interpret=interpret)[:n, :m]


# ---------------------------------------------------------------------------
# Kernel-backed base cases for the core recursion (TPU hot path).
# ---------------------------------------------------------------------------

def pallas_base_matmul(bm=None, bk=None, bn=None, interpret=None):
    """base_matmul hook for repro.core.strassen_matmul."""
    def base(a, b):
        return matmul(a, b, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return base


def pallas_base_syrk(bk=None, bn=None, interpret=None):
    """base_syrk hook for repro.core.ata (lower-tri-only leaf gram)."""
    def base(a):
        return syrk(a, bk=bk, bn=bn, symmetrize=False, interpret=interpret)
    return base


# ---------------------------------------------------------------------------
# Fused schedule pipeline (core/schedule.py -> kernels/strassen_fused.py):
# the whole level-capped ATA / Strassen recursion in ONE pallas_call, no
# per-level HBM temporaries.  These are the jit'd public entry points; the
# core recursion routes here via ata(..., mode="fused").
# ---------------------------------------------------------------------------

def ata_fused(a, *, levels=2, variant="strassen", gram="strassen", bk=None,
              bn=None, out_dtype=None, interpret=None, bwd="fused",
              pipeline_depth=None, operand_dtype=None, acc_dtype=None,
              sr_seed=None):
    """Dense ``tril(a.T @ a)`` via the fused leaf-task schedule.
    ``bk``/``bn`` default to the autotune-cache winner for this shape
    bucket (256 when untuned).  ``gram`` picks the registered symmetric
    decomposition (``leaf_ir.registered_gram_algebras()``; ``"dps"`` is
    the 5-product scheme).  ``bwd`` picks the VJP engine: ``"fused"``
    (packed-cotangent symm schedule, the default) or ``"dense"`` (the
    classical dense-dot baseline).

    Perf/precision knobs (DESIGN.md §16): ``pipeline_depth`` (revolving
    DMA buffers, None = backend default), ``operand_dtype`` (fp8/bf16
    operand tiles, fp32 accumulation), ``acc_dtype`` (VMEM accumulator
    storage) and ``sr_seed`` (stochastic-rounded bf16 output)."""
    bs = _resolve_blocks("ata", a.shape[0], a.shape[1], a.dtype, bk=bk, bn=bn)
    return _ata_fused_jit(a, levels=levels, variant=variant, gram=gram,
                          bk=bs["bk"], bn=bs["bn"], out_dtype=out_dtype,
                          interpret=interpret, bwd=bwd,
                          pipeline_depth=pipeline_depth,
                          operand_dtype=operand_dtype, acc_dtype=acc_dtype,
                          sr_seed=sr_seed)


@functools.partial(jax.jit, static_argnames=(
    "levels", "variant", "gram", "bk", "bn", "out_dtype", "interpret",
    "bwd", "pipeline_depth", "operand_dtype", "acc_dtype", "sr_seed"))
def _ata_fused_jit(a, *, levels, variant, gram="strassen", bk, bn,
                   out_dtype=None, interpret=None, bwd="fused",
                   pipeline_depth=None, operand_dtype=None, acc_dtype=None,
                   sr_seed=None):
    from . import strassen_fused as _sf
    return _sf.fused_ata(a, levels=levels, variant=variant, gram=gram,
                         bk=bk, bn=bn, out_dtype=out_dtype,
                         interpret=_auto_interpret(interpret,
                                                   site="ops.ata_fused"),
                         bwd=bwd, pipeline_depth=pipeline_depth,
                         operand_dtype=operand_dtype, acc_dtype=acc_dtype,
                         sr_seed=sr_seed)


def ata_fused_packed(a, *, levels=2, variant="strassen", gram="strassen",
                     bk=None, bn=None, out_dtype=None, interpret=None,
                     bwd="fused", pipeline_depth=None, operand_dtype=None,
                     acc_dtype=None, sr_seed=None):
    """Packed lower-tri block stack of ``a.T @ a`` via the fused schedule
    (upper-triangular blocks are never computed or written).
    Differentiable: the custom VJP consumes the *packed* cotangent
    directly (``bwd="fused"``) — no dense n^2 buffer in the backward.
    Same perf/precision knobs as :func:`ata_fused`."""
    bs = _resolve_blocks("ata", a.shape[0], a.shape[1], a.dtype, bk=bk, bn=bn)
    return _ata_fused_packed_jit(a, levels=levels, variant=variant,
                                 gram=gram, bk=bs["bk"], bn=bs["bn"],
                                 out_dtype=out_dtype, interpret=interpret,
                                 bwd=bwd, pipeline_depth=pipeline_depth,
                                 operand_dtype=operand_dtype,
                                 acc_dtype=acc_dtype, sr_seed=sr_seed)


@functools.partial(jax.jit, static_argnames=(
    "levels", "variant", "gram", "bk", "bn", "out_dtype", "interpret",
    "bwd", "pipeline_depth", "operand_dtype", "acc_dtype", "sr_seed"))
def _ata_fused_packed_jit(a, *, levels, variant, gram="strassen", bk, bn,
                          out_dtype=None, interpret=None, bwd="fused",
                          pipeline_depth=None, operand_dtype=None,
                          acc_dtype=None, sr_seed=None):
    from . import strassen_fused as _sf
    packed, _ = _sf.fused_ata_packed(
        a, levels=levels, variant=variant, gram=gram, bk=bk, bn=bn,
        out_dtype=out_dtype,
        interpret=_auto_interpret(interpret, site="ops.ata_fused_packed"),
        bwd=bwd, pipeline_depth=pipeline_depth, operand_dtype=operand_dtype,
        acc_dtype=acc_dtype, sr_seed=sr_seed)
    return packed


def symm_matmul(x, s_packed, *, levels=2, variant="strassen", bm=None,
                diag_sym=False, out_dtype=None, interpret=None,
                pipeline_depth=None, operand_dtype=None, acc_dtype=None):
    """``x @ Sym`` where Sym is given only as its packed lower-triangular
    tile stack (``syrk_packed`` / ``ata_fused_packed`` layout; the tile
    edge is read off the stack) — the symm-schedule kernel that powers
    the fused Gram backward.  ``diag_sym=True`` computes
    ``x @ (S + S^t)`` instead (the VJP operand)."""
    bs = _resolve_blocks("ata", x.shape[0], x.shape[1], x.dtype, bm=bm)
    return _symm_matmul_jit(x, s_packed, levels=levels, variant=variant,
                            bm=bs["bm"], diag_sym=diag_sym,
                            out_dtype=out_dtype, interpret=interpret,
                            pipeline_depth=pipeline_depth,
                            operand_dtype=operand_dtype, acc_dtype=acc_dtype)


@functools.partial(jax.jit, static_argnames=(
    "levels", "variant", "bm", "diag_sym", "out_dtype", "interpret",
    "pipeline_depth", "operand_dtype", "acc_dtype"))
def _symm_matmul_jit(x, s_packed, *, levels, variant, bm, diag_sym,
                     out_dtype=None, interpret=None, pipeline_depth=None,
                     operand_dtype=None, acc_dtype=None):
    from . import strassen_fused as _sf
    return _sf.fused_symm_matmul(
        x, s_packed, levels=levels, variant=variant, bm=bm,
        diag_sym=diag_sym, out_dtype=out_dtype,
        interpret=_auto_interpret(interpret, site="ops.symm_matmul"),
        pipeline_depth=pipeline_depth, operand_dtype=operand_dtype,
        acc_dtype=acc_dtype)


def matmul_fused(a, b, *, levels=2, variant="strassen", bm=None, bk=None,
                 bn=None, trans_a=False, trans_b=False, out_dtype=None,
                 interpret=None, bwd="fused", pipeline_depth=None,
                 operand_dtype=None, acc_dtype=None):
    """``op(a) @ op(b)`` via the fused Strassen program kernel;
    ``trans_a``/``trans_b`` transpose an operand *through the index
    maps* — no transposed HBM copy (the distributed ring/2.5D block
    tasks route here).  ``bwd="fused"`` (default) runs both VJP products
    through the same program with the transposes likewise folded."""
    m = a.shape[1] if trans_a else a.shape[0]
    n = b.shape[0] if trans_b else b.shape[1]
    bs = _resolve_blocks("matmul", m, n, a.dtype, bm=bm, bk=bk, bn=bn)
    return _matmul_fused_jit(a, b, levels=levels, variant=variant,
                             bm=bs["bm"], bk=bs["bk"], bn=bs["bn"],
                             trans_a=trans_a, trans_b=trans_b,
                             out_dtype=out_dtype, interpret=interpret,
                             bwd=bwd, pipeline_depth=pipeline_depth,
                             operand_dtype=operand_dtype,
                             acc_dtype=acc_dtype)


@functools.partial(jax.jit, static_argnames=(
    "levels", "variant", "bm", "bk", "bn", "trans_a", "trans_b",
    "out_dtype", "interpret", "bwd", "pipeline_depth", "operand_dtype",
    "acc_dtype"))
def _matmul_fused_jit(a, b, *, levels, variant, bm, bk, bn, trans_a=False,
                      trans_b=False, out_dtype=None, interpret=None,
                      bwd="fused", pipeline_depth=None, operand_dtype=None,
                      acc_dtype=None):
    from . import strassen_fused as _sf
    return _sf.fused_matmul(a, b, levels=levels, variant=variant, bm=bm,
                            bk=bk, bn=bn, trans_a=trans_a, trans_b=trans_b,
                            out_dtype=out_dtype,
                            interpret=_auto_interpret(
                                interpret, site="ops.matmul_fused"),
                            bwd=bwd, pipeline_depth=pipeline_depth,
                            operand_dtype=operand_dtype,
                            acc_dtype=acc_dtype)


def aat_fused(a, *, levels=2, variant="strassen", gram="strassen", bm=None,
              bk=None, out_dtype=None, interpret=None, pipeline_depth=None,
              operand_dtype=None, acc_dtype=None, sr_seed=None):
    """Dense ``tril(a @ a.T)`` — the Arrigoni-Massini row gram
    (``ata(x, gram_of="rows")``) via the same leaf-program executor; the
    transpose of ``a`` never exists in HBM."""
    bs = _resolve_blocks("aat", a.shape[0], a.shape[1], a.dtype,
                         bm=bm, bk=bk)
    return _aat_fused_jit(a, levels=levels, variant=variant, gram=gram,
                          bm=bs["bm"], bk=bs["bk"], out_dtype=out_dtype,
                          interpret=interpret,
                          pipeline_depth=pipeline_depth,
                          operand_dtype=operand_dtype, acc_dtype=acc_dtype,
                          sr_seed=sr_seed)


@functools.partial(jax.jit, static_argnames=(
    "levels", "variant", "gram", "bm", "bk", "out_dtype", "interpret",
    "pipeline_depth", "operand_dtype", "acc_dtype", "sr_seed"))
def _aat_fused_jit(a, *, levels, variant, gram="strassen", bm, bk,
                   out_dtype=None, interpret=None, pipeline_depth=None,
                   operand_dtype=None, acc_dtype=None, sr_seed=None):
    from . import strassen_fused as _sf
    return _sf.fused_aat(a, levels=levels, variant=variant, gram=gram,
                         bm=bm, bk=bk, out_dtype=out_dtype,
                         interpret=_auto_interpret(interpret,
                                                   site="ops.aat_fused"),
                         pipeline_depth=pipeline_depth,
                         operand_dtype=operand_dtype, acc_dtype=acc_dtype,
                         sr_seed=sr_seed)


def aat_fused_packed(a, *, levels=2, variant="strassen", gram="strassen",
                     bm=None, bk=None, out_dtype=None, interpret=None,
                     pipeline_depth=None, operand_dtype=None,
                     acc_dtype=None, sr_seed=None):
    """Packed lower-tri block stack of ``a @ a.T`` (row-gram dual of
    :func:`ata_fused_packed`)."""
    bs = _resolve_blocks("aat", a.shape[0], a.shape[1], a.dtype,
                         bm=bm, bk=bk)
    return _aat_fused_packed_jit(a, levels=levels, variant=variant,
                                 gram=gram, bm=bs["bm"], bk=bs["bk"],
                                 out_dtype=out_dtype, interpret=interpret,
                                 pipeline_depth=pipeline_depth,
                                 operand_dtype=operand_dtype,
                                 acc_dtype=acc_dtype, sr_seed=sr_seed)


@functools.partial(jax.jit, static_argnames=(
    "levels", "variant", "gram", "bm", "bk", "out_dtype", "interpret",
    "pipeline_depth", "operand_dtype", "acc_dtype", "sr_seed"))
def _aat_fused_packed_jit(a, *, levels, variant, gram="strassen", bm, bk,
                          out_dtype=None, interpret=None,
                          pipeline_depth=None, operand_dtype=None,
                          acc_dtype=None, sr_seed=None):
    from . import strassen_fused as _sf
    packed, _ = _sf.fused_aat_packed(
        a, levels=levels, variant=variant, gram=gram, bm=bm, bk=bk,
        out_dtype=out_dtype,
        interpret=_auto_interpret(interpret, site="ops.aat_fused_packed"),
        pipeline_depth=pipeline_depth, operand_dtype=operand_dtype,
        acc_dtype=acc_dtype, sr_seed=sr_seed)
    return packed


def rank_k_update(c_stack, a, *, levels=2, variant="strassen",
                  gram="strassen", gram_of="cols", beta=1.0, alpha=1.0,
                  bk=None, out_dtype=None, interpret=None, donate=True,
                  pipeline_depth=None, operand_dtype=None, acc_dtype=None):
    """``C <- beta C + alpha tril(a.T @ a)`` (``gram_of="rows"``:
    ``tril(a @ a.T)``) on a packed tile stack in ONE kernel — the
    accumulating (rank-k) program.  The stack, scaled by ``beta``, seeds
    the kernel's VMEM accumulator and the result is written over it, so
    an update materializes no delta stack and no unpack/gather;
    ``beta``/``alpha`` are run-time scalars (the defaults give ``C +=
    tril(...)``).  With ``donate`` (default) the state buffer is donated
    so XLA updates it in place at the jit boundary."""
    bs = _resolve_blocks("rank_k", a.shape[0], a.shape[1], a.dtype, bk=bk)
    fn = _rank_k_jit_donated if donate else _rank_k_jit
    return fn(c_stack, a, beta, alpha, levels=levels, variant=variant,
              gram=gram, gram_of=gram_of, bk=bs["bk"], out_dtype=out_dtype,
              interpret=interpret, pipeline_depth=pipeline_depth,
              operand_dtype=operand_dtype, acc_dtype=acc_dtype)


def _rank_k_impl(c_stack, a, beta, alpha, *, levels, variant,
                 gram="strassen", gram_of="cols", bk, out_dtype=None,
                 interpret=None, pipeline_depth=None, operand_dtype=None,
                 acc_dtype=None):
    from . import strassen_fused as _sf
    return _sf.fused_rank_k_update(
        c_stack, a, levels=levels, variant=variant, gram=gram,
        gram_of=gram_of, beta=beta, alpha=alpha, bk=bk, out_dtype=out_dtype,
        interpret=_auto_interpret(interpret, site="ops.rank_k_update"),
        pipeline_depth=pipeline_depth, operand_dtype=operand_dtype,
        acc_dtype=acc_dtype)


_rank_k_static = ("levels", "variant", "gram", "gram_of", "bk", "out_dtype",
                  "interpret", "pipeline_depth", "operand_dtype",
                  "acc_dtype")
_rank_k_jit = jax.jit(_rank_k_impl, static_argnames=_rank_k_static)
_rank_k_jit_donated = jax.jit(_rank_k_impl, static_argnames=_rank_k_static,
                              donate_argnums=(0,))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_kv", "interpret"))
def flash_mha(q, k, v, *, causal=True, window=0, softcap=0.0,
              block_q=512, block_kv=512, interpret=None):
    """FlashAttention with (B, S, H, D) layout + arbitrary seq lengths
    (pads to block multiples; padded kv is masked by causality/neg-inf)."""
    from . import flash_attention as _fa
    interpret = _auto_interpret(interpret)
    b, sq, h, d = q.shape
    skv = k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(block_q, max(sq, 16))
    bk = min(block_kv, max(skv, 16))
    pq, pk = (-sq) % bq, (-skv) % bk
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        # pad kv with zeros; give padded keys -inf via a window trick is
        # not needed: padded q rows are sliced away, and padded kv columns
        # are masked because causal q_pos < kv_pos for all real q ... only
        # true for causal; for non-causal we mask via window=skv when
        # padding. Handled by masking below through kv_len emulation:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        if not causal:
            raise NotImplementedError(
                "non-causal flash with ragged kv: pad kv to block multiple "
                "at the call site")
    o = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                            softcap=softcap, block_q=bq, block_kv=bk,
                            interpret=interpret)
    return o[:, :, :sq].transpose(0, 2, 1, 3)
