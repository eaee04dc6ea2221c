"""The generic Pallas executor for compiled leaf programs.

One kernel, one ``pallas_call`` site, every fused variant.  PRs 1-4 grew
three hand-specialized executors (forward ATA, symm backward, trans_a /
trans_b matmul) that differed only in grid decode, index-map axis roles
and which side transposes in VMEM.  This module rewrites them as a
single executor driven by the :mod:`repro.core.leaf_ir` IR: a
``LeafProgram`` (kind x levels x algebra table) is bound to tile sizes
(:class:`_Spec`), lowered to int32 scalar-prefetch tables, and executed
by ONE scalar-prefetch ``pallas_call`` whose grid enumerates
``(output tile, contribution slot, K block)``.  Per grid step the kernel

  1. gathers up to ``max_terms`` stored tiles per side straight from HBM
     (the prefetched tables drive the BlockSpec index maps — pad /
     concatenate / transpose copies of the reference recursions become
     index arithmetic),
  2. forms the +-1-signed operand sums tile-wise in VMEM, applying the
     per-term tri-mirror transposes (packed symm operand) and the
     whole-side transposes (ATA's left, AAT's right, trans_a/trans_b),
  3. runs the leaf product on the MXU into an fp32 VMEM accumulator that
     lives across the whole (contribution, K) sweep of one output tile
     — seeded from the incoming packed stack for accumulating (rank-k)
     programs instead of zero,
  4. writes each output tile to HBM exactly once — packed
     lower-triangular stack for gram kinds, dense grid otherwise.

Because the planner/executor split is IR-shaped, the two programs the
old stacks could not express fall out of the same machinery:

* ``aat`` — C = tril(A A^t), the Arrigoni-Massini 2021 row-gram
  recursion (:func:`fused_aat` / :func:`fused_aat_packed`, surfaced as
  ``ata(x, gram_of="rows")``): the transpose of A never exists in HBM.
* ``rank_k`` — C <- beta C + alpha tril(A^t A), or of A A^t with
  ``gram_of="rows"`` (:func:`fused_rank_k_update`): the running packed
  stack, scaled by a run-time ``beta``, seeds the accumulator, so
  streamed Gram chunks (``gram/stream.py``) and Shampoo's statistics
  EMA (``optim/shampoo.py``) never re-materialize a per-update delta.

Autodiff (DESIGN.md §11) is unchanged in spirit: custom VJPs route every
backward through the same executor (symm schedule for the gram kinds,
transpose-folded matmul programs for matmul), with ``bwd="dense"``
keeping the dense-dot baselines selectable for benchmarking.

The analytic HBM traffic model is likewise IR-driven: :func:`_traffic`
scores a bound :class:`_Spec` (reads = the real tile fetches of the
depth>=2 walk, with the padded grid beside them; writes = one store per
output tile), and
the per-kind models (``ata_traffic_model`` etc.) are thin geometry
wrappers over it — the model shares the executor's binding code, so it
cannot drift from the kernel's clamping/padding.
"""
from __future__ import annotations

import functools
import math
import operator
import warnings
from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import leaf_ir
from ..core.ata import ata_levels_for
from ..core.leaf_ir import LeafProgram, compile_program
from ..core.symmetry import unpack_tril_blocks
from .ops import _auto_interpret
from .syrk import _tri_decode

__all__ = ["fused_ata", "fused_ata_packed", "fused_aat", "fused_aat_packed",
           "fused_matmul", "fused_symm_matmul", "fused_rank_k_update",
           "ata_traffic_model", "aat_traffic_model", "ata_bwd_traffic_model",
           "rank_k_traffic_model", "matmul_traffic_model", "auto_levels",
           "stochastic_round_bf16"]


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# VMEM guard: the kernel gathers 2 * max_terms input tiles per grid step
# (double-buffered by the pipeline).  Each Strassen level doubles the
# operand fan-in (Winograd can quadruple it), so deep programs are clamped
# to keep the working set well under per-core VMEM: 2*8 tiles of 256x256
# fp32 = 4 MB single-buffered.
MAX_OPERAND_TERMS = 8

# Revolving-buffer depth cap: each extra slot holds another 2*max_terms
# operand tiles in VMEM, so depth 4 at 256x256 fp32 tiles is already
# 16 MB of ring — past the point of diminishing overlap returns.
MAX_PIPELINE_DEPTH = 4

# Operand-tile storage dtypes the executor will quantize to.  fp8 tiles
# halve (vs bf16) / quarter (vs fp32) the DMA traffic per term while the
# accumulation stays in the fp32 VMEM scratch — the serving-grade Gram
# trade (DESIGN.md §16).
_SUPPORTED_OPERAND_DTYPES = ("float8_e4m3fn", "float8_e5m2", "bfloat16",
                             "float16", "float32", "float64")

# (kind, variant, requested, clamped) combinations already warned about —
# the clamp silently changing the schedule depth bit users before, so it
# warns exactly once per distinct clamp.
_CLAMP_WARNED: set = set()


def _canon_dtype(dt):
    """Optional dtype-like -> canonical name string (or None): the
    hashable form threaded through custom-VJP nondiff argnums."""
    return None if dt is None else jnp.dtype(dt).name


def _resolve_operand_dtype(operand_dtype):
    name = _canon_dtype(operand_dtype)
    if name is not None and name not in _SUPPORTED_OPERAND_DTYPES:
        raise ValueError(
            f"operand_dtype={name!r} is not a supported operand-tile "
            f"storage dtype; pick one of {_SUPPORTED_OPERAND_DTYPES}")
    return name


def _resolve_acc_dtype(acc_dtype):
    name = "float32" if acc_dtype is None else jnp.dtype(acc_dtype).name
    if name not in ("float32", "bfloat16", "float64"):
        raise ValueError(f"acc_dtype={name!r}: the VMEM accumulator must "
                         "be float32 (default), bfloat16 or float64")
    return name


def _resolve_pipeline_depth(pipeline_depth, interpret) -> int:
    """Resolve the ``pipeline_depth`` knob.

    ``None`` picks the backend default: 2 (double buffering — prefetch
    the next contribution's operand tiles while the current MXU work
    runs) for compiled kernels, 1 in interpret mode, where the emulator
    runs DMAs synchronously and revolving buffers only add bookkeeping.
    Explicit values are always honored (parity tests force 2/3 under
    interpret).
    """
    if pipeline_depth is None:
        return 1 if interpret else 2
    depth = int(pipeline_depth)
    if not 1 <= depth <= MAX_PIPELINE_DEPTH:
        raise ValueError(
            f"pipeline_depth must be in [1, {MAX_PIPELINE_DEPTH}], got "
            f"{pipeline_depth} (each slot rings 2*{MAX_OPERAND_TERMS} "
            "operand tiles in VMEM)")
    return depth


def _resolve_sr_seed(sr_seed, out_dtype):
    """Validate the stochastic-rounding knob: SR only targets bf16
    outputs (the fp32 accumulator is rounded once, on store)."""
    if sr_seed is None:
        return None
    if jnp.dtype(out_dtype) != jnp.bfloat16:
        raise ValueError(
            "sr_seed (stochastic rounding) requires out_dtype=bfloat16, "
            f"got {jnp.dtype(out_dtype).name}")
    return int(sr_seed)


def _warn_fan_in_clamp(kind: str, variant: str, gram: str, requested: int,
                       clamped: int) -> None:
    key = (kind, variant, gram, requested, clamped)
    if key in _CLAMP_WARNED:
        return
    _CLAMP_WARNED.add(key)
    warnings.warn(
        f"fused {kind} schedule: levels={requested} (variant={variant!r}, "
        f"gram={gram!r}) exceeds the MAX_OPERAND_TERMS={MAX_OPERAND_TERMS} "
        f"VMEM operand fan-in or the SMEM_TABLE_BYTES={SMEM_TABLE_BYTES} "
        f"scalar-prefetch tables; clamped to levels={clamped}",
        stacklevel=3)


def _fan_in_depth(kind: str, levels: int, variant: str,
                  gram: str = "strassen") -> int:
    """The deepest program at most ``levels`` deep whose operand fan-in
    fits VMEM and whose lowered tables fit SMEM.  ``rank_k`` shares the
    ``ata`` program."""
    prog_kind = "ata" if kind == "rank_k" else kind
    g = gram if prog_kind in ("ata", "aat") else "strassen"
    while levels > 0:
        prog = compile_program(prog_kind, levels, variant, gram=g)
        if prog.max_terms <= MAX_OPERAND_TERMS \
                and _table_bytes(prog) <= SMEM_TABLE_BYTES:
            break
        levels -= 1
    return levels


def _fan_in_clamp(kind: str, levels: int, variant: str,
                  gram: str = "strassen") -> int:
    """:func:`_fan_in_depth`, warning once per distinct clamp (the
    shape-driven clamp above this is expected behaviour and stays
    silent).  ``symm`` warns under its own name as before."""
    clamped = _fan_in_depth(kind, levels, variant, gram)
    if clamped < levels:
        g = gram if kind in ("ata", "aat", "rank_k") else "strassen"
        _warn_fan_in_clamp(kind, variant, g, levels, clamped)
    return clamped


# ---------------------------------------------------------------------------
# Stochastic rounding: fp32 -> bf16 with probability proportional to the
# truncated fraction, so E[SR(x)] == x exactly.  Applied as a post-pass on
# the executor's fp32 output (one threefry draw per call, deterministic
# under a fixed seed); gradients pass straight through.
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _sr_apply(xf, bits):
    u = jax.lax.bitcast_convert_type(xf, jnp.uint32)
    # adding uniform 16-bit noise below the bf16 mantissa boundary and
    # truncating rounds up with probability (low 16 bits) / 2^16 — the
    # unbiased rounding; carries ripple into the exponent exactly when
    # the mantissa overflows (that IS the round-up to the next binade)
    rounded = ((u + bits.astype(jnp.uint32)) >> 16).astype(jnp.uint16)
    sr = jax.lax.bitcast_convert_type(rounded, jnp.bfloat16)
    # non-finite values: the noise could walk a NaN payload or push a
    # large-magnitude carry across the inf boundary — pass them through
    # round-to-nearest instead
    return jnp.where(jnp.isfinite(xf), sr, xf.astype(jnp.bfloat16))


def _sr_fwd(xf, bits):
    return _sr_apply(xf, bits), None


def _sr_bwd(_, g):
    # straight-through: rounding is an unbiased identity in expectation
    return g.astype(jnp.float32), None


_sr_apply.defvjp(_sr_fwd, _sr_bwd)


def stochastic_round_bf16(x: jax.Array, key) -> jax.Array:
    """Stochastically round ``x`` to bfloat16 (unbiased, deterministic
    per threefry ``key``); non-finite entries round to nearest.  The
    executor applies this on its fp32 output when ``sr_seed`` is set."""
    xf = x.astype(jnp.float32)
    bits = jax.random.bits(key, xf.shape, jnp.uint16)
    return _sr_apply(xf, bits)


# ---------------------------------------------------------------------------
# Geometry: bind a program kind to concrete shapes/tiles (single source of
# truth shared by the executor and the traffic models).
# ---------------------------------------------------------------------------

def _ata_geometry(m: int, n: int, levels: int, variant: str,
                  bk: int, bn: int, kind: str = "ata",
                  gram: str = "strassen"):
    """Executor/traffic-model geometry for the column-gram kinds.

    Clamps ``levels`` so (a) every leaf block holds at least one (bk, bn)
    tile of real data and (b) the operand fan-in fits VMEM (warned once),
    then derives leaf/padded shapes and grid extents.
    """
    levels = min(levels, ata_levels_for(m, n, max(bk, bn)))
    levels = _fan_in_clamp(kind, levels, variant, gram)
    plan = compile_program("rank_k" if kind == "rank_k" else "ata",
                           levels, variant, gram=gram)
    B = plan.blocks
    mb = _round_up(max(m, 1), B * bk) // B     # leaf rows (bk multiple)
    nb = _round_up(max(n, 1), B * bn) // B     # leaf cols (bn multiple)
    M, N = B * mb, B * nb
    t_blocks = N // bn
    return {
        "plan": plan, "levels": levels, "mb": mb, "nb": nb, "M": M, "N": N,
        "n_k": mb // bk, "nbt": nb // bn,
        "n_tri": t_blocks * (t_blocks + 1) // 2,
    }


def _aat_geometry(m: int, n: int, levels: int, variant: str,
                  bm: int, bk: int, gram: str = "strassen"):
    """Geometry for the row-gram (A A^t) kind — the column-gram geometry
    with the roles of the two grids swapped: output tiles tile the *row*
    dimension, the contraction sweeps the columns."""
    levels = min(levels, ata_levels_for(m, n, max(bm, bk)))
    levels = _fan_in_clamp("aat", levels, variant, gram)
    plan = compile_program("aat", levels, variant, gram=gram)
    B = plan.blocks
    mb = _round_up(max(m, 1), B * bm) // B     # leaf rows (bm multiple)
    nb = _round_up(max(n, 1), B * bk) // B     # leaf cols (bk multiple)
    M, N = B * mb, B * nb
    t_blocks = M // bm
    return {
        "plan": plan, "levels": levels, "mb": mb, "nb": nb, "M": M, "N": N,
        "n_k": nb // bk, "nbt": mb // bm,
        "n_tri": t_blocks * (t_blocks + 1) // 2,
    }


def _symm_geometry(m: int, T: int, levels: int, variant: str, bm: int):
    """Level clamp + padded-row geometry for the symm executor (shared
    with ``ata_bwd_traffic_model``).  ``T`` is the packed stack's tile
    count per side; the column side cannot be padded (the stack layout is
    fixed), so levels clamp to divisors of T.  Rectangular variants pad
    rows to their own ``blocks_m`` grid while T divides ``blocks_n``."""
    dn = leaf_ir.algebra_dims(variant)[2]
    while levels > 0 and T % (dn ** levels):
        levels -= 1
    levels = _fan_in_clamp("symm", levels, variant)
    plan = compile_program("symm", levels, variant)
    bm_blocks = plan.blocks_m
    mb = _round_up(max(m, 1), bm_blocks * bm) // bm_blocks
    return {"plan": plan, "levels": levels, "M": bm_blocks * mb,
            "nbm": mb // bm, "q": T // plan.blocks_n}


def _rank_k_geometry(m: int, T: int, levels: int, variant: str, bk: int,
                     gram: str = "strassen", gram_of: str = "cols"):
    """Geometry for an accumulating Gram against an existing packed
    (T-tile) stack: the ata (or, ``gram_of="rows"``, aat) geometry with
    the output side pinned to the stack layout, so levels clamp to
    divisors of T (like symm).  ``m`` is the contraction length: rows of
    A for the columns side, columns of A for the rows side."""
    while levels > 0 and T % (1 << levels):
        levels -= 1
    levels = min(levels, ata_levels_for(m, T, 1))   # never exceed the grid
    levels = _fan_in_clamp("rank_k", levels, variant, gram)
    plan = compile_program("rank_k", levels, variant, gram=gram,
                           gram_of=gram_of)
    B = plan.blocks
    mb = _round_up(max(m, 1), B * bk) // B
    return {"plan": plan, "levels": levels, "M": B * mb, "mb": mb,
            "n_k": mb // bk, "nbt": T // B,
            "n_tri": T * (T + 1) // 2}


def _matmul_geometry(m: int, k: int, n: int, levels: int, variant: str,
                     bm: int, bk: int, bn: int, trans_a: bool = False,
                     trans_b: bool = False):
    """Geometry for ``op(A) (m, k) @ op(B) (k, n)``: a generic per-axis
    level clamp (== strassen_levels_for at (2,2,2)) stops splitting once
    the smallest leaf axis reaches tile size, then the fan-in clamp."""
    dm, dk, dn = leaf_ir.algebra_dims(variant)
    leaf, lv = max(bm, bk, bn), 0
    cm, ck, cn = m, k, n
    while min(cm, ck, cn) > leaf:
        cm, ck, cn = cm // dm, ck // dk, cn // dn
        lv += 1
    levels = _fan_in_clamp("matmul", min(levels, lv), variant)
    plan = compile_program("matmul", levels, variant,
                           trans_a=trans_a, trans_b=trans_b)
    Bm, Bk, Bn = plan.blocks_m, plan.blocks_k, plan.blocks_n
    mb = _round_up(max(m, 1), Bm * bm) // Bm
    kb = _round_up(max(k, 1), Bk * bk) // Bk
    nb = _round_up(max(n, 1), Bn * bn) // Bn
    return {"plan": plan, "levels": levels, "mb": mb, "kb": kb, "nb": nb,
            "M": Bm * mb, "K": Bk * kb, "N": Bn * nb}


# ---------------------------------------------------------------------------
# Binding: a program + concrete tiles/grid, as a static (hashable) spec.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _Spec:
    """Static binding of a LeafProgram to tiles and a flattened grid.

    Grid is uniformly ``(n_out, n_c, n_k)``: output tiles (tri-decoded
    for packed outputs, row-major ``divmod(t, n_tj)`` for dense), the
    padded contribution sweep, and the K sweep.  ``q_i``/``q_j`` are
    output tiles per leaf block along each output dim; ``bi``/``bj`` the
    output tile edges; ``bc`` the contraction tile edge.
    """
    kind: str
    levels: int
    variant: str
    gram: str                   # gram-algebra entry (gram kinds)
    gram_of: str                # rank_k: "cols" (A^t A) or "rows" (A A^t)
    trans_a: bool               # matmul-only operand-spec transposes
    trans_b: bool
    tmax: int
    n_c: int
    n_k: int
    n_out: int
    n_tj: int                   # dense outputs: tiles along j (0 for tri)
    q_i: int
    q_j: int
    blocks_j: int               # dense outputs: leaf blocks along j
    bi: int
    bj: int
    bc: int
    out_tri: bool
    left_trans: bool
    right_trans: bool
    right_tri: bool
    diag_sym: bool
    accumulate: bool
    pipeline_depth: int = 1     # revolving VMEM buffer slots (1 = grid walk)
    acc_dtype: str = "float32"  # VMEM accumulator storage dtype (name)

    @property
    def grid_steps(self) -> int:
        return self.n_out * self.n_c * self.n_k

    @property
    def scope(self) -> str:
        """The kernel's name in HLO metadata and the device trace; the
        rows side of ``rank_k`` is ``rank_k_rows``."""
        kind = self.kind + ("_rows" if self.gram_of == "rows" else "")
        return f"fused:{kind}:l{self.levels}:{self.variant}:{self.gram}"


def _bind(prog: LeafProgram, *, n_out, n_tj, q_i, q_j, n_k, bi, bj, bc,
          diag_sym=False, pipeline_depth=1,
          acc_dtype="float32") -> _Spec:
    ls, rs, os_ = prog.left_spec, prog.right_spec, prog.out_spec
    return _Spec(
        kind=prog.kind, levels=prog.levels, variant=prog.variant,
        gram=prog.gram,
        gram_of=prog.gram_of if prog.kind == "rank_k" else "cols",
        trans_a=ls.transpose if prog.kind == "matmul" else False,
        trans_b=rs.transpose if prog.kind == "matmul" else False,
        tmax=prog.max_terms, n_c=prog.max_contributions, n_k=n_k,
        n_out=n_out, n_tj=n_tj, q_i=q_i, q_j=q_j,
        blocks_j=prog.out_blocks[1],
        bi=bi, bj=bj, bc=bc,
        out_tri=os_.packing == "tri",
        left_trans=ls.transpose, right_trans=rs.transpose,
        right_tri=rs.layout == "tri",
        diag_sym=diag_sym, accumulate=os_.accumulate,
        pipeline_depth=pipeline_depth, acc_dtype=acc_dtype)


# ---------------------------------------------------------------------------
# Scalar-prefetch tables: the program lowered to flat 1-D arrays indexed by
# (leaf destination, contribution slot[, term slot]) — ``_slot`` and
# ``_term`` give the offsets.  Flat, because SMEM pads the last dimension
# of a multi-dimensional array to 128 words: a 3-D (n_dest, n_c, tmax)
# table at ata levels 3 holds 18 KiB of data in 576 KiB of SMEM.  Per
# side, one int32 word packs a term's (row, col[, mirror]) and one
# float32 table holds its coefficient (rational gram-algebra
# coefficients like dps's +-1/2, +-1/4 must survive lowering).  Real
# entries come first in every row; the padding behind them carries
# coefficient 0 and indexes block (0, 0).  The depth-1 grid walk fetches
# those padded slots through its BlockSpecs and multiplies them by 0; the
# depth>=2 kernel reads the count table and never touches them: per
# destination ``_dest_count`` words hold its real contributions, and per
# (destination, contribution) one ``n_left << 16 | n_right`` word its
# real terms.  Per-term mirrors only ever occur on tri-stored right
# operands; left per-term trans is asserted unused at lowering — the left
# side's transposes are whole-operand OperandSpec flags, and transposed
# gram destinations were normalized into side-swapped contributions at
# the IR layer.  Accumulating programs take one more float32 table,
# ``(beta, alpha)``: run-time values, so one executable serves every
# scale, and beta = alpha = 1 multiplies by exactly 1.
# ---------------------------------------------------------------------------

# packed term word: mirror << 30 | row << 15 | col
_IDX_BITS = 15
_IDX_MASK = (1 << _IDX_BITS) - 1
# packed term-count word: n_left << 16 | n_right
_COUNT_BITS = 16
_COUNT_MASK = (1 << _COUNT_BITS) - 1

# SMEM the scalar-prefetch tables may take.  A v5e TensorCore has 1 MiB of
# SMEM, which also holds the kernel's own scalars; matmul/symm at levels 3
# (the deepest programs the fan-in clamp keeps) need 528 KiB.
SMEM_TABLE_BYTES = 768 * 1024


def _table_bytes(prog: LeafProgram) -> int:
    """SMEM bytes of the lowered tables: per (destination, contribution)
    slot one sign word, one term-count word and, per term and side, an
    index and a coefficient word; per destination one contribution-count
    word."""
    n_dest, n_c = prog.n_dests(), prog.max_contributions
    return 4 * n_dest * (n_c * (2 + 4 * prog.max_terms) + 1)


def _slot(ld, c, spec):
    """Flat offset of (destination, contribution) in the sign table."""
    return ld * spec.n_c + c


def _term(ld, c, p, spec):
    """Flat offset of term ``p`` of (destination, contribution)."""
    return (ld * spec.n_c + c) * spec.tmax + p


def _dest_count(ld, spec):
    """Flat offset of a destination's real-contribution count in the
    count table; its per-contribution term counts follow it."""
    return ld * (spec.n_c + 1)


def _term_counts(cnt_ref, ld, c, spec):
    """(real left terms, real right terms) of (destination, contribution)."""
    word = cnt_ref[_dest_count(ld, spec) + 1 + c]
    return word >> _COUNT_BITS, word & _COUNT_MASK


def _unpack(word):
    """Packed term word -> (row, col, mirror)."""
    return ((word >> _IDX_BITS) & _IDX_MASK, word & _IDX_MASK,
            word >> (2 * _IDX_BITS))


@functools.lru_cache(maxsize=None)
def _program_tables(kind: str, levels: int, variant: str,
                    gram: str = "strassen",
                    trans_a: bool = False, trans_b: bool = False,
                    gram_of: str = "cols"):
    prog = compile_program(kind, levels, variant, gram=gram,
                           trans_a=trans_a, trans_b=trans_b,
                           gram_of=gram_of)
    n_dest, n_c, tmax = prog.n_dests(), prog.max_contributions, \
        prog.max_terms
    assert max(prog.blocks_m, prog.blocks_k, prog.blocks_n) <= _IDX_MASK
    sign = np.zeros((n_dest, n_c), np.float32)
    lidx = np.zeros((n_dest, n_c, tmax), np.int32)
    lsgn = np.zeros((n_dest, n_c, tmax), np.float32)
    ridx = np.zeros_like(lidx)
    rsgn = np.zeros_like(lsgn)
    counts = np.zeros((n_dest, 1 + n_c), np.int32)
    for (di, dj), contribs in prog.by_dest().items():
        ld = prog.dest_index(di, dj)
        counts[ld, 0] = len(contribs)
        for s, contrib in enumerate(contribs):
            sign[ld, s] = contrib.sign
            counts[ld, 1 + s] = (len(contrib.left) << _COUNT_BITS
                                 | len(contrib.right))
            for p, (r, c, sg, tr) in enumerate(contrib.left):
                assert tr == 0, "per-term left transposes are not lowered"
                lidx[ld, s, p] = r << _IDX_BITS | c
                lsgn[ld, s, p] = sg
            for q, (r, c, sg, tr) in enumerate(contrib.right):
                ridx[ld, s, q] = tr << (2 * _IDX_BITS) | r << _IDX_BITS | c
                rsgn[ld, s, q] = sg
    return tuple(t.reshape(-1)
                 for t in (sign, lidx, lsgn, ridx, rsgn, counts))


# a re-registered algebra table must invalidate the lowered tables too —
# compile_program.cache_clear() alone would leave these stale
leaf_ir.on_algebra_change(_program_tables.cache_clear)


# ---------------------------------------------------------------------------
# The ONE kernel + pallas_call site.
# ---------------------------------------------------------------------------

def _decode_out(t, spec: _Spec):
    """Flattened output-tile index -> (global tile i, global tile j)."""
    if spec.out_tri:
        return _tri_decode(t)
    return t // spec.n_tj, t % spec.n_tj


def _dest_ld(gi, gj, spec: _Spec):
    """Output tile coords -> leaf-destination table index."""
    di, dj = gi // spec.q_i, gj // spec.q_j
    if spec.out_tri:
        return di * (di + 1) // 2 + dj
    return di * spec.blocks_j + dj


def _tri_term_coords(ridx_ref, ld, c, qt, spec, k, jq):
    """Conceptual global tile coords (gr, gc) and mirror flag of a
    tri-stored right term.

    Program-mirrored terms (mirror == 1) store the transposed leaf, so
    their within-leaf offsets swap; diagonal leaves straddle the stored
    triangle at tile granularity, handled downstream by max/min +
    transpose."""
    row, col, t = _unpack(ridx_ref[_term(ld, c, qt, spec)])
    gr = row * spec.q_j + jnp.where(t != 0, jq, k)
    gc = col * spec.q_j + jnp.where(t != 0, k, jq)
    return gr, gc, t


def _left_block(lidx_ref, ld, c, p, k, gi, spec):
    """Block index of left term ``p`` — shared by the depth-1 index maps
    and the depth>=2 in-kernel DMAs."""
    row, col, _ = _unpack(lidx_ref[_term(ld, c, p, spec)])
    if spec.left_trans:
        # stored leaf is (contraction, out_i)
        return row * spec.n_k + k, col * spec.q_i + gi % spec.q_i
    return row * spec.q_i + gi % spec.q_i, col * spec.n_k + k


def _right_block(ridx_ref, ld, c, q, k, jq, spec):
    """Block index of right term ``q`` (see :func:`_left_block`)."""
    if spec.right_tri:
        gr, gc, _ = _tri_term_coords(ridx_ref, ld, c, q, spec, k, jq)
        # the mirror, folded into the index map: always fetch the stored
        # lower-triangle tile
        fr = jnp.maximum(gr, gc)
        fc = jnp.minimum(gr, gc)
        return fr * (fr + 1) // 2 + fc, 0
    row, col, _ = _unpack(ridx_ref[_term(ld, c, q, spec)])
    if spec.right_trans:
        # stored leaf is (out_j, contraction)
        return row * spec.q_j + jq, col * spec.n_k + k
    return row * spec.n_k + k, col * spec.q_j + jq


def _signed_sum(term, tmax, n_terms=None):
    """``term(0) + term(1) + ...`` in term order.

    ``n_terms=None`` sums all ``tmax`` slots (the grid walk, whose padded
    slots hold coefficient 0); otherwise only the first ``n_terms``, a
    run-time count, so a padded slot — whose buffer holds stale data —
    is neither read nor added.  The real terms are summed in the same
    order either way, so both forms agree bit for bit on finite data."""
    def upto(n):
        return lambda: functools.reduce(
            operator.add, [term(p) for p in range(n)])
    if n_terms is None:
        return upto(tmax)()
    return jax.lax.switch(n_terms - 1,
                          [upto(n) for n in range(1, tmax + 1)])


def _right_sum(tile, ridx_ref, rsgn_ref, ld, c, k, jq, spec, n_terms=None):
    """Signed sum of the right operand's gathered tiles (``tile(q)`` reads
    term ``q``), in fp32 in VMEM (never in HBM), with the tri-stored
    mirrors and the whole-side transpose applied."""
    def term(qt):
        t_ = tile(qt).astype(jnp.float32)
        if spec.right_tri:
            gr, gc, t = _tri_term_coords(ridx_ref, ld, c, qt, spec, k, jq)
            # the index map fetched the stored (max, min) tile;
            # transpose in VMEM whenever the conceptual read was above
            # the diagonal or the term itself was mirrored
            t_ = jnp.where((t != 0) | (gr < gc), t_.T, t_)
            if spec.diag_sym:
                # the S + S^t operand: diagonal tiles double
                t_ = jnp.where(gr == gc, t_ + t_.T, t_)
        return t_ * rsgn_ref[_term(ld, c, qt, spec)].astype(jnp.float32)

    right = _signed_sum(term, spec.tmax, n_terms)
    if spec.right_trans and not spec.right_tri:
        right = right.T
    return right


def _left_sum(tile, lsgn_ref, ld, c, spec, n_terms=None):
    """Signed sum of the left operand's gathered tiles (see
    :func:`_right_sum`)."""
    left = _signed_sum(
        lambda p: tile(p).astype(jnp.float32)
        * lsgn_ref[_term(ld, c, p, spec)].astype(jnp.float32),
        spec.tmax, n_terms)
    # whole-side transposes flip the gathered sum once —
    # (sum s_p X_p)^t = sum s_p X_p^t, one transpose per gather.
    return left.T if spec.left_trans else left


def _seed(acc_ref, cin_ref, scale_ref):
    """The accumulator's start: the incoming tile times ``beta`` for
    accumulating programs, zero otherwise."""
    if cin_ref is None:
        acc_ref[...] = jnp.zeros_like(acc_ref)
        return
    wide = jnp.promote_types(acc_ref.dtype, jnp.float32)
    acc_ref[...] = (cin_ref[...].astype(wide)
                    * scale_ref[0].astype(wide)).astype(acc_ref.dtype)


def _coef(sign_ref, scale_ref, ld, c, spec):
    """A contribution's coefficient: its sign, times ``alpha`` for
    accumulating programs."""
    sgn = sign_ref[_slot(ld, c, spec)].astype(jnp.float32)
    return sgn * scale_ref[1] if spec.accumulate else sgn


def _leaf_kernel(sign_ref, lidx_ref, lsgn_ref, ridx_ref, rsgn_ref, *refs,
                 spec: _Spec):
    tmax = spec.tmax
    scale_ref = None
    if spec.accumulate:
        scale_ref, refs = refs[0], refs[1:]
    l_refs = refs[:tmax]
    r_refs = refs[tmax:2 * tmax]
    cin_ref = refs[2 * tmax] if spec.accumulate else None
    o_ref, acc_ref = refs[-2], refs[-1]
    t, c, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    gi, gj = _decode_out(t, spec)
    ld = _dest_ld(gi, gj, spec)
    jq = gj % spec.q_j
    sgn = sign_ref[_slot(ld, c, spec)]

    @pl.when((c == 0) & (k == 0))
    def _init():
        # rank-k: the running packed stack seeds the accumulator — the
        # incoming C is read once per tile, never re-materialized
        _seed(acc_ref, cin_ref, scale_ref)

    @pl.when(sgn != 0)
    def _accumulate():
        left = _left_sum(lambda p: l_refs[p][...], lsgn_ref, ld, c, spec)
        right = _right_sum(lambda q: r_refs[q][...], ridx_ref, rsgn_ref,
                           ld, c, k, jq, spec)
        contrib = _coef(sign_ref, scale_ref, ld, c, spec) * jnp.dot(
            left, right, preferred_element_type=jnp.float32)
        acc_ref[...] += contrib.astype(acc_ref.dtype)

    @pl.when((c == spec.n_c - 1) & (k == spec.n_k - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pipelined_kernel(sign_ref, lidx_ref, lsgn_ref, ridx_ref, rsgn_ref,
                      cnt_ref, *refs, spec: _Spec, l_shape, r_shape):
    """Depth>=2 executor body: one grid step per output tile; the
    (contribution, K) sweep runs in-kernel behind a revolving-buffer
    manual-DMA pipeline (DESIGN.md §16).

    Real-entry walk: the tile's destination has ``n_real`` real
    contributions (count table), so the sweep is ``n_real * n_k`` steps
    long, and step ``s`` fetches, waits on and adds only its
    contribution's real left and right terms.  The padded slots of the
    program tables cost no DMA, no wait and no VPU add.  A term's start
    and its wait sit under the identical predicate (``p < n_left``,
    ``q < n_right``, read from the same count word): a wait without its
    start would hang the core.

    Slot protocol: step ``s`` computes out of slot ``s % depth`` while
    the copies for step ``s + depth - 1`` stream into slot
    ``(s + depth - 1) % depth`` — the slot whose compute retired at step
    ``s - 1`` (the sweep is sequential per tile), so a buffer is never
    overwritten while in use.  The flattened step order
    ``s = c * n_k + k`` reproduces the depth-1 grid walk (k fastest) and
    the terms are summed in table order, so the accumulation order — and
    therefore the result, on finite operands — is bit-exact vs
    ``pipeline_depth=1``, whose padded slots only ever add +-0.  The
    epilogue contract is unchanged: the accumulator is seeded (from
    ``beta * c_in`` for accumulating programs) before the sweep and
    stored exactly once after it.
    """
    depth, tmax = spec.pipeline_depth, spec.tmax
    n_k = spec.n_k
    scale_ref = None
    if spec.accumulate:
        scale_ref, refs = refs[0], refs[1:]
    left_hbm, right_hbm = refs[0], refs[1]
    cin_ref = refs[2] if spec.accumulate else None
    o_ref = refs[3] if spec.accumulate else refs[2]
    l_bufs, r_bufs, l_sems, r_sems, acc_ref = refs[-5:]

    t = pl.program_id(0)
    gi, gj = _decode_out(t, spec)
    ld = _dest_ld(gi, gj, spec)
    jq = gj % spec.q_j
    n_steps = cnt_ref[_dest_count(ld, spec)] * n_k

    def _copy(s, side, p):
        """Async copy of term ``p`` of one side of step ``s``."""
        slot = s % depth
        c, k = s // n_k, s % n_k
        if side == 0:
            br, bc_ = _left_block(lidx_ref, ld, c, p, k, gi, spec)
            return pltpu.make_async_copy(
                left_hbm.at[pl.ds(br * l_shape[0], l_shape[0]),
                            pl.ds(bc_ * l_shape[1], l_shape[1])],
                l_bufs.at[slot, p], l_sems.at[slot, p])
        br, bc_ = _right_block(ridx_ref, ld, c, p, k, jq, spec)
        return pltpu.make_async_copy(
            right_hbm.at[pl.ds(br * r_shape[0], r_shape[0]),
                         pl.ds(bc_ * r_shape[1], r_shape[1])],
            r_bufs.at[slot, p], r_sems.at[slot, p])

    def _each_real_copy(s, op):
        """``op`` ("start" or "wait") on every real term copy of step
        ``s`` — the one predicate both ``_start`` and ``_wait`` go
        through."""
        counts = _term_counts(cnt_ref, ld, s // n_k, spec)
        for side in (0, 1):
            for p in range(tmax):
                @pl.when(p < counts[side])
                def _():
                    getattr(_copy(s, side, p), op)()

    def _start(s):
        _each_real_copy(s, "start")

    def _wait(s):
        _each_real_copy(s, "wait")

    _seed(acc_ref, cin_ref, scale_ref)

    for i in range(depth - 1):                     # pipeline warm-up
        pl.when(i < n_steps)(functools.partial(_start, i))

    def body(s, carry):
        slot = s % depth

        @pl.when(s + depth - 1 < n_steps)
        def _prefetch():
            _start(s + depth - 1)

        _wait(s)
        c, k = s // n_k, s % n_k
        n_left, n_right = _term_counts(cnt_ref, ld, c, spec)
        left = _left_sum(lambda p: l_bufs[slot, p], lsgn_ref, ld, c, spec,
                         n_left)
        right = _right_sum(lambda q: r_bufs[slot, q], ridx_ref, rsgn_ref,
                           ld, c, k, jq, spec, n_right)
        contrib = _coef(sign_ref, scale_ref, ld, c, spec) \
            * jnp.dot(left, right, preferred_element_type=jnp.float32)
        acc_ref[...] += contrib.astype(acc_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_steps, body, 0)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _operand_shapes(spec: _Spec):
    l_shape = (spec.bc, spec.bi) if spec.left_trans else (spec.bi, spec.bc)
    if spec.right_tri:
        r_shape = (spec.bj, spec.bj)
    elif spec.right_trans:
        r_shape = (spec.bj, spec.bc)
    else:
        r_shape = (spec.bc, spec.bj)
    return l_shape, r_shape


def _out_shape_struct(spec: _Spec, out_dtype):
    if spec.out_tri:
        return jax.ShapeDtypeStruct((spec.n_out * spec.bi, spec.bj),
                                    out_dtype)
    return jax.ShapeDtypeStruct(
        ((spec.n_out // spec.n_tj) * spec.bi, spec.n_tj * spec.bj),
        out_dtype)


def _execute_pipelined(spec: _Spec, tables, left, right, out_dtype,
                       interpret, c_in):
    """Depth>=2 ``pallas_call`` site: grid = output tiles only; the
    operands stay in HBM/ANY and the kernel streams their tiles through
    revolving VMEM buffers with manual async copies (DMA semaphores),
    overlapping the next step's fetch with the current MXU work.

    Pallas cannot batch a kernel whose operands stay in HBM, so under
    ``vmap`` (the engine's slot batch) the batch elements run the kernel
    one after another in a ``lax.map``."""
    operands = (left, right) if c_in is None else (left, right, c_in)

    @jax.custom_batching.custom_vmap
    def run(*ops):
        return _pipelined_call(spec, tables, ops, out_dtype, interpret)

    @run.def_vmap
    def _run_batched(axis_size, in_batched, *ops):
        def one(batched):
            it = iter(batched)
            return run(*[next(it) if b else x
                         for x, b in zip(ops, in_batched)])
        return jax.lax.map(one, [x for x, b in zip(ops, in_batched)
                                 if b]), True

    return run(*operands)


def _pipelined_call(spec: _Spec, tables, operands, out_dtype, interpret):
    left, right = operands[:2]
    n_tab = len(tables)
    depth, tmax = spec.pipeline_depth, spec.tmax
    l_shape, r_shape = _operand_shapes(spec)

    def out_map(t, *tabs):
        if spec.out_tri:
            return (t, 0)
        return (t // spec.n_tj, t % spec.n_tj)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    if spec.accumulate:
        in_specs.append(pl.BlockSpec((spec.bi, spec.bj), out_map))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_tab,
        grid=(spec.n_out,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((spec.bi, spec.bj), out_map),
        scratch_shapes=[
            pltpu.VMEM((depth, tmax) + l_shape, left.dtype),
            pltpu.VMEM((depth, tmax) + r_shape, right.dtype),
            pltpu.SemaphoreType.DMA((depth, tmax)),
            pltpu.SemaphoreType.DMA((depth, tmax)),
            pltpu.VMEM((spec.bi, spec.bj), jnp.dtype(spec.acc_dtype)),
        ],
    )
    with jax.named_scope(f"{spec.scope}:pd{depth}"):
        return pl.pallas_call(
            functools.partial(_pipelined_kernel, spec=spec,
                              l_shape=l_shape, r_shape=r_shape),
            grid_spec=grid_spec,
            out_shape=_out_shape_struct(spec, out_dtype),
            # only the output-tile axis remains a grid axis and its
            # tiles are independent -> megacore partitions freely; the
            # sequential sweep lives inside the kernel body.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            input_output_aliases=_stack_alias(
                operands[2] if spec.accumulate else None, out_dtype,
                n_tab + len(operands)),
            interpret=interpret,
        )(*tables, *operands)


def _stack_alias(c_in, out_dtype, n_args: int) -> dict:
    """Accumulating programs write their result over the incoming stack
    (the call's last argument) when it has the result's dtype: updated
    in place where the caller donates it, copied first by XLA where it
    does not."""
    if c_in is None or jnp.dtype(c_in.dtype) != jnp.dtype(out_dtype):
        return {}
    return {n_args - 1: 0}


def _execute(spec: _Spec, left: jax.Array, right: jax.Array,
             out_dtype, interpret, c_in: Optional[jax.Array] = None,
             scale: Optional[jax.Array] = None):
    """Run a bound program — the single ``pallas_call`` site.

    ``left``/``right`` are the padded operand arrays (the same array for
    the one-input gram kinds); ``c_in`` the incoming packed stack and
    ``scale`` the float32 ``(beta, alpha)`` of accumulating programs.
    Returns the raw output buffer: the packed tri stack for tri-packed
    programs, the dense (padded) grid otherwise.

    ``spec.pipeline_depth >= 2`` routes to the revolving-buffer DMA
    pipeline (one grid step per output tile, the (contribution, K) sweep
    in-kernel); depth 1 keeps the classic 3-axis grid walk.  Both paths
    accumulate in the same order, so they are bit-exact for a fixed
    ``acc_dtype``.
    """
    tables = _program_tables(spec.kind, spec.levels, spec.variant,
                             spec.gram, spec.trans_a, spec.trans_b,
                             spec.gram_of)
    if spec.accumulate:
        tables = tables + (scale,)
    if spec.pipeline_depth > 1:
        return _execute_pipelined(spec, tables, left, right, out_dtype,
                                  interpret, c_in)
    # the grid walk visits every padded slot, so it takes no count table
    tables = tables[:5] + tables[6:]
    n_tab = len(tables)

    def left_map(p):
        def index_map(t, c, k, sign, lidx, *tabs):
            gi, gj = _decode_out(t, spec)
            return _left_block(lidx, _dest_ld(gi, gj, spec), c, p, k, gi,
                               spec)
        return index_map

    def right_map(q):
        def index_map(t, c, k, sign, lidx, lsgn, ridx, rsgn, *tabs):
            gi, gj = _decode_out(t, spec)
            return _right_block(ridx, _dest_ld(gi, gj, spec), c, q, k,
                                gj % spec.q_j, spec)
        return index_map

    def out_map(t, c, k, *tabs):
        if spec.out_tri:
            return (t, 0)
        return (t // spec.n_tj, t % spec.n_tj)

    l_shape, r_shape = _operand_shapes(spec)

    in_specs = [pl.BlockSpec(l_shape, left_map(p)) for p in range(spec.tmax)]
    in_specs += [pl.BlockSpec(r_shape, right_map(q))
                 for q in range(spec.tmax)]
    operands = [left] * spec.tmax + [right] * spec.tmax
    if spec.accumulate:
        # the incoming stack: same tile walk as the output
        in_specs.append(pl.BlockSpec((spec.bi, spec.bj), out_map))
        operands.append(c_in)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_tab,
        grid=(spec.n_out, spec.n_c, spec.n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((spec.bi, spec.bj), out_map),
        scratch_shapes=[pltpu.VMEM((spec.bi, spec.bj),
                                   jnp.dtype(spec.acc_dtype))],
    )
    # named_scope: the bound program's identity (kind/levels/variant)
    # lands in the HLO metadata of the pallas_call, so profiler traces
    # and HLO censuses attribute kernel time/traffic to the schedule
    # that produced it (DESIGN.md §14)
    with jax.named_scope(spec.scope):
        return pl.pallas_call(
            functools.partial(_leaf_kernel, spec=spec),
            grid_spec=grid_spec,
            out_shape=_out_shape_struct(spec, out_dtype),
            # output tiles (t) are independent -> megacore partitions
            # them; the (contribution, K) sweep carries the VMEM
            # accumulator and must stay sequential per tile.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            input_output_aliases=_stack_alias(c_in, out_dtype,
                                              n_tab + len(operands)),
            interpret=interpret,
        )(*tables, *operands)


# ---------------------------------------------------------------------------
# Fused ATA: C = tril(A^t A) into the packed triangular block stack.
# ---------------------------------------------------------------------------

def fused_ata_packed(
    a: jax.Array,
    *,
    levels: int = 2,
    variant: str = "strassen",
    gram: str = "strassen",
    bk: int = 256,
    bn: int = 256,
    out_dtype=None,
    interpret=None,
    bwd: str = "fused",
    pipeline_depth=None,
    operand_dtype=None,
    acc_dtype=None,
    sr_seed=None,
):
    """Packed lower-triangular block stack of ``tril(a.T @ a)`` via the
    leaf-program executor.

    ``a`` is zero-padded so each of the ``2^levels`` leaf blocks is a
    (bk, bn)-tile multiple (exact: zero rows add nothing to A^tA, zero
    columns are sliced away by the dense wrapper).

    Returns ``(packed, n_padded)`` with packed of shape
    ``(T(T+1)/2 * bn, bn)``, ``T = n_padded // bn``, in the ordering of
    ``symmetry.pack_tril_blocks`` / ``kernels.syrk``.

    ``levels`` is a cap: the unroll depth is clamped (``_ata_geometry``)
    so every leaf block holds at least one (bk, bn) tile of real data
    and so the operand fan-in fits VMEM (``MAX_OPERAND_TERMS``, warned
    once).

    Differentiable: the custom VJP consumes the *packed* cotangent
    directly through :func:`fused_symm_matmul` (``bwd="fused"``, the
    default) — ``dA = A (S + S^t)`` with S the block-lower cotangent,
    no dense n^2 buffer ever materialized.  ``bwd="dense"`` selects the
    classical dense-dot baseline (unpack + ``A @ (S + S^t)``) for
    benchmarking.

    Perf/precision knobs (DESIGN.md §16): ``pipeline_depth`` revolving
    DMA buffer slots (None = backend default: 2 compiled, 1 interpret);
    ``operand_dtype`` quantizes the stored operand tiles (fp8/bf16)
    while accumulation stays in ``acc_dtype`` (fp32 default);
    ``sr_seed`` stochastically rounds a bf16 output (deterministic per
    seed, unbiased in expectation).
    """
    interpret = _auto_interpret(interpret, site="fused_ata_packed")
    depth = _resolve_pipeline_depth(pipeline_depth, interpret)
    op_dt = _resolve_operand_dtype(operand_dtype)
    acc_dt = _resolve_acc_dtype(acc_dtype)
    m, n = a.shape
    geo = _ata_geometry(m, n, levels, variant, bk, bn, gram=gram)
    out_dtype = (jnp.promote_types(a.dtype, jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))
    sr = _resolve_sr_seed(sr_seed, out_dtype)
    core_out = jnp.dtype(jnp.float32) if sr is not None else out_dtype
    packed = _fused_ata_packed_core(a, levels, variant, gram, bk, bn,
                                    core_out, interpret, bwd, depth,
                                    op_dt, acc_dt)
    if sr is not None:
        packed = stochastic_round_bf16(packed, jax.random.PRNGKey(sr))
    return packed, geo["N"]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11))
def _fused_ata_packed_core(a, levels, variant, gram, bk, bn, out_dtype,
                           interpret, bwd, pipeline_depth, operand_dtype,
                           acc_dtype):
    return _fused_ata_packed_exec(a, levels, variant, gram, bk, bn,
                                  out_dtype, interpret, pipeline_depth,
                                  operand_dtype, acc_dtype)[0]


def _fused_ata_packed_fwd(a, levels, variant, gram, bk, bn, out_dtype,
                          interpret, bwd, pipeline_depth, operand_dtype,
                          acc_dtype):
    return (_fused_ata_packed_core(a, levels, variant, gram, bk, bn,
                                   out_dtype, interpret, bwd,
                                   pipeline_depth, operand_dtype,
                                   acc_dtype), a)


def _fused_ata_packed_bwd(levels, variant, gram, bk, bn, out_dtype,
                          interpret, bwd, pipeline_depth, operand_dtype,
                          acc_dtype, a, gp):
    # vdot(gp, packed(A)) has S = block-lower cotangent (diagonal tiles
    # full — the forward computes them full), so dA = A (S + S^t): the
    # packed stack *is* S and feeds the symm executor directly.
    acc = jnp.promote_types(a.dtype, jnp.float32)
    m, n = a.shape
    if bwd == "dense":
        geo = _ata_geometry(m, n, levels, variant, bk, bn, gram=gram)
        M, N = geo["M"], geo["N"]
        s = unpack_tril_blocks(gp.astype(acc), N, bn, symmetrize=False)
        ap = jnp.pad(a.astype(acc), ((0, M - m), (0, N - n)))
        da = jnp.dot(ap, s + s.T, preferred_element_type=acc)[:m, :n]
    else:
        da = fused_symm_matmul(a, gp, levels=levels, variant=variant,
                               bm=bk, diag_sym=True, out_dtype=acc,
                               interpret=interpret,
                               pipeline_depth=pipeline_depth)[:, :n]
    return (da.astype(a.dtype),)


_fused_ata_packed_core.defvjp(_fused_ata_packed_fwd, _fused_ata_packed_bwd)


def _fused_ata_packed_exec(
    a: jax.Array,
    levels: int,
    variant: str,
    gram: str,
    bk: int,
    bn: int,
    out_dtype,
    interpret,
    pipeline_depth: int = 1,
    operand_dtype=None,
    acc_dtype: str = "float32",
):
    """Forward executor (no autodiff surface — see the custom VJP above)."""
    m, n = a.shape
    geo = _ata_geometry(m, n, levels, variant, bk, bn, gram=gram)
    plan = geo["plan"]
    M, N = geo["M"], geo["N"]
    out_dtype = (jnp.promote_types(a.dtype, jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))
    if (M, N) != (m, n):
        with jax.named_scope("gram:pad"):
            a = jnp.pad(a, ((0, M - m), (0, N - n)))
    if operand_dtype is not None:
        # the quantization step: operand tiles are STORED (and DMA'd) at
        # the low precision; every compute upcasts tile-wise to fp32
        a = a.astype(jnp.dtype(operand_dtype))
    spec = _bind(plan, n_out=geo["n_tri"], n_tj=0, q_i=geo["nbt"],
                 q_j=geo["nbt"], n_k=geo["n_k"], bi=bn, bj=bn, bc=bk,
                 pipeline_depth=pipeline_depth, acc_dtype=acc_dtype)
    return _execute(spec, a, a, out_dtype, interpret), N


def fused_ata(
    a: jax.Array,
    *,
    levels: int = 2,
    variant: str = "strassen",
    gram: str = "strassen",
    bk: int = 256,
    bn: int = 256,
    out_dtype=None,
    interpret=None,
    bwd: str = "fused",
    pipeline_depth=None,
    operand_dtype=None,
    acc_dtype=None,
    sr_seed=None,
) -> jax.Array:
    """Dense ``tril(a.T @ a)`` at the original size via the fused pipeline.

    Differentiable: ``dA = A (S + S^t)`` with ``S = tril(cotangent)``.
    ``bwd="fused"`` (default) runs the backward through the symm program
    executor (:func:`fused_symm_matmul`): the cotangent is gathered
    straight into the packed lower-triangular tile stack (n(n+1)/2
    storage, per-tile slices — no dense S + S^t or padded-S buffer) and
    the product runs the same leaf-program pipeline as the forward.
    ``bwd="dense"`` keeps the classical ``jnp.dot(a, s + s.T)`` baseline.

    Accepts the same perf/precision knobs as :func:`fused_ata_packed`:
    ``pipeline_depth``, ``operand_dtype``, ``acc_dtype``, ``sr_seed``.
    """
    interpret = _auto_interpret(interpret, site="fused_ata")
    depth = _resolve_pipeline_depth(pipeline_depth, interpret)
    op_dt = _resolve_operand_dtype(operand_dtype)
    acc_dt = _resolve_acc_dtype(acc_dtype)
    out_dtype = (jnp.promote_types(a.dtype, jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))
    sr = _resolve_sr_seed(sr_seed, out_dtype)
    core_out = jnp.dtype(jnp.float32) if sr is not None else out_dtype
    out = _fused_ata_dense(a, levels, variant, gram, bk, bn, core_out,
                           interpret, bwd, depth, op_dt, acc_dt)
    if sr is not None:
        out = stochastic_round_bf16(out, jax.random.PRNGKey(sr))
    return out


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11))
def _fused_ata_dense(a, levels, variant, gram, bk, bn, out_dtype, interpret,
                     bwd, pipeline_depth, operand_dtype, acc_dtype):
    n = a.shape[1]
    packed, n_pad = _fused_ata_packed_exec(
        a, levels, variant, gram, bk, bn, out_dtype, interpret,
        pipeline_depth, operand_dtype, acc_dtype)
    with jax.named_scope("gram:unpack"):
        dense = unpack_tril_blocks(packed, n_pad, bn, symmetrize=False)
        # diagonal blocks are computed full — drop their upper halves
        return jnp.tril(dense)[:n, :n]


def _fused_ata_dense_fwd(a, levels, variant, gram, bk, bn, out_dtype,
                         interpret, bwd, pipeline_depth, operand_dtype,
                         acc_dtype):
    return (_fused_ata_dense(a, levels, variant, gram, bk, bn, out_dtype,
                             interpret, bwd, pipeline_depth, operand_dtype,
                             acc_dtype), a)


def _pack_cotangent(g: jax.Array, n: int, n_pad: int, bn: int) -> jax.Array:
    """Packed lower-triangular (bn, bn) tile stack of ``S = tril(g)``,
    zero-padded to ``n_pad`` — built from per-tile slices of ``g``, so the
    padded dense S (and a fortiori S + S^t) never materializes in HBM;
    the stack is the only n(n+1)/2-sized temporary."""
    t = n_pad // bn
    blocks = []
    for i in range(t):
        r0 = i * bn
        for j in range(i + 1):
            c0 = j * bn
            if r0 >= n or c0 >= n:
                blocks.append(jnp.zeros((bn, bn), g.dtype))
                continue
            blk = g[r0:min(r0 + bn, n), c0:min(c0 + bn, n)]
            pr, pc = bn - blk.shape[0], bn - blk.shape[1]
            if pr or pc:
                blk = jnp.pad(blk, ((0, pr), (0, pc)))
            if i == j:
                blk = jnp.tril(blk)
            blocks.append(blk)
    return jnp.concatenate(blocks, axis=0)


def _fused_ata_dense_bwd(levels, variant, gram, bk, bn, out_dtype, interpret,
                         bwd, pipeline_depth, operand_dtype, acc_dtype,
                         a, g):
    # C = tril(A^t A) => dL/dA = A (S + S^t), S = tril(dL/dC); the factor
    # 2 on the diagonal of S + S^t is exactly the quadratic term's.
    acc = jnp.promote_types(a.dtype, jnp.float32)
    m, n = a.shape
    if bwd == "dense":
        s = jnp.tril(g).astype(acc)
        da = jnp.dot(a.astype(acc), s + s.T, preferred_element_type=acc)
    else:
        geo = _ata_geometry(m, n, levels, variant, bk, bn, gram=gram)
        sp = _pack_cotangent(g.astype(acc), n, geo["N"], bn)
        da = fused_symm_matmul(a, sp, levels=geo["levels"], variant=variant,
                               bm=bk, diag_sym=True, out_dtype=acc,
                               interpret=interpret,
                               pipeline_depth=pipeline_depth)[:, :n]
    return (da.astype(a.dtype),)


_fused_ata_dense.defvjp(_fused_ata_dense_fwd, _fused_ata_dense_bwd)


# ---------------------------------------------------------------------------
# Fused AAT: C = tril(A A^t) — the Arrigoni-Massini (2021) row-gram
# recursion, compiled from the same IR.  The transpose of A never exists
# in HBM: the right side reads the SAME stored A tiles mirrored through
# the index maps and flips the gathered sum in VMEM.
# ---------------------------------------------------------------------------

def fused_aat_packed(
    a: jax.Array,
    *,
    levels: int = 2,
    variant: str = "strassen",
    gram: str = "strassen",
    bm: int = 256,
    bk: int = 256,
    out_dtype=None,
    interpret=None,
    pipeline_depth=None,
    operand_dtype=None,
    acc_dtype=None,
    sr_seed=None,
):
    """Packed lower-triangular block stack of ``tril(a @ a.T)``.

    Returns ``(packed, m_padded)`` with packed of shape
    ``(T(T+1)/2 * bm, bm)``, ``T = m_padded // bm``.  Zero-padding is
    exact: zero columns add nothing to A A^t, zero rows add zero
    rows/columns to C that the dense wrapper slices away.

    Accepts the same perf/precision knobs as :func:`fused_ata_packed`.
    """
    interpret = _auto_interpret(interpret, site="fused_aat_packed")
    depth = _resolve_pipeline_depth(pipeline_depth, interpret)
    op_dt = _resolve_operand_dtype(operand_dtype)
    acc_dt = _resolve_acc_dtype(acc_dtype)
    m, n = a.shape
    geo = _aat_geometry(m, n, levels, variant, bm, bk, gram=gram)
    plan = geo["plan"]
    M, N = geo["M"], geo["N"]
    out_dtype = (jnp.promote_types(a.dtype, jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))
    sr = _resolve_sr_seed(sr_seed, out_dtype)
    core_out = jnp.dtype(jnp.float32) if sr is not None else out_dtype
    if (M, N) != (m, n):
        a = jnp.pad(a, ((0, M - m), (0, N - n)))
    if op_dt is not None:
        a = a.astype(jnp.dtype(op_dt))
    spec = _bind(plan, n_out=geo["n_tri"], n_tj=0, q_i=geo["nbt"],
                 q_j=geo["nbt"], n_k=geo["n_k"], bi=bm, bj=bm, bc=bk,
                 pipeline_depth=depth, acc_dtype=acc_dt)
    packed = _execute(spec, a, a, core_out, interpret)
    if sr is not None:
        packed = stochastic_round_bf16(packed, jax.random.PRNGKey(sr))
    return packed, M


def fused_aat(
    a: jax.Array,
    *,
    levels: int = 2,
    variant: str = "strassen",
    gram: str = "strassen",
    bm: int = 256,
    bk: int = 256,
    out_dtype=None,
    interpret=None,
    pipeline_depth=None,
    operand_dtype=None,
    acc_dtype=None,
    sr_seed=None,
) -> jax.Array:
    """Dense ``tril(a @ a.T)`` at the original size via the fused
    pipeline — ``ata(x, gram_of="rows")``.

    Differentiable: ``dA = (S + S^t) A`` with ``S = tril(cotangent)``
    (the dense-dot VJP; the row-gram backward is symmetric-left rather
    than symmetric-right, which the symm program does not yet express).

    Accepts the same perf/precision knobs as :func:`fused_ata_packed`.
    """
    interpret = _auto_interpret(interpret, site="fused_aat")
    depth = _resolve_pipeline_depth(pipeline_depth, interpret)
    op_dt = _resolve_operand_dtype(operand_dtype)
    acc_dt = _resolve_acc_dtype(acc_dtype)
    out_dtype = (jnp.promote_types(a.dtype, jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))
    sr = _resolve_sr_seed(sr_seed, out_dtype)
    core_out = jnp.dtype(jnp.float32) if sr is not None else out_dtype
    out = _fused_aat_dense(a, levels, variant, gram, bm, bk, core_out,
                           interpret, depth, op_dt, acc_dt)
    if sr is not None:
        out = stochastic_round_bf16(out, jax.random.PRNGKey(sr))
    return out


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(1, 2, 3, 4, 5, 6, 7, 8, 9, 10))
def _fused_aat_dense(a, levels, variant, gram, bm, bk, out_dtype, interpret,
                     pipeline_depth, operand_dtype, acc_dtype):
    m = a.shape[0]
    packed, m_pad = fused_aat_packed(a, levels=levels, variant=variant,
                                     gram=gram, bm=bm, bk=bk,
                                     out_dtype=out_dtype,
                                     interpret=interpret,
                                     pipeline_depth=pipeline_depth,
                                     operand_dtype=operand_dtype,
                                     acc_dtype=acc_dtype)
    dense = unpack_tril_blocks(packed, m_pad, bm, symmetrize=False)
    return jnp.tril(dense)[:m, :m]


def _fused_aat_dense_fwd(a, levels, variant, gram, bm, bk, out_dtype,
                         interpret, pipeline_depth, operand_dtype,
                         acc_dtype):
    return (_fused_aat_dense(a, levels, variant, gram, bm, bk, out_dtype,
                             interpret, pipeline_depth, operand_dtype,
                             acc_dtype), a)


def _fused_aat_dense_bwd(levels, variant, gram, bm, bk, out_dtype, interpret,
                         pipeline_depth, operand_dtype, acc_dtype, a, g):
    # C = tril(A A^t) => dA = (S + S^t) A, S = tril(g)
    acc = jnp.promote_types(a.dtype, jnp.float32)
    s = jnp.tril(g).astype(acc)
    da = jnp.dot(s + s.T, a.astype(acc), preferred_element_type=acc)
    return (da.astype(a.dtype),)


_fused_aat_dense.defvjp(_fused_aat_dense_fwd, _fused_aat_dense_bwd)


# ---------------------------------------------------------------------------
# Fused rank-k update: C <- beta C + alpha tril(A^t A) (or of A A^t) against
# an existing packed stack — the accumulating gram program.  The incoming
# stack, scaled by beta, seeds the VMEM accumulator tile-wise and the result
# is written over it, so a streamed Gram update or a statistics EMA step is
# ONE kernel with no delta stack and no unpack/gather in HBM.
# ---------------------------------------------------------------------------

def fused_rank_k_update(
    c_stack: jax.Array,
    a: jax.Array,
    *,
    levels: int = 2,
    variant: str = "strassen",
    gram: str = "strassen",
    gram_of: str = "cols",
    beta=1.0,
    alpha=1.0,
    bk: int = 256,
    out_dtype=None,
    interpret=None,
    pipeline_depth=None,
    operand_dtype=None,
    acc_dtype=None,
) -> jax.Array:
    """``C <- beta C + alpha tril(a.T @ a)`` on a packed lower-triangular
    tile stack (``gram_of="rows"``: ``tril(a @ a.T)``).

    ``c_stack`` is a ``(T(T+1)/2 * bn, bn)`` stack (``fused_ata_packed``
    / ``kernels.syrk`` ordering — the tile edge is read off the stack's
    trailing dim); ``a`` is an (m, n) operand whose Gram side spans at
    most ``T * bn`` (n for the columns side, m for the rows side; zero-
    padded to the stack span, exact for the Gram).  ``bk`` tiles the
    contraction (the other side).  ``beta`` and ``alpha`` are run-time
    scalars — one executable serves every value, and ``beta = alpha =
    1`` (the default) is the plain accumulation ``C += tril(...)`` bit for
    bit.  Returns the updated stack, written over the incoming one
    (in place where the caller donates it).

    ``levels`` is clamped to depths dividing the (fixed) stack layout,
    like :func:`fused_symm_matmul`.  Differentiable in the stack and in
    ``a`` (the scalars get a zero cotangent): the stack cotangent passes
    through packed (times beta), and ``dA`` runs the symm program on the
    packed cotangent (DESIGN.md §11) — no dense n^2 buffer in either
    direction.

    ``pipeline_depth``/``operand_dtype``/``acc_dtype`` as in
    :func:`fused_ata_packed`; ``operand_dtype`` quantizes only the
    incoming ``a`` — the running stack seeds the accumulator at its own
    precision, so streamed state never degrades.
    """
    interpret = _auto_interpret(interpret, site="fused_rank_k_update")
    depth = _resolve_pipeline_depth(pipeline_depth, interpret)
    op_dt = _resolve_operand_dtype(operand_dtype)
    acc_dt = _resolve_acc_dtype(acc_dtype)
    if gram_of not in ("cols", "rows"):
        raise ValueError(f"gram_of must be 'cols' or 'rows', got {gram_of!r}")
    if c_stack.ndim != 2 or a.ndim != 2:
        raise ValueError(f"bad ranks: stack {c_stack.shape} x {a.shape}")
    bn = c_stack.shape[1]
    if c_stack.shape[0] % bn:
        raise ValueError(f"packed stack {c_stack.shape} not a (bn, bn) "
                         "tile stack")
    n_tri = c_stack.shape[0] // bn
    T = (math.isqrt(8 * n_tri + 1) - 1) // 2
    if T * (T + 1) // 2 != n_tri:
        raise ValueError(f"stack of {n_tri} tiles is not triangular")
    side = a.shape[0] if gram_of == "rows" else a.shape[1]
    if side > T * bn:
        raise ValueError(f"a {a.shape} has a {gram_of} side of {side} but "
                         f"the stack spans {T * bn}")
    out_dtype = (c_stack.dtype if out_dtype is None
                 else jnp.dtype(out_dtype))
    scale = jnp.stack([jnp.asarray(beta, jnp.float32),
                       jnp.asarray(alpha, jnp.float32)])
    return _fused_rank_k_core(c_stack, a, scale, levels, variant, gram,
                              gram_of, bk, bn, out_dtype,
                              jnp.dtype(c_stack.dtype), interpret, depth,
                              op_dt, acc_dt)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14))
def _fused_rank_k_core(c_stack, a, scale, levels, variant, gram, gram_of,
                       bk, bn, out_dtype, stack_dtype, interpret,
                       pipeline_depth, operand_dtype, acc_dtype):
    return _fused_rank_k_exec(c_stack, a, scale, levels, variant, gram,
                              gram_of, bk, bn, out_dtype, interpret,
                              pipeline_depth, operand_dtype, acc_dtype)


def _fused_rank_k_exec(c_stack, a, scale, levels, variant, gram, gram_of,
                       bk, bn, out_dtype, interpret, pipeline_depth=1,
                       operand_dtype=None, acc_dtype="float32"):
    n_tri = c_stack.shape[0] // bn
    T = (math.isqrt(8 * n_tri + 1) - 1) // 2
    N = T * bn
    rows = gram_of == "rows"
    m, n = a.shape
    k_len = n if rows else m        # the contraction: the other side
    geo = _rank_k_geometry(k_len, T, levels, variant, bk, gram=gram,
                           gram_of=gram_of)
    plan, K = geo["plan"], geo["M"]
    shape = (N, K) if rows else (K, N)
    if shape != (m, n):
        a = jnp.pad(a, ((0, shape[0] - m), (0, shape[1] - n)))
    if operand_dtype is not None:
        a = a.astype(jnp.dtype(operand_dtype))
    spec = _bind(plan, n_out=geo["n_tri"], n_tj=0, q_i=geo["nbt"],
                 q_j=geo["nbt"], n_k=geo["n_k"], bi=bn, bj=bn, bc=bk,
                 pipeline_depth=pipeline_depth, acc_dtype=acc_dtype)
    return _execute(spec, a, a, out_dtype, interpret, c_in=c_stack,
                    scale=scale)


def _fused_rank_k_fwd(c_stack, a, scale, levels, variant, gram, gram_of, bk,
                      bn, out_dtype, stack_dtype, interpret, pipeline_depth,
                      operand_dtype, acc_dtype):
    out = _fused_rank_k_core(c_stack, a, scale, levels, variant, gram,
                             gram_of, bk, bn, out_dtype, stack_dtype,
                             interpret, pipeline_depth, operand_dtype,
                             acc_dtype)
    return out, (a, scale)


def _fused_rank_k_bwd(levels, variant, gram, gram_of, bk, bn, out_dtype,
                      stack_dtype, interpret, pipeline_depth, operand_dtype,
                      acc_dtype, res, g):
    # C_out = beta C_in + alpha P, P = tril(A^t A) (rows: tril(A A^t)) as
    # the stack holds it: dC_in = beta g (packed pass-through, cast back
    # to the stack primal's dtype); dA = alpha A (S + S^t) with S the
    # block-lower cotangent stack (rows: alpha (S + S^t) A, the same
    # symm program on A^t); the scalars are not differentiated.
    a, scale = res
    acc = jnp.promote_types(a.dtype, jnp.float32)
    rows = gram_of == "rows"
    x = a.T if rows else a
    n = x.shape[1]
    T = (math.isqrt(8 * (g.shape[0] // bn) + 1) - 1) // 2
    lv = _rank_k_geometry(x.shape[0], T, levels, variant, bk, gram=gram,
                          gram_of=gram_of)["levels"]
    da = fused_symm_matmul(x, g, levels=lv, variant=variant, bm=bk,
                           diag_sym=True, out_dtype=acc,
                           interpret=interpret,
                           pipeline_depth=pipeline_depth)[:, :n]
    da = (scale[1].astype(acc) * da).astype(a.dtype)
    dc = scale[0] * g.astype(jnp.float32)
    return (dc.astype(stack_dtype), (da.T if rows else da),
            jnp.zeros_like(scale))


_fused_rank_k_core.defvjp(_fused_rank_k_fwd, _fused_rank_k_bwd)


# ---------------------------------------------------------------------------
# Fused symm matmul: D = X @ Sym where Sym is given ONLY as the packed
# lower-triangular (bs, bs) tile stack of S (syrk / fused-ATA layout).
# The executor binding of the ``symm`` program — and the engine of the
# Gram backward: dA = A (S + S^t) with S the (packed) cotangent.
# ---------------------------------------------------------------------------

def fused_symm_matmul(
    x: jax.Array,
    s_packed: jax.Array,
    *,
    levels: int = 2,
    variant: str = "strassen",
    bm: int = 256,
    diag_sym: bool = False,
    out_dtype=None,
    interpret=None,
    pipeline_depth=None,
    operand_dtype=None,
    acc_dtype=None,
) -> jax.Array:
    """``x @ Sym`` via the flattened symm program, one fused kernel.

    ``s_packed`` is the packed lower-triangular tile stack of S —
    shape (T(T+1)/2 * bs, bs) in ``kernels.syrk`` / ``fused_ata_packed``
    ordering (the tile edge ``bs`` is read off the stack's trailing dim).

    * ``diag_sym=False``: Sym is the symmetric completion of the stack
      (diagonal tiles stored full); computes ``x @ Sym``.
    * ``diag_sym=True``: Sym = S + S^t with S the block-lower matrix the
      stack represents — the Gram-VJP operand.  Identical mirrored reads;
      diagonal tiles contribute ``tile + tile^t``.

    ``x`` is zero-padded on the right to the stack's T*bs columns (exact:
    the padded columns multiply rows of Sym that padded-A gradients
    discard) and on the bottom to leaf multiples.  Returns
    ``(x.shape[0], T*bs)``.

    Same fusion contract as the forward: operand sums and mirrored
    transposes live in VMEM only, fp32 VMEM accumulation, one HBM write
    per output tile, no dense Sym (or S + S^t) buffer ever exists.

    ``pipeline_depth``/``operand_dtype``/``acc_dtype`` as in
    :func:`fused_ata_packed` (``operand_dtype`` quantizes both ``x`` and
    the packed stack).
    """
    interpret = _auto_interpret(interpret, site="fused_symm_matmul")
    depth = _resolve_pipeline_depth(pipeline_depth, interpret)
    op_dt = _resolve_operand_dtype(operand_dtype)
    acc_dt = _resolve_acc_dtype(acc_dtype)
    if x.ndim != 2 or s_packed.ndim != 2:
        raise ValueError(f"bad ranks: {x.shape} x packed {s_packed.shape}")
    bs = s_packed.shape[1]
    if s_packed.shape[0] % bs:
        raise ValueError(f"packed stack {s_packed.shape} not a (bs, bs) "
                         "tile stack")
    n_tri = s_packed.shape[0] // bs
    T = (math.isqrt(8 * n_tri + 1) - 1) // 2
    if T * (T + 1) // 2 != n_tri:
        raise ValueError(f"stack of {n_tri} tiles is not triangular")
    N = T * bs
    m, nx = x.shape
    if nx > N:
        raise ValueError(f"x has {nx} cols but the stack spans {N}")
    if nx < N:
        x = jnp.pad(x, ((0, 0), (0, N - nx)))
    out_dtype = (jnp.promote_types(jnp.promote_types(x.dtype,
                                                     s_packed.dtype),
                                   jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))

    geo = _symm_geometry(m, T, levels, variant, bm)
    plan = geo["plan"]
    M, nbm, q = geo["M"], geo["nbm"], geo["q"]
    if M != m:
        x = jnp.pad(x, ((0, M - m), (0, 0)))
    if op_dt is not None:
        x = x.astype(jnp.dtype(op_dt))
        s_packed = s_packed.astype(jnp.dtype(op_dt))
    spec = _bind(plan, n_out=(M // bm) * T, n_tj=T, q_i=nbm, q_j=q,
                 n_k=q, bi=bm, bj=bs, bc=bs, diag_sym=diag_sym,
                 pipeline_depth=depth, acc_dtype=acc_dt)
    out = _execute(spec, x, s_packed, out_dtype, interpret)
    return out[:m]


# ---------------------------------------------------------------------------
# Analytic HBM traffic model — IR-driven, one core shared by every kind.
#
# In interpret mode (CPU) the Pallas pipeline is *emulated* with XLA loops
# whose HLO carries full-array state buffers, so an HLO census of the
# interpret lowering measures the emulation, not the kernel.  On hardware
# the kernel's HBM behaviour is exact and simple by construction — grid
# DMA reads of operand tiles, one write per output tile, and NO other
# HBM buffer — so we model it in closed form over the bound _Spec, the
# same way bench_roofline treats Pallas flash-attention FLOPs
# analytically.
# ---------------------------------------------------------------------------

def _dest_tiles(spec: _Spec, n_dest: int) -> np.ndarray:
    """Output tiles of each leaf destination, in table order."""
    if not spec.out_tri:
        return np.full(n_dest, spec.q_i * spec.q_j)
    q, b = spec.q_i, math.isqrt(8 * n_dest + 1) // 2   # b(b+1)/2 dests
    diag = np.array([di == dj for di in range(b) for dj in range(di + 1)])
    # a diagonal leaf block is stored as its tile-level lower triangle
    return np.where(diag, q * (q + 1) // 2, q * q)


def _traffic(spec: _Spec, *, left_bytes: int, right_bytes: int,
             out_bytes: int, cin_bytes: int = 0) -> dict:
    """Core HBM model of one bound program, as the depth>=2 kernel walks
    it: per output tile, ``n_k`` steps for each real contribution of its
    destination, each fetching that contribution's real left and right
    terms (the count table the kernel reads); one write per output tile;
    plus the incoming stack read for accumulating programs.

    ``padded_grid_steps`` / ``padded_read_bytes`` are the padded grid
    the depth-1 walk visits (every contribution slot, ``tmax`` tiles a
    side), and ``skipped_fetch_share`` the share of that grid's tile
    fetches the depth>=2 walk does not issue."""
    counts = _program_tables(spec.kind, spec.levels, spec.variant,
                             spec.gram, spec.trans_a, spec.trans_b,
                             spec.gram_of)[5]
    counts = counts.reshape(-1, spec.n_c + 1).astype(np.int64)
    tiles = _dest_tiles(spec, counts.shape[0])
    assert tiles.sum() == spec.n_out, (tiles.sum(), spec.n_out)
    steps = int(tiles @ counts[:, 0]) * spec.n_k
    n_left = int(tiles @ (counts[:, 1:] >> _COUNT_BITS).sum(1)) * spec.n_k
    n_right = int(tiles @ (counts[:, 1:] & _COUNT_MASK).sum(1)) * spec.n_k
    padded = spec.grid_steps
    l_tile = spec.bi * spec.bc * left_bytes
    r_tile = ((spec.bj * spec.bj) if spec.right_tri
              else spec.bj * spec.bc) * right_bytes
    stack_reads = (spec.n_out * spec.bi * spec.bj * cin_bytes
                   if spec.accumulate else 0)
    writes = spec.n_out * spec.bi * spec.bj * out_bytes
    # MXU work per step: one (bi, bc) x (bc, bj) leaf product (the VPU
    # gather adds are second-order) — feeds the pipelined occupancy term
    # in cost_model.pipelined_bytes_score
    flops = 2 * steps * spec.bi * spec.bc * spec.bj
    return {
        "grid_steps": steps,
        "read_bytes": n_left * l_tile + n_right * r_tile + stack_reads,
        "write_bytes": writes, "flops": flops,
        "padded_grid_steps": padded,
        "padded_read_bytes": padded * spec.tmax * (l_tile + r_tile)
        + stack_reads,
        "skipped_fetch_share": 1 - (n_left + n_right)
        / (2 * padded * spec.tmax),
    }


def ata_traffic_model(
    m: int, n: int, *, levels: int = 2, variant: str = "strassen",
    gram: str = "strassen",
    bk: int = 256, bn: int = 256, in_bytes: int = 4, out_bytes: int = 4,
) -> dict:
    """HBM bytes of ``fused_ata_packed`` on an (m, n) input.

    Reads/writes from the shared IR traffic core; ``intermediate_bytes``
    is HBM-materialized temporaries — just the zero-pad copy of A when
    the shape is not tile-aligned, 0 otherwise.  Uses the same
    ``_ata_geometry`` as the executor, so the model cannot drift from
    the kernel's clamping/padding.
    """
    geo = _ata_geometry(m, n, levels, variant, bk, bn, gram=gram)
    M, N = geo["M"], geo["N"]
    spec = _bind(geo["plan"], n_out=geo["n_tri"], n_tj=0, q_i=geo["nbt"],
                 q_j=geo["nbt"], n_k=geo["n_k"], bi=bn, bj=bn, bc=bk)
    t = _traffic(spec, left_bytes=in_bytes, right_bytes=in_bytes,
                 out_bytes=out_bytes)
    t["intermediate_bytes"] = M * N * in_bytes if (M, N) != (m, n) else 0
    t["padded_shape"] = (M, N)
    return t


def aat_traffic_model(
    m: int, n: int, *, levels: int = 2, variant: str = "strassen",
    gram: str = "strassen",
    bm: int = 256, bk: int = 256, in_bytes: int = 4, out_bytes: int = 4,
) -> dict:
    """HBM bytes of ``fused_aat_packed`` (row gram) — same core model,
    the row-gram geometry."""
    geo = _aat_geometry(m, n, levels, variant, bm, bk, gram=gram)
    M, N = geo["M"], geo["N"]
    spec = _bind(geo["plan"], n_out=geo["n_tri"], n_tj=0, q_i=geo["nbt"],
                 q_j=geo["nbt"], n_k=geo["n_k"], bi=bm, bj=bm, bc=bk)
    t = _traffic(spec, left_bytes=in_bytes, right_bytes=in_bytes,
                 out_bytes=out_bytes)
    t["intermediate_bytes"] = M * N * in_bytes if (M, N) != (m, n) else 0
    t["padded_shape"] = (M, N)
    return t


def rank_k_traffic_model(
    m: int, n: int, *, levels: int = 2, variant: str = "strassen",
    gram: str = "strassen", gram_of: str = "cols",
    bk: int = 256, bn: int = 256, state_bytes: int = 4, in_bytes: int = 4,
) -> dict:
    """HBM bytes of one ``fused_rank_k_update`` chunk vs the status-quo
    streamed update it replaces (ata kernel + delta stack + gather-add:
    the delta stack is written and re-read, and the state is read and
    rewritten).  ``m`` is the contraction length and ``n`` the Gram
    side, on either ``gram_of`` side."""
    T = _round_up(max(n, 1), bn) // bn
    # the stack layout fixes T; mirror the executor's divisibility clamp
    geo = _rank_k_geometry(m, T, levels, variant, bk, gram=gram,
                           gram_of=gram_of)
    M, N = geo["M"], T * bn
    spec = _bind(geo["plan"], n_out=geo["n_tri"], n_tj=0, q_i=geo["nbt"],
                 q_j=geo["nbt"], n_k=geo["n_k"], bi=bn, bj=bn, bc=bk)
    t = _traffic(spec, left_bytes=in_bytes, right_bytes=in_bytes,
                 out_bytes=state_bytes, cin_bytes=state_bytes)
    stack_bytes = geo["n_tri"] * bn * bn * state_bytes
    t["intermediate_bytes"] = (M * N * in_bytes if (M, N) != (m, n) else 0)
    t["padded_shape"] = (M, N)
    t["state_bytes"] = stack_bytes
    # status quo (PR 2-4 stream updater): fused ata writes a delta stack,
    # the gather reads it, and the add reads + writes the state.
    t["baseline"] = {
        "read_bytes": (t["read_bytes"] - stack_bytes) + 2 * stack_bytes,
        "write_bytes": 2 * stack_bytes,
        "intermediate_bytes": t["intermediate_bytes"] + stack_bytes,
    }
    return t


def ata_bwd_traffic_model(
    m: int, n: int, *, levels: int = 2, variant: str = "strassen",
    gram: str = "strassen",
    bk: int = 256, bn: int = 256, in_bytes: int = 4, cot_bytes: int = 4,
    cotangent: str = "packed",
) -> dict:
    """HBM bytes of the Gram *backward* ``dA = A (S + S^t)`` on an (m, n)
    forward problem — the fused symm-program kernel vs the dense-dot
    baseline it replaces.  Shares ``_ata_geometry`` / ``_symm_geometry``
    with the executors, so the model cannot drift from their clamping.

    ``cotangent="packed"``: the cotangent arrives as the packed stack
    (``fused_ata_packed``'s VJP) and feeds the kernel directly — zero
    HBM intermediates beyond an optional pad copy of A.
    ``cotangent="dense"``: the dense entry point first gathers tril(g)
    into the packed stack (the stack — n(n+1)/2-ish bytes — is the only
    temporary).

    The baseline models what the dense-dot backward materializes
    semantically: ``tril(g)`` (select), ``S^t`` (transpose) and
    ``S + S^t`` (add) — three dense N^2 buffers.  An
    ``hbm_intermediate_census`` of its compiled HLO lands near this
    (XLA fusion may materialize fewer; the packed entry's unpack scatter
    adds more).  The fused read term counts the real-entry walk of the
    depth>=2 kernel, same as the forward model.
    """
    geo = _ata_geometry(m, n, levels, variant, bk, bn, gram=gram)
    M, N = geo["M"], geo["N"]
    T = N // bn
    sgeo = _symm_geometry(M, T, geo["levels"], variant, bk)
    plan, q = sgeo["plan"], sgeo["q"]
    assert sgeo["M"] == M, (sgeo["M"], M)   # bwd reuses the forward padding
    spec = _bind(plan, n_out=(M // bk) * T, n_tj=T, q_i=sgeo["nbm"],
                 q_j=q, n_k=q, bi=bk, bj=bn, bc=bn, diag_sym=True)
    t = _traffic(spec, left_bytes=in_bytes, right_bytes=cot_bytes,
                 out_bytes=4)            # dA in the fp32 accum dtype
    stack_bytes = T * (T + 1) // 2 * bn * bn * cot_bytes
    pad_copy = M * N * in_bytes if (M, N) != (m, n) else 0
    fused_inter = pad_copy + (stack_bytes if cotangent == "dense" else 0)
    dense_inter = 3 * N * N * cot_bytes
    t.update({
        "intermediate_bytes": fused_inter,
        "packed_stack_bytes": stack_bytes,
        "padded_shape": (M, N),
        "levels": sgeo["levels"],
        "dense_baseline": {
            "read_bytes": M * N * in_bytes + N * N * cot_bytes,
            "write_bytes": M * N * 4,
            "intermediate_bytes": dense_inter,
        },
        "intermediate_ratio_dense_over_fused": (
            dense_inter / fused_inter if fused_inter else None),
    })
    return t


def matmul_traffic_model(
    m: int, k: int, n: int, *, levels: int = 2, variant: str = "strassen",
    bm: int = 256, bk: int = 256, bn: int = 256, in_bytes: int = 4,
    out_bytes: int = 4,
) -> dict:
    """HBM bytes of ``fused_matmul`` for ``op(A) (m, k) @ op(B) (k, n)``
    (the transposes change index maps, not fetch counts)."""
    geo = _matmul_geometry(m, k, n, levels, variant, bm, bk, bn)
    M, K, N = geo["M"], geo["K"], geo["N"]
    spec = _bind(geo["plan"], n_out=(M // bm) * (N // bn), n_tj=N // bn,
                 q_i=geo["mb"] // bm, q_j=geo["nb"] // bn,
                 n_k=geo["kb"] // bk, bi=bm, bj=bn, bc=bk)
    t = _traffic(spec, left_bytes=in_bytes, right_bytes=in_bytes,
                 out_bytes=out_bytes)
    t["intermediate_bytes"] = ((M * K if (M, K) != (m, k) else 0)
                               + (K * N if (K, N) != (k, n) else 0)
                               ) * in_bytes
    t["padded_shape"] = (M, K, N)
    return t


# Two depths whose scores lie within this share of each other tie, and a
# tie goes to the shallower program: fewer roundings, smaller tables.
AUTO_TIE_SHARE = 0.01


def auto_levels(kind: str, m: int, n: int, *, k: Optional[int] = None,
                gram_of: str = "cols", variant: str = "strassen",
                gram: str = "strassen", bm: int = 256, bk: int = 256,
                bn: int = 256, operand_dtype=jnp.float32,
                out_dtype=jnp.float32, pipeline_depth=None,
                interpret=None) -> int:
    """The Strassen depth ``levels="auto"`` runs on a fused call: the
    cheapest of depths 0 to ``AUTO_MAX_LEVELS`` by the executor's own
    traffic model, scored with ``cost_model.pipelined_bytes_score`` at
    the pipeline depth the call will run.  Depths whose scores lie within
    ``AUTO_TIE_SHARE`` of the best tie, and the shallowest of them wins.
    Depths past the VMEM fan-in or SMEM table limits are not tried.

    The choice rests on the shape, the dtypes and the tiles alone.  The
    executor recomputes a Strassen product once for each destination it
    feeds, so on today's walk a deeper program reads more tile bytes
    than it saves in products, and the rule picks classical blocking; a
    schedule that makes Strassen cheaper in bytes is picked by the same
    rule.  The depth that ran is recorded in the kernel's scope name
    (``fused:<kind>:l<levels>:...``, in the HLO metadata and the device
    trace), so no counter repeats it.

    Shapes by ``kind``: ``"ata"`` and ``"aat"`` take A's (m, n);
    ``"rank_k"`` the contraction length ``m`` and the stack's Gram span
    ``n`` (``gram_of`` names the side); ``"matmul"`` ``op(A) (m, k) @
    op(B) (k, n)``.  Tiles as the kind's traffic model names them.
    ``out_dtype`` is the output's (for ``rank_k``, the stack's) dtype.
    """
    from ..core.strassen import AUTO_MAX_LEVELS
    if kind not in ("ata", "aat", "rank_k", "matmul"):
        raise ValueError(f"no auto depth for kind {kind!r}")
    if kind == "matmul" and k is None:
        raise ValueError("matmul needs the contraction length k")
    depth = _resolve_pipeline_depth(
        pipeline_depth, _auto_interpret(interpret, site="auto_levels"))
    tiles = {"ata": (bk, bn), "aat": (bm, bk), "rank_k": (bk, bn),
             "matmul": (bm, bk, bn)}[kind]
    return _auto_levels(kind, m, n, k, gram_of, variant, gram, tiles,
                        jnp.dtype(operand_dtype).itemsize,
                        jnp.dtype(out_dtype).itemsize, depth,
                        AUTO_MAX_LEVELS)


@functools.lru_cache(maxsize=None)
def _auto_levels(kind, m, n, k, gram_of, variant, gram, tiles, in_bytes,
                 out_bytes, depth, max_levels) -> int:
    from ..core.cost_model import pipelined_bytes_score

    def model(levels):
        if kind == "ata":
            return ata_traffic_model(m, n, levels=levels, variant=variant,
                                     gram=gram, bk=tiles[0], bn=tiles[1],
                                     in_bytes=in_bytes, out_bytes=out_bytes)
        if kind == "aat":
            return aat_traffic_model(m, n, levels=levels, variant=variant,
                                     gram=gram, bm=tiles[0], bk=tiles[1],
                                     in_bytes=in_bytes, out_bytes=out_bytes)
        if kind == "rank_k":
            return rank_k_traffic_model(m, n, levels=levels,
                                        variant=variant, gram=gram,
                                        gram_of=gram_of, bk=tiles[0],
                                        bn=tiles[1], in_bytes=in_bytes,
                                        state_bytes=out_bytes)
        return matmul_traffic_model(m, k, n, levels=levels, variant=variant,
                                    bm=tiles[0], bk=tiles[1], bn=tiles[2],
                                    in_bytes=in_bytes, out_bytes=out_bytes)

    scores = []
    for levels in range(_fan_in_depth(kind, max_levels, variant, gram) + 1):
        t = model(levels)
        scores.append(pipelined_bytes_score(
            t["read_bytes"] + t["intermediate_bytes"], t["write_bytes"],
            t["flops"], pipeline_depth=depth, grid_steps=t["grid_steps"]))
    best = min(scores)
    return next(lv for lv, sc in enumerate(scores)
                if sc <= best * (1 + AUTO_TIE_SHARE))


leaf_ir.on_algebra_change(_auto_levels.cache_clear)


# ---------------------------------------------------------------------------
# Fused Strassen matmul: C = op(A) @ op(B), dense output.
# ---------------------------------------------------------------------------

def fused_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    levels: int = 2,
    variant: str = "strassen",
    bm: int = 256,
    bk: int = 256,
    bn: int = 256,
    trans_a: bool = False,
    trans_b: bool = False,
    out_dtype=None,
    interpret=None,
    bwd: str = "fused",
    pipeline_depth=None,
    operand_dtype=None,
    acc_dtype=None,
) -> jax.Array:
    """``op(a) @ op(b)`` via the flattened Strassen program, one fused
    kernel; ``op`` transposes when the flag is set — folded into the
    BlockSpec index maps (mirrored tile fetches), so no transposed copy
    of an operand ever exists in HBM.  The engine of the distributed
    ring/2.5D block tasks (``core.distributed``), which are all
    ``A_loc^t @ A_perm`` products.

    Same fusion contract as :func:`fused_ata_packed`: operand sums live
    in VMEM only, every output tile is written once, no ``M_i`` in HBM;
    the same level/fan-in clamps keep leaves at tile granularity and the
    operand gather inside VMEM.

    Differentiable: ``bwd="fused"`` (default) runs both VJP products
    through the same program executor with the transposes folded into
    the index maps, so the backward costs what the forward costs.
    ``bwd="dense"`` keeps the classical ``jnp.dot`` VJP.
    """
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"bad shapes for matmul: {a.shape} x {b.shape}")
    k_a = a.shape[0] if trans_a else a.shape[1]
    k_b = b.shape[1] if trans_b else b.shape[0]
    if k_a != k_b:
        raise ValueError(
            f"bad shapes for matmul: {a.shape} x {b.shape} "
            f"(trans_a={trans_a}, trans_b={trans_b})")
    interpret = _auto_interpret(interpret, site="fused_matmul")
    depth = _resolve_pipeline_depth(pipeline_depth, interpret)
    op_dt = _resolve_operand_dtype(operand_dtype)
    acc_dt = _resolve_acc_dtype(acc_dtype)
    out_dtype = (jnp.promote_types(jnp.promote_types(a.dtype, b.dtype),
                                   jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))
    return _fused_matmul_core(a, b, levels, variant, bm, bk, bn, trans_a,
                              trans_b, out_dtype, interpret, bwd, depth,
                              op_dt, acc_dt)


def _fused_matmul_exec(a, b, levels, variant, bm, bk, bn, out_dtype,
                       interpret, trans_a=False, trans_b=False,
                       pipeline_depth=1, operand_dtype=None,
                       acc_dtype="float32"):
    """Executor binding for C = op(a) @ op(b)."""
    m, k_dim = a.shape[::-1] if trans_a else a.shape
    n, _ = b.shape if trans_b else b.shape[::-1]
    geo = _matmul_geometry(m, k_dim, n, levels, variant, bm, bk, bn,
                           trans_a=trans_a, trans_b=trans_b)
    plan, mb, kb, nb = geo["plan"], geo["mb"], geo["kb"], geo["nb"]
    M, K, N = geo["M"], geo["K"], geo["N"]
    a_shape = (K, M) if trans_a else (M, K)
    b_shape = (N, K) if trans_b else (K, N)
    if a.shape != a_shape:
        a = jnp.pad(a, [(0, t - s) for s, t in zip(a.shape, a_shape)])
    if b.shape != b_shape:
        b = jnp.pad(b, [(0, t - s) for s, t in zip(b.shape, b_shape)])
    if operand_dtype is not None:
        a = a.astype(jnp.dtype(operand_dtype))
        b = b.astype(jnp.dtype(operand_dtype))

    nbm, nbn = mb // bm, nb // bn
    spec = _bind(plan, n_out=(M // bm) * (N // bn), n_tj=N // bn,
                 q_i=nbm, q_j=nbn, n_k=kb // bk, bi=bm, bj=bn, bc=bk,
                 pipeline_depth=pipeline_depth, acc_dtype=acc_dtype)
    out = _execute(spec, a, b, out_dtype, interpret)
    return out[:m, :n]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13,
                                    14))
def _fused_matmul_core(a, b, levels, variant, bm, bk, bn, trans_a, trans_b,
                       out_dtype, interpret, bwd, pipeline_depth,
                       operand_dtype, acc_dtype):
    return _fused_matmul_exec(a, b, levels, variant, bm, bk, bn, out_dtype,
                              interpret, trans_a=trans_a, trans_b=trans_b,
                              pipeline_depth=pipeline_depth,
                              operand_dtype=operand_dtype,
                              acc_dtype=acc_dtype)


def _fused_matmul_fwd(a, b, levels, variant, bm, bk, bn, trans_a, trans_b,
                      out_dtype, interpret, bwd, pipeline_depth,
                      operand_dtype, acc_dtype):
    return (_fused_matmul_core(a, b, levels, variant, bm, bk, bn, trans_a,
                               trans_b, out_dtype, interpret, bwd,
                               pipeline_depth, operand_dtype, acc_dtype),
            (a, b))


def _fused_matmul_bwd(levels, variant, bm, bk, bn, trans_a, trans_b,
                      out_dtype, interpret, bwd, pipeline_depth,
                      operand_dtype, acc_dtype, res, g):
    a, b = res
    acc = jnp.promote_types(jnp.promote_types(a.dtype, b.dtype), jnp.float32)
    gf = g.astype(acc)
    if bwd == "dense":
        op_a = (lambda x: x.T.astype(acc)) if trans_a else \
            (lambda x: x.astype(acc))
        op_b = (lambda x: x.T.astype(acc)) if trans_b else \
            (lambda x: x.astype(acc))
        ca, cb = op_a(a), op_b(b)
        da = jnp.dot(gf, cb.T, preferred_element_type=acc)
        db = jnp.dot(ca.T, gf, preferred_element_type=acc)
        if trans_a:
            da = da.T
        if trans_b:
            db = db.T
    else:
        # the VJP products are themselves matmul programs with the
        # transposes folded into the index maps (the kernel upcasts
        # tile-wise in VMEM, so bf16 residuals feed the backward
        # without an HBM-wide fp32 copy):
        ex = functools.partial(_fused_matmul_exec, levels=levels,
                               variant=variant, out_dtype=acc,
                               interpret=interpret,
                               pipeline_depth=pipeline_depth)
        if not trans_a and not trans_b:
            # da = g b^t; db = a^t g
            da = ex(gf, b, bm=bm, bk=bn, bn=bk, trans_b=True)
            db = ex(a, gf, bm=bk, bk=bm, bn=bn, trans_a=True)
        elif trans_a and trans_b:
            # C = a^t b^t: da = b^t g^t (stored (k, m));
            #              db = g^t a^t (stored (n, k))
            da = ex(b, gf, bm=bk, bk=bn, bn=bm, trans_a=True, trans_b=True)
            db = ex(gf, a, bm=bn, bk=bm, bn=bk, trans_a=True, trans_b=True)
        elif trans_a:
            # C = a^t b: da = b g^t (stored (k, m)); db = a g
            da = ex(b, gf, bm=bk, bk=bn, bn=bm, trans_b=True)
            db = ex(a, gf, bm=bk, bk=bm, bn=bn)
        else:
            # C = a b^t: da = g b (b stored (n, k)); db = g^t a
            da = ex(gf, b, bm=bm, bk=bn, bn=bk)
            db = ex(gf, a, bm=bn, bk=bm, bn=bk, trans_a=True)
    return da.astype(a.dtype), db.astype(b.dtype)


_fused_matmul_core.defvjp(_fused_matmul_fwd, _fused_matmul_bwd)
