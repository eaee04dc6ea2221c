"""ATA: the paper's cache-oblivious Strassen-based algorithm for C = A^t A.

Algorithm 1 of the paper, adapted for TPU (DESIGN.md §2):

    split A into quadrants A11 A12 / A21 A22, then
      C11 = ATA(A11) + ATA(A21)                  (recursive, symmetric)
      C22 = ATA(A12) + ATA(A22)                  (recursive, symmetric)
      C21 = HASA(A12^t, A11) + HASA(A22^t, A21)  (rectangular Strassen)
      C12 = C21^t                                (never computed)

Only the lower triangle is computed; multiplication count is upper-bounded
by (2/7) n^{log2 7} (paper §3.1) versus n^2(n+1)/2 classical.

Two execution modes (DESIGN.md §4):

* ``mode="fused"`` — the hot path.  The recursion is flattened at trace
  time into a leaf-task schedule (``core/schedule.py``) and executed by a
  single Pallas kernel (``kernels/strassen_fused.py``): operand sums live
  in VMEM, products accumulate in fp32 VMEM scratch, and each packed
  lower-triangular output block is written to HBM exactly once.
* ``mode="reference"`` — the original trace-time recursion, capped at
  ``levels``.  Materializes per-level temporaries in HBM; kept as the
  numerical oracle, for autodiff, and for custom ``base_syrk`` /
  ``base_matmul`` hooks.

``mode="auto"`` picks fused on TPU (reference when custom leaf hooks are
given, which the fused schedule cannot honor) and reference elsewhere.
"""
from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from .strassen import (
    strassen_matmul, resolve_mode, AUTO_MAX_LEVELS, DEFAULT_LEAF,
    DEFAULT_LEVELS,
)
from .symmetry import symmetrize_from_lower

__all__ = ["ata", "ata_full", "ata_levels_for", "resolve_levels"]


def _default_base_syrk(a: jax.Array) -> jax.Array:
    """Classical leaf gram with >=fp32 accumulation (lower triangle kept)."""
    acc = jnp.promote_types(a.dtype, jnp.float32)
    return jnp.tril(jnp.dot(a.T, a, preferred_element_type=acc))


def ata(
    a: jax.Array,
    *,
    gram_of: str = "cols",
    levels: Union[int, str] = DEFAULT_LEVELS,
    leaf: int = DEFAULT_LEAF,
    variant: str = "strassen",
    gram: str = "strassen",
    base_syrk: Optional[Callable] = None,
    base_matmul: Optional[Callable] = None,
    mode: str = "auto",
    bwd: str = "fused",
    out_dtype=None,
    block: Optional[int] = None,
    interpret: Optional[bool] = None,
    pipeline_depth: Optional[int] = None,
    operand_dtype=None,
    acc_dtype=None,
    sr_seed: Optional[int] = None,
) -> jax.Array:
    """Lower triangle of ``a.T @ a`` via the paper's ATA recursion.

    Args:
      a: (m, n) array — general rectangular, any size.
      gram_of: which gram to compute — ``"cols"`` (default, the paper's
        ``tril(a.T @ a)``, an (n, n) result) or ``"rows"``
        (``tril(a @ a.T)``, an (m, m) result — the Arrigoni-Massini 2021
        transpose-gram recursion).  On the fused path ``"rows"`` runs
        the dedicated ``aat`` leaf program: the transpose of ``a`` never
        materializes in HBM.  The reference recursion computes it as
        ``ATA(a.T)`` (the identity the 2021 paper exploits), which is
        the oracle but does materialize the transpose.  NOTE: the row
        gram currently differentiates through the dense-dot VJP
        (``dA = (S + S^t) A`` — a symmetric-LEFT product the symm
        program does not yet express), so ``bwd=`` applies to the
        ``"cols"`` path only.
      levels: recursion depth cap (0 => classical SYRK), or ``"auto"``
        (:func:`resolve_levels`): on the fused path the executor's
        cheapest depth by its own traffic model
        (``strassen_fused.auto_levels``); in reference mode the
        recursion's natural depth, until a dimension reaches ``leaf``
        (capped at ``AUTO_MAX_LEVELS``).
      leaf: stop recursing when m or n <= leaf (paper: 32; TPU: 256).
        Reference mode only (the fused schedule unrolls exactly ``levels``,
        and its ``"auto"`` depth ignores ``leaf``).
      variant: Strassen variant for the off-diagonal C21 products
        (any registered algebra — "strassen" | "winograd" | "classical"
        by default; ``leaf_ir.registered_algebras()``).
      gram: registered gram algebra for the symmetric decomposition on
        the FUSED path ("strassen" = the paper's 4-gram + 2-product
        recursion, "dps" = the Dumas-Pernet-Sedoglavic-shaped 5-product
        scheme; ``leaf_ir.registered_gram_algebras()``).  The reference
        recursion is the paper's fixed oracle and ignores it.
      base_syrk: leaf gram fn (n-triangular); default jnp, or Pallas syrk.
        Forces reference mode under ``mode="auto"``.
      base_matmul: leaf matmul for the HASA calls.  Same.
      mode: "auto" | "fused" | "reference" (see module docstring).
      bwd: VJP engine for the fused path — "fused" (default: the
        packed-cotangent symm-schedule kernel, DESIGN.md §11) or "dense"
        (the classical ``A (S + S^t)`` dense-dot baseline).  Reference
        mode differentiates through the recursion and ignores this.
      out_dtype: result dtype.  Defaults to the *promoted accumulation
        dtype* — fp32 for bf16/fp32 inputs — instead of silently
        downcasting fp32-accumulated results back to the input dtype
        (Strassen recombination loses ~1 bit/level; see strassen.py).
      block: Pallas tile edge for the fused path (bk = bn = block);
        ``None`` consults the gram autotune cache for this shape bucket
        (256 when untuned).
      interpret: Pallas interpret-mode override for the fused path
        (default: interpret off-TPU).
      pipeline_depth: revolving-buffer DMA pipeline depth for the fused
        path (DESIGN.md §16).  ``None`` = backend default (2 compiled,
        1 interpret); 1 reproduces the unpipelined grid walk bit-exactly.
      operand_dtype: quantize operand tiles to this dtype (fp8 e4m3/e5m2,
        bf16, ...) before the kernel; accumulation stays >=fp32.  Fused
        path only; ``None`` keeps the native operand dtype.
      acc_dtype: VMEM accumulator storage dtype on the fused path
        (default fp32).
      sr_seed: when set (with bf16 ``out_dtype``), apply deterministic
        stochastic rounding to the fused Gram output under this seed.

    Returns:
      (n, n) array, strictly upper triangle zeroed, dtype ``out_dtype``.
    """
    if a.ndim != 2:
        raise ValueError(f"ata expects a matrix, got shape {a.shape}")
    if gram_of not in ("cols", "rows"):
        raise ValueError(f"gram_of must be 'cols' or 'rows', got "
                         f"{gram_of!r}")
    out_dtype = (jnp.promote_types(a.dtype, jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))
    mode = resolve_mode(mode, base_syrk, base_matmul)
    levels = resolve_levels(levels, a.shape, a.dtype, mode=mode,
                            gram_of=gram_of, leaf=leaf, variant=variant,
                            gram=gram, block=block, out_dtype=out_dtype,
                            operand_dtype=operand_dtype,
                            pipeline_depth=pipeline_depth,
                            interpret=interpret)
    if mode != "fused" and operand_dtype is not None:
        # Reference oracle for quantized operands: quantize once, then
        # recurse in the promoted compute dtype (the fused kernel upcasts
        # quantized tiles to fp32 before every signed sum / dot).
        a = a.astype(jnp.dtype(operand_dtype)).astype(
            jnp.promote_types(a.dtype, jnp.float32))
    if gram_of == "rows":
        if mode == "fused":
            from ..kernels.ops import aat_fused
            return aat_fused(a, levels=levels, variant=variant, gram=gram,
                             bm=block, bk=block, out_dtype=out_dtype,
                             interpret=interpret,
                             pipeline_depth=pipeline_depth,
                             operand_dtype=operand_dtype,
                             acc_dtype=acc_dtype, sr_seed=sr_seed)
        # reference oracle: AAT(A) = ATA(A^t) — the 2021 paper's identity
        syrk = base_syrk or _default_base_syrk
        out = _ata_rec(a.T, levels, leaf, variant, syrk, base_matmul)
        return out.astype(out_dtype)
    if mode == "fused":
        from ..kernels.ops import ata_fused
        return ata_fused(a, levels=levels, variant=variant, gram=gram,
                         bk=block, bn=block, out_dtype=out_dtype,
                         interpret=interpret, bwd=bwd,
                         pipeline_depth=pipeline_depth,
                         operand_dtype=operand_dtype, acc_dtype=acc_dtype,
                         sr_seed=sr_seed)
    syrk = base_syrk or _default_base_syrk
    out = _ata_rec(a, levels, leaf, variant, syrk, base_matmul)
    return out.astype(out_dtype)


def _ata_rec(a, levels, leaf, variant, syrk, base_matmul):
    m, n = a.shape
    # Base case (paper: m or n <= 32; TPU leaf rescaled).
    if levels <= 0 or m <= leaf or n <= leaf:
        return syrk(a)

    # Pad odd dims (exact: zero rows of A add nothing to A^tA; zero cols add
    # zero rows+cols to C, sliced away below).
    pm, pn = m % 2, n % 2
    ap = jnp.pad(a, ((0, pm), (0, pn))) if (pm or pn) else a
    mp, np_ = ap.shape
    m2, n2 = mp // 2, np_ // 2

    a11 = ap[:m2, :n2]
    a12 = ap[:m2, n2:]
    a21 = ap[m2:, :n2]
    a22 = ap[m2:, n2:]

    rec = lambda x: _ata_rec(x, levels - 1, leaf, variant, syrk, base_matmul)

    # C11, C22: sums of two symmetric recursive grams (lines 7-10, Alg. 1).
    c11 = rec(a11) + rec(a21)
    c22 = rec(a12) + rec(a22)

    # C21: two generalized-Strassen rectangular products (lines 11-12).
    c21 = strassen_matmul(
        a12.T, a11, levels=levels - 1, leaf=leaf, variant=variant,
        base_matmul=base_matmul, mode="reference",
    ) + strassen_matmul(
        a22.T, a21, levels=levels - 1, leaf=leaf, variant=variant,
        base_matmul=base_matmul, mode="reference",
    )

    top = jnp.concatenate([c11, jnp.zeros((n2, np_ - n2), c11.dtype)], axis=1)
    bot = jnp.concatenate([c21.astype(c11.dtype), c22], axis=1)
    c = jnp.concatenate([top, bot], axis=0)
    return c[:n, :n]


def ata_full(a: jax.Array, **kw) -> jax.Array:
    """Full symmetric ``a.T @ a`` (mirrors C21 into C12, per the paper)."""
    lower = ata(a, **kw)
    with jax.named_scope("gram:mirror"):
        return symmetrize_from_lower(lower)


def resolve_levels(levels: Union[int, str], shape, dtype, *, mode: str,
                   gram_of: str = "cols", leaf: int = DEFAULT_LEAF,
                   variant: str = "strassen", gram: str = "strassen",
                   block: Optional[int] = None, out_dtype=None,
                   operand_dtype=None, pipeline_depth: Optional[int] = None,
                   interpret: Optional[bool] = None) -> int:
    """The depth :func:`ata` runs for ``levels`` on an A of ``shape`` and
    ``dtype`` under a resolved ``mode``.  An integer is returned as it
    is.  ``"auto"`` on the fused path is the executor's cheapest depth by
    its traffic model (``strassen_fused.auto_levels``), at the tiles,
    dtypes and pipeline depth the call will run; in reference mode it is
    the recursion's natural depth (:func:`ata_levels_for` at ``leaf``,
    capped at ``AUTO_MAX_LEVELS``)."""
    if levels != "auto":
        return levels
    m, n = shape
    if mode != "fused":
        return min(ata_levels_for(m, n, leaf), AUTO_MAX_LEVELS)
    from ..kernels.ops import _resolve_blocks
    from ..kernels.strassen_fused import auto_levels
    if gram_of == "rows":
        kind, tiles = "aat", _resolve_blocks("aat", m, n, dtype, bm=block,
                                             bk=block)
    else:
        kind, tiles = "ata", _resolve_blocks("ata", m, n, dtype, bk=block,
                                             bn=block)
    return auto_levels(
        kind, m, n, variant=variant, gram=gram, **tiles,
        operand_dtype=dtype if operand_dtype is None else operand_dtype,
        out_dtype=(jnp.promote_types(dtype, jnp.float32)
                   if out_dtype is None else out_dtype),
        pipeline_depth=pipeline_depth, interpret=interpret)


def ata_levels_for(m: int, n: int, leaf: int = DEFAULT_LEAF) -> int:
    """Natural recursion depth: recurse until a dim hits the leaf size."""
    leaf = max(leaf, 1)        # (1+1)//2 == 1: leaf=0 would never terminate
    lv = 0
    while m > leaf and n > leaf:
        m, n = (m + 1) // 2, (n + 1) // 2
        lv += 1
    return lv
