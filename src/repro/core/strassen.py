"""HASA: Strassen's algorithm generalized to rectangular / odd-size matrices.

Faithful to the paper's use of D'Alberto & Nicolau's generalized Strassen
("HASA") as the subroutine for the off-diagonal block C21 = A12^t A11 +
A22^t A21 of the ATA recursion.

TPU adaptation (see DESIGN.md §2): the recursion is unrolled at *trace* time
(Python recursion over static shapes), capped at ``levels`` to bound jaxpr
growth; below the cap we fall back to a base matmul that is either
``jnp.dot`` (XLA) or the Pallas MXU kernel. Odd dimensions are handled by
zero-padding to even (equivalent to the paper's peeling, but keeps every
quadrant MXU-shaped), and the padding is sliced away on the way out.

Accumulation dtype is fp32 even for bf16 inputs — Strassen's recombination
loses ~1 bit/level, so we never accumulate in bf16.  For the same reason
results default to the promoted accumulation dtype (``out_dtype=``
downcasts explicitly when the caller wants the input dtype back).

``mode="fused"`` executes through the flattened leaf-task schedule
(``core/schedule.py`` + ``kernels/strassen_fused.py``) instead of this
recursion — see DESIGN.md §4 and the docstring in ``ata.py``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

# Base-case threshold: Strassen recursion stops when any dim is <= this.
# Paper uses 32 (CPU cache line / load-store cost balance). On TPU the MXU is
# a 128x128 systolic array, so sub-128 tiles waste the unit: we stop at 256.
DEFAULT_LEAF = 256
DEFAULT_LEVELS = 2

# Deepest depth levels="auto" considers.  On the fused path "auto" is the
# cheapest depth up to this one by the executor's own traffic model
# (``kernels.strassen_fused.auto_levels``): the executor recomputes each
# Strassen product once for every destination it feeds, so a deeper
# program reads more tile bytes than it saves in products, and the model
# picks classical blocking on today's walk.  In reference mode "auto" is
# the recursion's natural depth (down to ``leaf``), capped here: each
# level costs one more bit of accumulated error and exponentially larger
# schedules/jaxprs (paper §6 uses 1-2 parallel levels for the same reason).
AUTO_MAX_LEVELS = 3


def resolve_mode(mode: str, *leaf_hooks) -> str:
    """Resolve mode="auto" -> "fused" | "reference".

    Fused is the default on TPU; custom leaf hooks (base_syrk/base_matmul)
    force the reference recursion because the flattened schedule has no
    per-leaf call-out.  Off-TPU the reference recursion is both faster
    (XLA-compiled vs interpreted Pallas) and differentiable, so it stays
    the default there; tests exercise the fused path with interpret=True
    explicitly.
    """
    if mode == "auto":
        if any(h is not None for h in leaf_hooks):
            return "reference"
        return "fused" if jax.default_backend() == "tpu" else "reference"
    if mode not in ("fused", "reference"):
        raise ValueError(f"unknown mode {mode!r} "
                         "(want 'auto' | 'fused' | 'reference')")
    if mode == "fused" and any(h is not None for h in leaf_hooks):
        raise ValueError(
            "mode='fused' cannot honor base_syrk/base_matmul leaf hooks "
            "(the flattened schedule has no per-leaf call-out) — use "
            "mode='reference' or drop the hooks")
    return mode


def _default_base_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """Classical base-case matmul with >=fp32 accumulation."""
    acc = jnp.promote_types(jnp.promote_types(a.dtype, b.dtype), jnp.float32)
    return jnp.dot(a, b, preferred_element_type=acc)


def _pad_to_even(x: jax.Array) -> jax.Array:
    m, n = x.shape
    pm, pn = m % 2, n % 2
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _quadrants(x: jax.Array):
    m, n = x.shape
    m2, n2 = m // 2, n // 2
    return (x[:m2, :n2], x[:m2, n2:], x[m2:, :n2], x[m2:, n2:])


def strassen_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    levels: Union[int, str] = DEFAULT_LEVELS,
    leaf: int = DEFAULT_LEAF,
    variant: str = "strassen",
    base_matmul: Optional[Callable] = None,
    mode: str = "auto",
    bwd: str = "fused",
    trans_a: bool = False,
    trans_b: bool = False,
    out_dtype=None,
    block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Compute ``op(a) @ op(b)`` via (level-capped) Strassen recursion,
    ``op`` = transpose where the flag is set.

    Args:
      a: (m, k) array — or (k, m) with ``trans_a``.
      b: (k, n) array — or (n, k) with ``trans_b``.
      trans_a, trans_b: use an operand transposed.  The fused path folds
        the transpose into the executor's index maps (no transposed HBM
        copy — this is how ``core.distributed``'s ``A_loc^t A_perm``
        ring block tasks run); the reference recursion materializes
        ``.T`` (the oracle).
      levels: max recursion depth (0 => classical), or ``"auto"``: on
        the fused path the executor's cheapest depth by its traffic model
        (``strassen_fused.auto_levels``), in reference mode recursing
        until a dim hits ``leaf`` (capped at AUTO_MAX_LEVELS).
      leaf: stop recursing when min(m, k, n) <= leaf (reference mode; also
        sets the reference "auto" depth).
      variant: "strassen" (7 mults / 18 adds, as in the paper),
               "winograd" (7 mults / 15 adds, beyond-paper option) or
               "classical".
      base_matmul: leaf matmul; defaults to jnp.dot w/ fp32 accumulation.
        Forces reference mode under ``mode="auto"``.
      mode: "auto" | "fused" | "reference" — fused executes the flattened
        schedule in one Pallas kernel (no per-level HBM temporaries).
      bwd: fused-path VJP engine — "fused" (default: both VJP products
        through the schedule kernel, transposes folded into index maps)
        or "dense" (classical jnp.dot VJP).  Reference mode ignores it.
      out_dtype: result dtype; defaults to the promoted *accumulation*
        dtype (fp32 for bf16/fp32 inputs) rather than downcasting.
      block: Pallas tile edge for the fused path (bm = bk = bn = block);
        ``None`` consults the gram autotune cache (256 when untuned).
      interpret: Pallas interpret override for the fused path.

    Returns (m, n) array in ``out_dtype``.
    """
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"bad shapes for matmul: {a.shape} x {b.shape}")
    m, k_a = a.shape[::-1] if trans_a else a.shape
    k_b, n = b.shape[::-1] if trans_b else b.shape
    if k_a != k_b:
        raise ValueError(
            f"bad shapes for matmul: {a.shape} x {b.shape} "
            f"(trans_a={trans_a}, trans_b={trans_b})")
    out_dtype = (jnp.promote_types(jnp.promote_types(a.dtype, b.dtype),
                                   jnp.float32)
                 if out_dtype is None else jnp.dtype(out_dtype))
    mode = resolve_mode(mode, base_matmul)
    if levels == "auto" and mode == "fused":
        from ..kernels.ops import _resolve_blocks
        from ..kernels.strassen_fused import auto_levels
        levels = auto_levels(
            "matmul", m, n, k=k_a, variant=variant,
            **_resolve_blocks("matmul", m, n, a.dtype, bm=block, bk=block,
                              bn=block),
            operand_dtype=jnp.promote_types(a.dtype, b.dtype),
            out_dtype=out_dtype)
    elif levels == "auto":
        levels = min(strassen_levels_for(m, k_a, n, leaf), AUTO_MAX_LEVELS)
    if mode == "fused":
        from ..kernels.ops import matmul_fused
        return matmul_fused(a, b, levels=levels, variant=variant, bm=block,
                            bk=block, bn=block, trans_a=trans_a,
                            trans_b=trans_b, out_dtype=out_dtype,
                            interpret=interpret, bwd=bwd)
    base = base_matmul or _default_base_matmul
    # reference oracle: materialize the transposes (the fused executor's
    # index-map folding is exactly what removes these copies)
    res = _strassen_rec(a.T if trans_a else a, b.T if trans_b else b,
                        levels, leaf, variant, base)
    return res.astype(out_dtype)


def _strassen_rec(a, b, levels, leaf, variant, base):
    m, k = a.shape
    _, n = b.shape
    if variant == "classical" or levels <= 0 or min(m, k, n) <= leaf:
        return base(a, b)

    # Pad odd dims to even so quadrants are well-formed (HASA handles
    # arbitrary sizes; zero-padding is the TPU-friendly equivalent of
    # peeling and is exact).
    ap, bp = _pad_to_even(a), _pad_to_even(b)
    mp, kp = ap.shape
    _, np_ = bp.shape

    a11, a12, a21, a22 = _quadrants(ap)
    b11, b12, b21, b22 = _quadrants(bp)

    rec = functools.partial(
        _strassen_rec, levels=levels - 1, leaf=leaf, variant=variant, base=base
    )

    if variant == "strassen":
        # The 7 products as distributed to P_ids0..P_ids6 in the paper's
        # HASA-P (§4.3.2). NOTE: the paper's listing types M7's second
        # operand as (B21 - B22); Strassen's identity requires (B21 + B22)
        # — verified numerically, recorded in DESIGN.md §9.
        m1 = rec(a11 + a22, b11 + b22)
        m2 = rec(a21 + a22, b11)
        m3 = rec(a11, b12 - b22)
        m4 = rec(a22, b21 - b11)
        m5 = rec(a11 + a12, b22)
        m6 = rec(a21 - a11, b11 + b12)
        m7 = rec(a12 - a22, b21 + b22)
        c11 = m1 + m4 - m5 + m7
        c12 = m3 + m5
        c21 = m2 + m4
        c22 = m1 - m2 + m3 + m6
    elif variant == "winograd":
        # Winograd's variant: 7 mults, 15 adds (beyond-paper constant-factor
        # improvement mentioned in §1 of the paper).
        s1 = a21 + a22
        s2 = s1 - a11
        s3 = a11 - a21
        s4 = a12 - s2
        t1 = b12 - b11
        t2 = b22 - t1
        t3 = b22 - b12
        t4 = t2 - b21
        m1 = rec(a11, b11)
        m2 = rec(a12, b21)
        m3 = rec(s4, b22)
        m4 = rec(a22, t4)
        m5 = rec(s1, t1)
        m6 = rec(s2, t2)
        m7 = rec(s3, t3)
        u1 = m1 + m6
        u2 = u1 + m7
        u3 = u1 + m5
        c11 = m1 + m2
        c12 = u3 + m3
        c21 = u2 - m4
        c22 = u2 + m5
    else:
        raise ValueError(f"unknown variant {variant!r}")

    c = jnp.concatenate(
        [jnp.concatenate([c11, c12], axis=1), jnp.concatenate([c21, c22], axis=1)],
        axis=0,
    )
    return c[:m, :n]  # strip padding


def strassen_levels_for(m: int, k: int, n: int, leaf: int = DEFAULT_LEAF) -> int:
    """Natural number of Strassen levels for a problem (cache-oblivious
    analogue: recurse until the leaf threshold)."""
    leaf = max(leaf, 1)        # (1+1)//2 == 1: leaf=0 would never terminate
    lv = 0
    while min(m, k, n) > leaf:
        m, k, n = (m + 1) // 2, (k + 1) // 2, (n + 1) // 2
        lv += 1
    return lv
