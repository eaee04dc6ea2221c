"""SYRK Pallas kernel: C = A^t A computing ONLY lower-triangular blocks.

This is the paper's central memory/work saving (store n(n+1)/2 instead of
n^2) realized at TPU block granularity: the grid enumerates the T(T+1)/2
lower-triangular (i, j) block pairs — upper blocks are never scheduled, so
both the MXU work and the HBM writes for them simply do not exist.

Output is the *packed triangular block stack* of shape (T(T+1)/2 * bn, bn)
(block t at rows [t*bn, (t+1)*bn)), matching
``repro.core.symmetry.pack_tril_blocks`` ordering; unpack with
``unpack_tril_blocks``.

The linear grid index t is decoded to (i, j) inside the index_maps with an
integer-corrected float sqrt (exact for t < 2^22, far beyond any real T).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tri_decode(t):
    """Linear lower-triangular index -> (i, j), i >= j, row-major."""
    tf = t.astype(jnp.float32)
    i = ((jnp.sqrt(8.0 * tf + 1.0) - 1.0) * 0.5).astype(jnp.int32)
    # float-precision correction (at most one step either way)
    i = jnp.where((i + 1) * (i + 2) // 2 <= t, i + 1, i)
    i = jnp.where(i * (i + 1) // 2 > t, i - 1, i)
    j = t - i * (i + 1) // 2
    return i, j


def _syrk_kernel(ai_ref, aj_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (bk, bn)^T @ (bk, bn) -> (bn, bn) on the MXU, fp32 accumulation.
    acc_ref[...] += jnp.dot(
        ai_ref[...].T, aj_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def syrk_packed(
    a: jax.Array,
    *,
    bk: int = 256,
    bn: int = 256,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Packed lower-triangular block stack of ``a.T @ a``.

    ``a``: (M, N) with M % bk == 0, N % bn == 0 (ops.syrk pads).
    Returns (T(T+1)/2 * bn, bn) with T = N // bn.
    """
    m, n = a.shape
    assert m % bk == 0 and n % bn == 0, (a.shape, bk, bn)
    t_blocks = n // bn
    n_tri = t_blocks * (t_blocks + 1) // 2
    n_k = m // bk
    out_dtype = out_dtype or a.dtype

    def ai_map(t, k):
        i, _ = _tri_decode(t)
        return (k, i)

    def aj_map(t, k):
        _, j = _tri_decode(t)
        return (k, j)

    return pl.pallas_call(
        functools.partial(_syrk_kernel, n_k=n_k),
        grid=(n_tri, n_k),
        in_specs=[
            pl.BlockSpec((bk, bn), ai_map),
            pl.BlockSpec((bk, bn), aj_map),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda t, k: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tri * bn, bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        # output tiles (t) are independent -> megacore can partition them;
        # the K sweep carries the VMEM accumulator and stays sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, a)
