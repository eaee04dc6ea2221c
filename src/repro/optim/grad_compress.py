"""Error-feedback gradient compression for cross-pod all-reduce.

At 2+ pods the inter-pod links are the scarcest bandwidth.  Two schemes,
both Seide-et-al.-style error feedback (the compression residual is kept
locally and added back before the next compression, so the *accumulated*
error stays bounded and convergence is preserved):

* ``compressed_psum`` — int8: each leaf is quantized to int8 with a
  per-leaf fp32 scale before the cross-pod reduction (4x fewer bytes).
* ``lowrank_psum`` — Gram-powered low-rank (PowerSGD-flavored): for tall
  2-D leaves the devices agree on a shared top-``rank`` right-singular
  basis Q by all-reducing the *Gram* of the gradient — `sum_i G_i^t G_i`,
  which is exactly ``core.distributed.gram_allreduce`` over the pod axis,
  i.e. the paper's A^tA as the service op inside a distributed reduction
  — then reduce only the rank-sized projection ``G_i Q``.  Wire payload
  per leaf: n^2 + rank*m words, vs m*n uncompressed — a win for tall
  leaves (m >> n + rank), e.g. embeddings and vocab projections; leaves
  where low-rank does not pay fall back to the int8 path.

Used by the trainer inside ``shard_map`` over the 'pod' axis only; the
intra-pod reduction stays full-precision (fast ICI).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class ErrorFeedback(NamedTuple):
    residual: object            # pytree matching grads, fp32

    @staticmethod
    def init(grads_like):
        return ErrorFeedback(jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads_like))


def int8_quantize(x: jax.Array):
    """fp -> (int8 values, fp32 scale). Symmetric per-tensor quantization."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _int8_leaf(g, r, axis: str, n):
    """One leaf of the int8 error-feedback reduction: (mean grad, residual).

    Wire payload is the int8 tensor (+one fp32 scale) per participant —
    an ``all_gather`` of int8 then a local dequantized sum, exact w.r.t.
    the quantized values (scales differ per pod, so a plain psum of int8
    would be wrong).
    """
    gf = g.astype(jnp.float32) + r
    q, scale = int8_quantize(gf)
    new_r = gf - int8_dequantize(q, scale)            # residual stays local
    qg = jax.lax.all_gather(q, axis)                  # (n, ...) int8 on wire
    sg = jax.lax.all_gather(scale, axis)              # (n,) fp32
    total = jnp.einsum("n,n...->...", sg, qg.astype(jnp.float32))
    return total / n, new_r


def compressed_psum(grads, axis: str, ef: ErrorFeedback):
    """Error-feedback int8 all-reduce over mesh axis ``axis``.

    Must run inside shard_map with ``axis`` in scope.  Returns
    (mean-reduced fp32 grads, new ErrorFeedback).
    """
    n = jax.lax.axis_size(axis)
    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = treedef.flatten_up_to(ef.residual)
    outs = [_int8_leaf(g, r, axis, n) for g, r in zip(flat_g, flat_r)]
    reduced = treedef.unflatten([o[0] for o in outs])
    new_res = treedef.unflatten([o[1] for o in outs])
    return reduced, ErrorFeedback(new_res)


def lowrank_basis(g2d: jax.Array, rank: int, *, levels=1, leaf: int = 256,
                  mode: str = "auto", axis=None) -> jax.Array:
    """Shared top-``rank`` right-singular basis of a (stacked) gradient.

    The basis is the top eigenvectors of the Gram ``sum_i G_i^t G_i`` —
    THE paper's operation, computed through the ATA pipeline: locally via
    ``core.ata.ata_full``, or (``axis`` given, inside shard_map) via
    ``core.distributed.gram_allreduce`` so every participant derives the
    *same* basis from the stacked-gradient Gram.
    """
    if axis is None:
        from ..core.ata import ata_full
        c = ata_full(g2d.astype(jnp.float32), levels=levels, leaf=leaf,
                     mode=mode, out_dtype=jnp.float32)
    else:
        from ..core.distributed import gram_allreduce
        c = gram_allreduce(g2d.astype(jnp.float32), axis, levels=levels,
                           leaf=leaf, mode=mode, out_dtype=jnp.float32)
    _, v = jnp.linalg.eigh(c)                  # ascending eigenvalues
    return v[:, -rank:]                        # (n, rank), orthonormal


def lowrank_psum(grads, axis: str, ef: ErrorFeedback, *, rank: int = 8,
                 levels=1, leaf: int = 256, mode: str = "auto",
                 min_rows: int = 0):
    """Gram-powered low-rank error-feedback all-reduce (module docstring).

    2-D leaves with ``m > max(min_rows, n + rank)`` (where low-rank beats
    shipping the leaf) are reduced as ``mean(G) Q Q^t`` with the shared
    basis Q from :func:`lowrank_basis`; everything else takes the int8
    path.  Must run inside shard_map with ``axis`` in scope.  Returns
    (mean-reduced fp32 grads, new ErrorFeedback).
    """
    n_dev = jax.lax.axis_size(axis)

    def leaf_fn(g, r):
        m_n = g.shape
        if len(m_n) != 2 or m_n[0] <= max(min_rows, m_n[1] + rank) \
                or m_n[1] <= rank:
            return _int8_leaf(g, r, axis, n_dev)
        gf = g.astype(jnp.float32) + r
        q = lowrank_basis(gf, rank, levels=levels, leaf=leaf, mode=mode,
                          axis=axis)
        p = jax.lax.psum(gf @ q, axis) / n_dev     # (m, rank) on the wire
        approx = p @ q.T                           # mean(G) projected on Q
        new_r = gf - (gf @ q) @ q.T                # local reconstruction err
        return approx, new_r

    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = treedef.flatten_up_to(ef.residual)
    outs = [leaf_fn(g, r) for g, r in zip(flat_g, flat_r)]
    reduced = treedef.unflatten([o[0] for o in outs])
    new_res = treedef.unflatten([o[1] for o in outs])
    return reduced, ErrorFeedback(new_res)
