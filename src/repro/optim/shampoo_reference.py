"""Plain reference of Distributed Shampoo's statistics, blocked.

Straightforward ``jax.numpy`` in fp32 under ``jax.default_matmul_precision
("highest")``: no kernels, no packing, dense (rows, rows) and (cols, cols)
statistics.  A weight's gradient is cut into blocks exactly as
``shampoo._to_blocks`` cuts it, and every step each block G updates

    L <- beta2 L + (1 - beta2) G G^T,    R <- beta2 R + (1 - beta2) G^T G

(Shi et al., arXiv:2309.06497; ``beta2 = 1`` sums, L <- L + G G^T, as
Distributed Shampoo does).  Departures from Distributed Shampoo: this
computes the statistics only, so no inverse root, grafting or
preconditioned step (those are not timed by the benchmark); no bias
correction of the statistics; and the blocking is ``_to_blocks``', which
pads a ragged edge block with zeros where Distributed Shampoo keeps a
smaller block (zero rows and columns add zeros to a statistic).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .shampoo import _plan, _to_blocks


def init(block_shapes) -> dict:
    """Zero dense statistics of blocks of the given (rows, cols) shapes."""
    return {"l": [jnp.zeros((r, r), jnp.float32) for r, _ in block_shapes],
            "r": [jnp.zeros((c, c), jnp.float32) for _, c in block_shapes]}


def update(stats: dict, grad_blocks, beta2: float) -> dict:
    """One statistics step of every block."""
    alpha = 1.0 if beta2 == 1.0 else 1.0 - beta2
    with jax.default_matmul_precision("highest"):
        return {"l": [beta2 * s + alpha * (g @ g.T)
                      for s, g in zip(stats["l"], grad_blocks)],
                "r": [beta2 * s + alpha * (g.T @ g)
                      for s, g in zip(stats["r"], grad_blocks)]}


def weight_blocks(grad, block_size: int, max_blocks: int = 64) -> list:
    """A weight's gradient cut into Shampoo's blocks, in order."""
    plan = _plan(grad.shape, block_size, max_blocks)
    return list(_to_blocks(jnp.asarray(grad, jnp.float32), plan))


def weight_stats(grads, block_size: int, beta2: float,
                 max_blocks: int = 64) -> dict:
    """The blocked statistics of one weight after the gradients
    ``grads`` (one a step, from zero state)."""
    stats = None
    for g in grads:
        blocks = weight_blocks(g, block_size, max_blocks)
        if stats is None:
            stats = init([b.shape for b in blocks])
        stats = update(stats, blocks, beta2)
    return stats
