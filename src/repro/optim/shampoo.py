"""Blocked distributed Shampoo whose statistics run on the packed rank-k path.

The preconditioner statistics of every 2-D gradient block G are the
paper's operation on each side of G, kept as exponential moving averages
(Distributed Shampoo, Shi et al., arXiv:2309.06497):

    L <- beta2 L + alpha G G^t,    R <- beta2 R + alpha G^t G

with ``alpha = 1 - beta2``, or ``alpha = 1`` when ``beta2 = 1`` (a plain
sum, Distributed Shampoo's own default).  Each statistic lives as the
executor's packed lower-triangular tile stack (about n(n+1)/2 words) and
each update is ONE accumulating kernel (``gram.stream.stack_update``:
``gram_of="rows"`` for L, ``"cols"`` for R): the stack, scaled by
``beta2`` at run time, seeds the kernel's accumulator and the result is
written over it.  G is never transposed and no dense statistic exists;
:func:`update_stats` runs the two updates of every block in one jitted,
donated call.  The kernel runs the Strassen recursion to ``ata_levels``
(``"auto"``: the executor's cheapest depth by its traffic model, which
is classical blocking on today's walk), fused on TPU; ``ata_mode=
"reference"`` (the default off-TPU) runs the XLA reference recursion
into the same stacks instead.

Structure (after Anil et al.'s distributed Shampoo):
  * large dims are partitioned into blocks of <= block_size; each sub-block
    is preconditioned independently (block-diagonal Shampoo);
  * leading dims beyond the trailing 2 (layer stacks, expert stacks) are
    folded into the block list;
  * inverse-4th-roots via eigh, recomputed every ``precond_interval`` steps
    under lax.cond (kept OUTSIDE the block vmap so the skip branch really
    skips) — the only place a statistic is unpacked to dense;
  * Adam grafting: the Shampoo direction is rescaled to the Adam update's
    norm; 1-D params (biases, norm scales) fall back to plain AdamW.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..core.ata import ata_full
from ..core.strassen import resolve_mode
from ..core.symmetry import pack_tril_blocks
from ..gram import stream
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .adamw import Optimizer, clip_by_global_norm


def _plan(shape, block_size, max_blocks):
    """Static per-leaf plan: 'shampoo' (trailing 2-D preconditioned) or
    'adam'."""
    if len(shape) < 2 or shape[-1] < 2 or shape[-2] < 2:
        return None
    m, n = shape[-2], shape[-1]
    bsm, bsn = min(block_size, m), min(block_size, n)
    nbm, nbn = -(-m // bsm), -(-n // bsn)
    if nbm > max_blocks or nbn > max_blocks:
        return None
    return (nbm, bsm, nbn, bsn)


def _to_blocks(g, plan):
    """(..., M, N) -> (K, bsm, bsn) with K = prod(batch)*nbm*nbn."""
    nbm, bsm, nbn, bsn = plan
    batch = g.shape[:-2]
    m, n = g.shape[-2:]
    g = jnp.pad(g, [(0, 0)] * len(batch)
                + [(0, nbm * bsm - m), (0, nbn * bsn - n)])
    g = g.reshape(*batch, nbm, bsm, nbn, bsn)
    g = jnp.moveaxis(g, -2, -3)                       # (..., nbm, nbn, bsm, bsn)
    return g.reshape(-1, bsm, bsn)


def _from_blocks(blocks, plan, shape):
    nbm, bsm, nbn, bsn = plan
    batch = shape[:-2]
    m, n = shape[-2:]
    g = blocks.reshape(*batch, nbm, nbn, bsm, bsn)
    g = jnp.moveaxis(g, -2, -3).reshape(*batch, nbm * bsm, nbn * bsn)
    return g[..., :m, :n]


def _inv_4th_root(s, eps):
    """(bs, bs) symmetric PSD -> (s/trace_norm + eps I)^{-1/4} via eigh."""
    bs = s.shape[-1]
    # normalize for conditioning; the grafting rescale absorbs the factor
    tr = jnp.trace(s) / bs
    s = s / jnp.maximum(tr, 1e-30)
    w, u = jnp.linalg.eigh(s + eps * jnp.eye(bs, dtype=s.dtype))
    w = jnp.maximum(w, eps)
    return (u * (w ** -0.25)) @ u.T


# ---------------------------------------------------------------------------
# Statistics: packed L and R stacks, one accumulating kernel per side.
# ---------------------------------------------------------------------------

def _stat_tile(bs: int, block: Optional[int] = None) -> int:
    """Tile edge of a ``bs``-wide statistic's stack: ``block``, else the
    autotune cache's winner (256 untuned), never wider than ``bs``
    rounded up to 8."""
    if block is None:
        from ..kernels.ops import _resolve_blocks
        block = _resolve_blocks("rank_k", bs, bs, jnp.float32, bn=None)["bn"]
    return min(block, -(-bs // 8) * 8)


def init_stats(block_shapes: Sequence[Tuple[int, int]], *,
               block: Optional[int] = None) -> dict:
    """Zero statistics of gradient blocks of the given (rows, cols)
    shapes: ``{"l": [rows x rows stack per block], "r": [cols x cols]}``,
    each a :class:`~repro.gram.stream.GramStackStream` in fp32.

    The stacks are committed to the default device, as a step's outputs
    are, so the first :func:`update_stats` and every later one share one
    executable."""
    dev = jax.devices()[0]

    def zeros(n):
        return jax.device_put(stream.stack_init(n, block=_stat_tile(n, block)),
                              dev)
    return {"l": [zeros(r) for r, _ in block_shapes],
            "r": [zeros(c) for _, c in block_shapes]}


def _fold(state, g, gram_of, beta, alpha, *, levels, leaf, variant, mode,
          block, interpret):
    """``state <- beta state + alpha tril(Gram)`` of one gradient block."""
    if resolve_mode(mode) == "fused":
        return stream.stack_update(state, g, levels=levels,
                                   variant=variant, block=block,
                                   gram_of=gram_of, beta=beta, alpha=alpha,
                                   interpret=interpret)
    # the XLA reference recursion, packed into the same stack layout
    dense = ata_full(g, gram_of=gram_of, levels=levels, leaf=leaf,
                     variant=variant, mode="reference",
                     out_dtype=state.stack.dtype)
    pad = state.n_padded - dense.shape[0]
    delta = pack_tril_blocks(jnp.pad(dense, ((0, pad), (0, pad))),
                             state.block)
    k = g.shape[1] if gram_of == "rows" else g.shape[0]
    return stream.GramStackStream(stack=beta * state.stack + alpha * delta,
                                  rows=state.rows + k)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=(
    "levels", "leaf", "variant", "mode", "block", "interpret"))
def _stats_step(stats, grad_blocks, beta, alpha, *, levels, leaf, variant,
                mode, block, interpret):
    """Every block's L and R update, one after another, in one program;
    ``beta`` and ``alpha`` are traced float32 scalars, so one executable
    serves every ``beta2``."""
    kw = dict(levels=levels, leaf=leaf, variant=variant, mode=mode,
              block=block, interpret=interpret)
    return {"l": [_fold(st, g, "rows", beta, alpha, **kw)
                  for st, g in zip(stats["l"], grad_blocks)],
            "r": [_fold(st, g, "cols", beta, alpha, **kw)
                  for st, g in zip(stats["r"], grad_blocks)]}


def update_stats(stats: dict, grad_blocks, beta2: float = 1.0, *,
                 levels: Union[int, str] = "auto", leaf: int = 256,
                 variant: str = "strassen", mode: str = "auto",
                 block: Optional[int] = None,
                 interpret: Optional[bool] = None) -> dict:
    """One statistics step of Distributed Shampoo on packed stacks.

    ``stats`` is :func:`init_stats`' structure (donated: every stack is
    updated in place); ``grad_blocks`` the (rows, cols) gradient blocks,
    in the same order.  Each block's ``L <- beta2 L + alpha G G^t`` and
    ``R <- beta2 R + alpha G^t G`` run as one accumulating kernel each
    (``alpha = 1 - beta2``, or 1 when ``beta2 = 1``), all in one jitted
    call.  ``block`` is the kernels' contraction tile; ``mode`` as in
    ``core.ata`` ("auto": the kernel on TPU, the XLA reference recursion
    elsewhere; "fused" forces the kernel).  Outside a trace the
    step is a live ``shampoo_stats`` span and counts its Grams in
    ``shampoo_stat_grams_total{side}``.
    """
    grad_blocks = list(grad_blocks)
    if len(grad_blocks) != len(stats["l"]):
        raise ValueError(f"{len(grad_blocks)} gradient blocks for "
                         f"{len(stats['l'])} statistics")
    alpha = 1.0 if beta2 == 1.0 else 1.0 - beta2
    step = functools.partial(_stats_step, levels=levels, leaf=leaf,
                             variant=variant, mode=mode, block=block,
                             interpret=interpret)
    beta, alpha = jnp.float32(beta2), jnp.float32(alpha)
    if any(isinstance(x, jax.core.Tracer) for x in
           jax.tree.leaves((stats, grad_blocks))):
        return step(stats, grad_blocks, beta, alpha)
    k = len(grad_blocks)
    with _trace.span("shampoo_stats", blocks=k, sides="l,r"):
        out = step(stats, grad_blocks, beta, alpha)
    grams = _metrics.counter("shampoo_stat_grams_total",
                             "Shampoo statistic Gram updates dispatched")
    grams.inc(k, side="l")
    grams.inc(k, side="r")
    return out


def dense_stats(stats: dict, block_shapes) -> dict:
    """The statistics as dense symmetric fp32 matrices (for the inverse
    roots, and for checking): ``{"l": [(rows, rows)], "r": [...]}``."""
    return {"l": [stream.stack_finalize(st, r)
                  for st, (r, _) in zip(stats["l"], block_shapes)],
            "r": [stream.stack_finalize(st, c)
                  for st, (_, c) in zip(stats["r"], block_shapes)]}


# ---------------------------------------------------------------------------
# The optimizer
# ---------------------------------------------------------------------------

def shampoo(lr, *, block_size: int = 1024, stat_interval: int = 1,
            precond_interval: int = 20, beta2_stat: float = 1.0,
            b1=0.9, b2=0.95, eps=1e-8, matrix_eps=1e-6,
            weight_decay=0.1, grad_clip: Optional[float] = 1.0,
            ata_levels: Union[int, str] = "auto", ata_leaf: int = 128,
            max_blocks: int = 64,
            ata_variant: str = "strassen",
            ata_mode: str = "auto",
            ata_block: Optional[int] = None) -> Optimizer:
    """ATA-powered blocked Shampoo with Adam grafting.

    ``beta2_stat`` is the statistics' EMA factor (1.0, the default, sums
    them).  ``ata_mode`` ("auto" | "fused" | "reference") picks the
    statistics' engine — "auto" runs the accumulating kernel on TPU and
    the XLA reference recursion elsewhere; ``ata_block`` is the
    statistics' tile edge (``None`` consults the gram autotune cache).
    """
    lr_fn = lr if callable(lr) else (lambda _: lr)
    stat_kw = dict(levels=ata_levels, leaf=ata_leaf, variant=ata_variant,
                   mode=ata_mode, block=ata_block)

    def _shapes(p, plan):
        nbm, bsm, nbn, bsn = plan
        k = math.prod(p.shape[:-2] or (1,)) * nbm * nbn
        return [(bsm, bsn)] * k

    def init(params):
        f32 = lambda p: jnp.zeros(p.shape, jnp.float32)

        def stats(p):
            plan = _plan(p.shape, block_size, max_blocks)
            if plan is None:
                return {"l": [], "r": [], "pl": jnp.zeros((0,)),
                        "pr": jnp.zeros((0,))}
            shapes = _shapes(p, plan)
            k, (bsm, bsn) = len(shapes), shapes[0]
            eye = lambda bs: jnp.broadcast_to(jnp.eye(bs, dtype=jnp.float32),
                                              (k, bs, bs))
            return {**init_stats(shapes, block=ata_block),
                    "pl": eye(bsm), "pr": eye(bsn)}

        return {"m": jax.tree.map(f32, params),
                "v": jax.tree.map(f32, params),
                "gram": jax.tree.map(stats, params)}

    def update(grads, state, params, step):
        if grad_clip:
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
        else:
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            from .adamw import global_norm
            gnorm = global_norm(grads)
        t = step + 1
        bc1 = 1.0 - b1 ** t.astype(jnp.float32)
        bc2 = 1.0 - b2 ** t.astype(jnp.float32)
        lr_t = lr_fn(step)
        do_stat = (step % stat_interval) == 0
        do_precond = (step % precond_interval) == 0

        new_m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                             state["m"], grads)
        new_v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                             state["v"], grads)

        def leaf(p, g, m, v, gr):
            # Adam (grafting reference and 1-D fallback)
            mh, vh = m / bc1, v / bc2
            u_adam = mh / (jnp.sqrt(vh) + eps)
            plan = _plan(p.shape, block_size, max_blocks)
            if plan is None:
                u = u_adam
                new_gr = gr
            else:
                blk = _to_blocks(g, plan)              # (K, bsm, bsn)
                shapes = _shapes(p, plan)
                stats = {"l": gr["l"], "r": gr["r"]}
                stats = jax.lax.cond(
                    do_stat,
                    lambda st: update_stats(st, list(blk), beta2_stat,
                                            **stat_kw),
                    lambda st: st, stats)

                def recompute(_):
                    dense = dense_stats(stats, shapes)
                    root = jax.vmap(lambda s: _inv_4th_root(s, matrix_eps))
                    return (root(jnp.stack(dense["l"])),
                            root(jnp.stack(dense["r"])))

                pl, pr = jax.lax.cond(do_precond, recompute,
                                      lambda _: (gr["pl"], gr["pr"]), None)
                # precondition blocks of the *momentum* (common practice)
                mblk = _to_blocks(mh, plan)
                ublk = jnp.einsum("kab,kbc,kcd->kad", pl, mblk, pr)
                u_sh = _from_blocks(ublk, plan, p.shape)
                # Adam grafting: Shampoo direction at Adam magnitude
                ratio = (jnp.linalg.norm(u_adam)
                         / jnp.maximum(jnp.linalg.norm(u_sh), 1e-16))
                u = u_sh * ratio
                new_gr = {**stats, "pl": pl, "pr": pr}
            u = u + weight_decay * p.astype(jnp.float32)
            return -lr_t * u, new_gr

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(new_m)
        flat_v = treedef.flatten_up_to(new_v)
        flat_gr = treedef.flatten_up_to(state["gram"])
        outs = [leaf(*args) for args in zip(flat_p, flat_g, flat_m,
                                            flat_v, flat_gr)]
        updates = treedef.unflatten([o[0] for o in outs])
        new_gram = treedef.unflatten([o[1] for o in outs])
        new_state = {"m": new_m, "v": new_v, "gram": new_gram}
        return updates, new_state, {"grad_norm": gnorm}

    return Optimizer(init, update)
