"""Online (streaming) Gram accumulation: C += A_chunk^t A_chunk.

The paper frames A^tA as "an intermediate operation during the solution
of a wide set of problems"; in most of those problems A arrives in row
chunks (minibatches, shards, token streams).  This module keeps the
running Gram in **packed lower-triangular form** — n(n+1)/2 words, the
paper's storage saving (`core/symmetry.py`) — and folds each chunk in
through the ATA recursion (fused Pallas kernel on TPU via
``mode="auto"``), with the state buffer **donated** so the accumulator is
updated in place rather than reallocated per chunk.

Exactness over ragged chunks: ``C = sum_i A_i^t A_i`` for any row
partition of A (the C11/C22 two-addend identity of Algorithm 1
generalized to any number of addends), so any chunking — including a
ragged final chunk — reproduces the one-shot ``ata_full(A)`` up to fp32
accumulation-order rounding.  ``tests/test_gram_stream.py`` and the
hypothesis property in ``tests/test_properties.py`` pin this down.

Fused updates are end-to-end *packed* — the kernel's tri-block stack
feeds the element-packed state through one static gather, so neither the
forward delta nor (via the gather's scatter-add VJP composed with the
packed kernel's packed-cotangent VJP) the backward ever materializes a
dense (n, n) buffer (DESIGN.md §11); ``tests/test_fused_grads.py``
checks streamed-update gradients against the reference recursion.

Sharded variant: ``update_sharded`` composes with
``core.distributed.gram_reducescatter`` — each device streams its *row
shard* of the chunk and holds only its block-row shard of C, so the
replicated C of the paper-faithful all-reduce scheme never materializes.

Distributed variant: ``distributed_init`` / ``distributed_update`` /
``distributed_finalize`` are the pjit-level composition with ANY
``core.distributed`` scheme — including the half-ring and the
communication-avoiding 2.5D ``bfs25d``, whose circulant block-stack
state (n(n+1)/2-ish words, sharded over the ring axis) accumulates
per-chunk deltas without ever materializing a replicated C.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.ata import ata, resolve_levels
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..core.distributed import (assemble_ring_gram, gram_bfs25d,
                                gram_reducescatter, gram_ring,
                                ring_stack_len)
from ..core.strassen import resolve_mode
from ..core.symmetry import pack_tril, tril_vector_from_blocks, unpack_tril

__all__ = ["GramStream", "init", "update", "finalize",
           "GramStackStream", "stack_init", "stack_update", "stack_finalize",
           "sharded_init", "update_sharded",
           "distributed_init", "distributed_update", "distributed_finalize",
           "CheckpointedGramStream"]


class GramStream(NamedTuple):
    """Running Gram state (a pytree — jit/scan/donate friendly).

    packed: (n(n+1)/2,) packed lower triangle of the accumulated C.
    rows:   scalar int32, total rows streamed so far (for normalized
            second-moment readings: C / rows).
    """
    packed: jax.Array
    rows: jax.Array

    @property
    def n(self) -> int:
        # n(n+1)/2 = L  =>  n = (sqrt(8L+1) - 1) / 2
        return (math.isqrt(8 * self.packed.shape[0] + 1) - 1) // 2


def init(n: int, *, dtype=jnp.float32) -> GramStream:
    """Fresh accumulator for an n-column stream (fp32 by default: the
    accumulation dtype must not lose bits across many chunks)."""
    return GramStream(packed=jnp.zeros(n * (n + 1) // 2, dtype),
                      rows=jnp.zeros((), jnp.int32))


@functools.lru_cache(maxsize=None)
def _updater(levels, leaf, variant, mode, block, interpret):
    resolved = resolve_mode(mode)

    def step(packed, rows, chunk):
        if resolved == "fused":
            # End-to-end packed: the fused kernel's tri-block stack feeds
            # the element-packed state through one static gather — the
            # dense (n, n) delta never materializes, and because the
            # gather's VJP is a scatter back into the stack (consumed by
            # the packed kernel's own packed-cotangent VJP), jax.grad of
            # a streamed update stays dense-free too (DESIGN.md §11).
            from ..kernels.ops import ata_fused_packed
            lv = resolve_levels(levels, chunk.shape, chunk.dtype,
                                mode="fused", variant=variant, block=block,
                                out_dtype=packed.dtype)
            stack = ata_fused_packed(chunk, levels=lv, variant=variant,
                                     bk=block, bn=block,
                                     out_dtype=packed.dtype,
                                     interpret=interpret)
            delta = tril_vector_from_blocks(stack, stack.shape[1],
                                            chunk.shape[1])
        else:
            delta = pack_tril(ata(chunk, levels=levels, leaf=leaf,
                                  variant=variant, mode=mode,
                                  out_dtype=packed.dtype, block=block,
                                  interpret=interpret))
        return packed + delta, rows + chunk.shape[0]
    # donate the packed accumulator: the update runs in place, no second
    # n(n+1)/2 buffer per chunk
    return jax.jit(step, donate_argnums=(0,))


def update(state: GramStream, chunk: jax.Array, *,
           levels: Union[int, str] = 2, leaf: int = 256,
           variant: str = "strassen", mode: str = "auto",
           block: Optional[int] = None,
           interpret: Optional[bool] = None) -> GramStream:
    """Fold one row chunk in: state.packed += pack_tril(tril(chunk^t chunk)).

    ``chunk`` is (m_chunk, n) with any m_chunk >= 1 (ragged tails fine).
    Kernel knobs mirror ``core.ata``; ``block=None`` consults the
    autotune cache (``gram.autotune``).
    """
    if chunk.ndim != 2 or state.n != chunk.shape[1]:
        raise ValueError(
            f"chunk shape {chunk.shape} does not match stream n={state.n}")
    fn = _updater(levels, leaf, variant, mode, block, interpret)
    packed, rows = fn(state.packed, state.rows, chunk)
    return GramStream(packed=packed, rows=rows)


def finalize(state: GramStream, *, symmetrize: bool = True,
             out_dtype=None, guard: bool = False) -> jax.Array:
    """Dense (n, n) Gram from the packed state (mirrored when
    ``symmetrize``, else lower-triangular like ``ata``).

    ``guard=True`` runs the streaming output guards first
    (``gram.verify.check_packed_state``: NaN/Inf scan + diagonal
    nonnegativity on the packed state — the chunks are gone, so no
    Freivalds probe) and raises :class:`~.verify.VerificationError`
    instead of handing corrupted state downstream.
    """
    if guard:
        import numpy as np
        from .verify import check_packed_state
        check_packed_state(np.asarray(jax.device_get(state.packed)), state.n)
    c = unpack_tril(state.packed, state.n, symmetrize=symmetrize)
    return c.astype(out_dtype) if out_dtype is not None else c


# ---------------------------------------------------------------------------
# Rank-k streaming: the state IS the kernel's packed tile stack, and each
# chunk folds in through the accumulating (rank_k) leaf program — the
# kernel seeds its VMEM accumulator from the (optionally beta-scaled)
# stack and writes over it, so no per-chunk delta stack, no unpack and no
# gather ever materializes (the element-packed ``update`` above computes a
# full n(n+1)/2 delta per chunk and adds it; this path replaces that with
# ONE kernel per chunk, on either side of the chunk: ``gram_of``).
# ---------------------------------------------------------------------------

class GramStackStream(NamedTuple):
    """Running Gram state in the executor's packed tile-stack layout.

    stack: (T(T+1)/2 * block, block) lower-triangular tile stack of the
           accumulated C (``kernels.syrk`` / ``fused_ata_packed``
           ordering; diagonal tiles full).
    rows:  scalar int32, total rows streamed so far.
    """
    stack: jax.Array
    rows: jax.Array

    @property
    def block(self) -> int:
        return self.stack.shape[1]

    @property
    def n_padded(self) -> int:
        n_tri = self.stack.shape[0] // self.block
        t = (math.isqrt(8 * n_tri + 1) - 1) // 2
        return t * self.block


def stack_init(n: int, *, block: Optional[int] = None,
               dtype=jnp.float32) -> GramStackStream:
    """Fresh rank-k accumulator for an n-column stream.

    ``block`` is the stack's tile edge (``None`` consults the autotune
    cache for the (n, n) bucket, 256 when untuned); the stack spans
    ``ceil(n / block)`` tiles — padded columns are exact zeros.
    """
    if block is None:
        from ..kernels.ops import _resolve_blocks
        block = _resolve_blocks("rank_k", n, n, dtype, bn=None)["bn"]
    t = -(-n // block)
    return GramStackStream(
        stack=jnp.zeros((t * (t + 1) // 2 * block, block), dtype),
        rows=jnp.zeros((), jnp.int32))


def stack_update(state: GramStackStream, chunk: jax.Array, *,
                 levels: Union[int, str] = 2,
                 variant: str = "strassen", block: Optional[int] = None,
                 gram_of: str = "cols", beta=1.0, alpha=1.0,
                 interpret: Optional[bool] = None) -> GramStackStream:
    """Fold one chunk in: ``state.stack <- beta * state.stack + alpha *
    packed(tril(chunk^t chunk))`` — one accumulating kernel, state
    donated, no intermediate delta.  The defaults give ``+=``.

    ``gram_of="cols"`` (default): ``chunk`` is (m_chunk, n) with n <= the
    stack's padded span, its rows streamed in.  ``gram_of="rows"``: the
    Gram is ``chunk chunk^t``, ``chunk`` is (n, k_chunk) with n <= the
    span, its columns streamed in (Shampoo's left statistic of a
    gradient block, with no transposed copy of the block).  ``beta`` and
    ``alpha`` are run-time scalars: an exponential moving average takes
    ``beta = b, alpha = 1 - b`` from one executable for every ``b``.
    ``rows`` counts the vectors folded in, unscaled.

    ``block`` is the *contraction* tile (the output tile edge is fixed
    by the stack).  ``levels`` clamps to depths the stack layout
    divides, like the symm executor; ``"auto"`` is the executor's
    cheapest depth by its traffic model (``strassen_fused.auto_levels``).
    """
    if gram_of not in ("cols", "rows"):
        raise ValueError(f"gram_of must be 'cols' or 'rows', got {gram_of!r}")
    side = chunk.shape[0] if gram_of == "rows" else chunk.shape[-1]
    if chunk.ndim != 2 or side > state.n_padded:
        raise ValueError(
            f"chunk shape {chunk.shape} does not fit stream "
            f"n_padded={state.n_padded} ({gram_of} side)")
    from ..kernels.ops import _resolve_blocks, rank_k_update
    m, n = chunk.shape
    if levels == "auto":
        from ..kernels.strassen_fused import auto_levels
        block = _resolve_blocks("rank_k", m, n, chunk.dtype, bk=block)["bk"]
        levels = auto_levels("rank_k", n if gram_of == "rows" else m,
                             state.n_padded, gram_of=gram_of,
                             variant=variant, bk=block, bn=state.block,
                             operand_dtype=chunk.dtype,
                             out_dtype=state.stack.dtype)
    stack = rank_k_update(state.stack, chunk, levels=levels, variant=variant,
                          gram_of=gram_of, beta=beta, alpha=alpha,
                          bk=block, interpret=interpret)
    return GramStackStream(stack=stack,
                           rows=state.rows + (n if gram_of == "rows" else m))


def stack_finalize(state: GramStackStream, n: Optional[int] = None, *,
                   symmetrize: bool = True, out_dtype=None,
                   guard: bool = False) -> jax.Array:
    """Dense (n, n) Gram from the stacked state (mirrored when
    ``symmetrize``, else lower-triangular like ``ata``).

    ``guard=True`` scans the tile stack for NaN/Inf before unpacking and
    raises :class:`~.verify.VerificationError` on corruption (the
    diagonal check happens on the unpacked dense form below — tile-stack
    indexing of the diagonal is block-size dependent)."""
    import numpy as np
    from ..core.symmetry import unpack_tril_blocks
    if guard:
        from .verify import VerificationError
        if not np.isfinite(np.asarray(jax.device_get(state.stack))).all():
            raise VerificationError(
                "streamed Gram tile stack contains non-finite entries")
    n_pad = state.n_padded
    c = unpack_tril_blocks(state.stack, n_pad, state.block,
                           symmetrize=False)
    c = jnp.tril(c)
    if guard:
        from .verify import VerificationError
        d = np.asarray(jax.device_get(jnp.diagonal(c))).astype(np.float64)
        scale = float(np.abs(d).max()) if d.size else 0.0
        if not (d >= -1e-4 * max(scale, 1.0)).all():
            raise VerificationError(
                "streamed Gram state has a negative diagonal entry")
    if symmetrize:
        from ..core.symmetry import symmetrize_from_lower
        c = symmetrize_from_lower(c)
    if n is not None:
        c = c[:n, :n]
    return c.astype(out_dtype) if out_dtype is not None else c


# ---------------------------------------------------------------------------
# Sharded streaming (inside shard_map): C lives sharded by block-rows.
# ---------------------------------------------------------------------------

def sharded_init(n: int, axis_size: int, *, dtype=jnp.float32) -> jax.Array:
    """Per-device state for ``update_sharded``: this device's (n/P, n)
    block-row shard of C (call inside shard_map, or build the global
    (n, n) array with a ``P(row_axis, None)`` sharding outside)."""
    if n % axis_size:
        raise ValueError(f"n={n} not divisible by axis_size={axis_size}")
    return jnp.zeros((n // axis_size, n), dtype)


def update_sharded(c_shard: jax.Array, chunk_local: jax.Array,
                   row_axis: str, *, levels: Union[int, str] = 2,
                   leaf: int = 256, variant: str = "strassen",
                   mode: str = "auto") -> jax.Array:
    """One streamed chunk under shard_map: rows of the chunk sharded over
    ``row_axis``, C sharded by block-rows over the same axis.

    Per chunk each device computes the Gram of its row shard (fused
    pipeline via ``mode="auto"``) and a single ``psum_scatter``
    (``gram_reducescatter``) lands each device's block-row slice — the
    full C is never replicated, and per-chunk collective bandwidth is
    n^2/P words per device instead of n^2.
    """
    delta = gram_reducescatter(chunk_local, row_axis, levels=levels,
                               leaf=leaf, variant=variant, mode=mode,
                               out_dtype=c_shard.dtype)
    return c_shard + delta


# ---------------------------------------------------------------------------
# pjit-level distributed streaming: state sharded by the scheme's natural
# output layout, chunks sharded like the scheme's input.
# ---------------------------------------------------------------------------

def _state_spec(scheme: str, row_axis: str, col_axis: Optional[str]):
    if scheme == "reducescatter":
        return P(row_axis, None)
    if scheme in ("ring", "bfs25d"):
        return P(None, None, col_axis)
    raise ValueError(f"unsupported streaming scheme {scheme!r}")


def distributed_init(n: int, mesh: Mesh, *, scheme: str = "reducescatter",
                     row_axis: str = "data",
                     col_axis: Optional[str] = "model",
                     dtype=jnp.float32) -> jax.Array:
    """Zero accumulator for ``distributed_update`` on ``mesh``.

    * ``"reducescatter"`` — dense (n, n) C sharded by block-rows over
      ``row_axis`` (never replicated).
    * ``"ring"`` / ``"bfs25d"`` — the half-ring circulant block stack
      (floor(T/2)+1, n/T, n) sharded over ``col_axis``: ~n(n+1)/2 words
      of global state, the packed-triangle saving at mesh scale.
    """
    spec = _state_spec(scheme, row_axis, col_axis)
    if scheme == "reducescatter":
        shape = (n, n)
    else:
        T = mesh.shape[col_axis]
        if n % T:
            raise ValueError(f"n={n} not divisible by ring size {T}")
        shape = (ring_stack_len(T), n // T, n)
    return jax.device_put(jnp.zeros(shape, dtype),
                          NamedSharding(mesh, spec))


def distributed_update(state: jax.Array, chunk: jax.Array, mesh: Mesh, *,
                       scheme: str = "reducescatter",
                       row_axis: str = "data",
                       col_axis: Optional[str] = "model",
                       rep_axis: Optional[str] = None,
                       levels: Union[int, str] = 2, leaf: int = 256,
                       variant: str = "strassen",
                       mode: str = "auto") -> jax.Array:
    """Fold one globally-sharded row chunk into the distributed state:
    ``state += scheme(chunk)``.  Chunk rows must divide by the row axis;
    for the ring family the chunk is also column-sharded (and, for
    ``bfs25d``, replicated over ``rep_axis`` — the 2.5D trade applies
    per chunk, so each update ships only ceil(half/c) permute hops)."""
    spec = _state_spec(scheme, row_axis, col_axis)

    if scheme == "reducescatter":
        def body(c_shard, chunk_local):
            return update_sharded(c_shard, chunk_local, row_axis,
                                  levels=levels, leaf=leaf, variant=variant,
                                  mode=mode)
        chunk_spec = P(row_axis, None)
    else:
        T = mesh.shape[col_axis]

        def body(stack, chunk_local):
            if scheme == "ring":
                delta = gram_ring(chunk_local, col_axis, row_axis,
                                  levels=levels, leaf=leaf, variant=variant,
                                  mode=mode, out_dtype=stack.dtype)
            else:
                if rep_axis is None:
                    raise ValueError("bfs25d streaming needs rep_axis")
                delta = gram_bfs25d(chunk_local, col_axis, rep_axis,
                                    row_axis, levels=levels, leaf=leaf,
                                    variant=variant, mode=mode,
                                    out_dtype=stack.dtype, col_size=T,
                                    rep_size=mesh.shape[rep_axis])
            return stack + delta
        chunk_spec = P(row_axis, col_axis)

    return jax.shard_map(body, mesh=mesh, in_specs=(spec, chunk_spec),
                         out_specs=spec, check_vma=False)(state, chunk)


def distributed_finalize(state: jax.Array, mesh: Mesh, *,
                         scheme: str = "reducescatter",
                         col_axis: Optional[str] = "model") -> jax.Array:
    """Dense symmetric (n, n) C from the distributed state (the
    reduce-scatter state already IS dense; ring-family states are
    assembled from the circulant block layout)."""
    if scheme == "reducescatter":
        return state
    T = mesh.shape[col_axis]
    return assemble_ring_gram(state, T, state.shape[2])


# ---------------------------------------------------------------------------
# Crash-recoverable streaming: write-ahead checkpoints of the accumulator.
# ---------------------------------------------------------------------------

class CheckpointedGramStream:
    """A streaming Gram whose state survives the process (DESIGN.md §13).

    Wraps :class:`GramStream` (``layout="packed"``) or
    :class:`GramStackStream` (``layout="stack"``) and commits the
    accumulator to a :class:`~repro.checkpoint.CheckpointManager`
    directory every ``every`` chunks — atomic rename commits, so a kill
    at ANY point leaves either the previous or the new checkpoint
    intact, never a torn one.  The commit step number is the count of
    chunks *fully folded in* (write-ahead in the sense that the state on
    disk is always a prefix of the stream: resume never replays a chunk
    into state that already contains it, and never skips one — the
    resumer re-feeds chunks from ``next_chunk`` on).

    Because chunked accumulation is exact over any row partition (module
    docstring) and the resumed state is the *bit-identical* buffer the
    crashed process committed, a resumed run's finalize is bit-exact
    against the uninterrupted run as long as chunks are re-fed at the
    same boundaries (fp addition is order-sensitive; the checkpoint
    preserves the order).

    ::

        s = CheckpointedGramStream(n, workdir, every=4)
        for i, chunk in enumerate(chunks):
            if i < s.next_chunk:      # already folded in pre-crash
                continue
            s.update(chunk)
        c = s.finalize(guard=True)
    """

    def __init__(self, n: int, workdir: str, *, every: int = 1,
                 layout: str = "packed", block: Optional[int] = None,
                 dtype=jnp.float32, keep: int = 2,
                 async_save: bool = False, **update_kw):
        if layout not in ("packed", "stack"):
            raise ValueError(f"layout must be 'packed' or 'stack', "
                             f"got {layout!r}")
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        from ..checkpoint import CheckpointManager
        self.n = n
        self.layout = layout
        self.every = every
        self.update_kw = update_kw
        # sync by default: a streaming WAL wants the commit durable when
        # .commit() returns (the trainer's overlap-with-compute motive
        # doesn't apply to host-side accumulator snapshots)
        self.manager = CheckpointManager(workdir, keep=keep,
                                         async_save=async_save)
        self.chunks = 0            # chunks fully folded into .state
        self._dirty = 0            # chunks since the last commit
        self.resumed = False
        if layout == "packed":
            self.state = init(n, dtype=dtype)
        else:
            self.state = stack_init(n, block=block, dtype=dtype)
        with _trace.span("stream_restore", layout=layout):
            restored, meta = self.manager.restore()
        if restored is not None:
            if int(meta.get("n", n)) != n or meta.get("layout") != layout:
                raise ValueError(
                    f"checkpoint in {workdir} holds a "
                    f"{meta.get('layout')} stream of n={meta.get('n')}, "
                    f"not the requested {layout} n={n}")
            if layout == "packed":
                self.state = GramStream(
                    packed=jnp.asarray(restored["packed"]),
                    rows=jnp.asarray(restored["rows"]))
            else:
                self.state = GramStackStream(
                    stack=jnp.asarray(restored["stack"]),
                    rows=jnp.asarray(restored["rows"]))
            self.chunks = int(meta["chunks"])
            self.resumed = True

    @property
    def next_chunk(self) -> int:
        """Index of the first chunk NOT yet folded in (resume cursor)."""
        return self.chunks

    def update(self, chunk) -> None:
        """Fold one chunk in; commits every ``every`` chunks."""
        if self.layout == "packed":
            self.state = update(self.state, chunk, **self.update_kw)
        else:
            self.state = stack_update(self.state, chunk, **self.update_kw)
        self.chunks += 1
        self._dirty += 1
        if self._dirty >= self.every:
            self.commit()

    def commit(self) -> None:
        """Force a checkpoint of the current state (no-op when clean)."""
        if self._dirty == 0 and self.manager.latest_step() == self.chunks:
            return
        if self.layout == "packed":
            tree = {"packed": self.state.packed, "rows": self.state.rows}
        else:
            tree = {"stack": self.state.stack, "rows": self.state.rows}
        with _trace.span("stream_commit", chunks=self.chunks,
                         dirty=self._dirty, layout=self.layout):
            self.manager.save(self.chunks, tree,
                              extra={"chunks": self.chunks, "n": self.n,
                                     "layout": self.layout})
        _metrics.counter("gram_stream_commits_total",
                         "checkpoint commits of streamed Gram state").inc(
            layout=self.layout)
        self._dirty = 0

    def finalize(self, *, symmetrize: bool = True, out_dtype=None,
                 guard: bool = False) -> jax.Array:
        """Commit any uncheckpointed chunks, then the dense Gram (with
        the output guards when ``guard`` — see ``finalize``)."""
        self.commit()
        self.manager.wait()
        if self.layout == "packed":
            return finalize(self.state, symmetrize=symmetrize,
                            out_dtype=out_dtype, guard=guard)
        return stack_finalize(self.state, self.n, symmetrize=symmetrize,
                              out_dtype=out_dtype, guard=guard)
