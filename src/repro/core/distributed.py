"""Distributed ATA — the paper's ATA-P mapped onto a JAX SPMD mesh.

Paper (§4): a dynamic MPI process tree — each complete parallel level of
ATA-P fans out to 6 processes (4x ATA + 2x HASA), communicators perform
3 simultaneous MPI reductions (the two addends of C11, C22, C21), then
point-to-point sends patch C together on the subtree root.

TPU adaptation (DESIGN.md §2): TPU pods are SPMD machines — the process tree
becomes a mesh decomposition and the reductions become axis collectives:

* ``gram_allreduce`` — paper-faithful scheme. A is sharded by *rows* over
  ``row_axis`` (the recursion over m: C = sum_r A_r^t A_r — exactly the
  C11/C22 two-addend reduction generalized to P addends). Each device runs
  the sequential ATA recursion on its shard; one ``psum`` realizes the
  paper's reduction tree. Latency: one collective — matching the paper's
  claim of minimal message count; bandwidth: n^2 words (the paper's
  BW = (n/2)^2 per message, and like the paper it is independent of P).

* ``gram_reducescatter`` — beyond-paper: same compute, but the reduction
  emits C sharded by block-rows (``psum_scatter``), cutting the per-device
  bandwidth term by P and never materializing C replicated.

* ``gram_ring`` — beyond-paper: A sharded by rows *and* columns
  (``row_axis`` x ``col_axis``). Diagonal blocks use ATA (half work);
  off-diagonal blocks use Strassen — the exact ATA/HASA division of labor
  of the paper — scheduled as a **half-ring**: because C is symmetric, only
  floor(T/2)+1 ring steps are needed (vs T for a generic A^tB collective
  matmul). Each step's ``ppermute`` overlaps with the previous step's block
  product (collective-matmul pattern), turning the paper's blocking
  Send/Recv into bandwidth-optimal, compute-overlapped ICI traffic.

* ``gram_bfs25d`` — communication-avoiding 2.5D variant (Ballard et al.,
  arXiv:1202.3173; Benson & Ballard, arXiv:1409.2908): a third mesh axis
  ``rep_axis`` of size c replicates A (the 2.5D memory-for-communication
  trade), and the half-ring's independent Strassen/HASA block tasks are
  dealt out BFS-style (CAPS's breadth-first step) across the c replication
  groups — group r takes ring steps ``s ≡ r+1 (mod c)``.  Each group skews
  its A copy once (one ``ppermute`` jump over (rep, col)) and then hops by
  c, so the ring-permute rounds on the critical path drop from floor(T/2)
  to ceil(floor(T/2)/c) while each task still falls into the same fused
  local kernel (ATA diagonal, Strassen off-diagonal).  A final ``psum``
  over (rep, row) — small payload: the packed block stack, not A — merges
  the groups' disjoint block stacks into the half-ring layout of
  ``gram_ring``.

All four run inside ``shard_map``; ``distributed_gram`` is the pjit-level
wrapper over a globally-sharded A, and ``scheme="auto"`` picks the scheme
by the communication cost model in ``core.cost_model``
(``rank_gram_schemes``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ata import ata, ata_full
from .strassen import strassen_matmul
from .symmetry import symmetrize_from_lower

__all__ = [
    "gram_allreduce", "gram_reducescatter", "gram_ring", "gram_bfs25d",
    "distributed_gram", "ring_layout_coords", "assemble_ring_gram",
    "ring_stack_len", "feasible_schemes", "default_gram_axes",
    "scheme_fallback_chain", "shrink_mesh", "SCHEME_LADDER",
]

# Degradation order for the serving layer's scheme fallback: most
# communication-avoiding (and most moving parts) first, the
# paper-faithful single-psum scheme last — each step rightward trades
# bandwidth optimality for fewer ways to fail (fewer collectives, fewer
# axes involved).
SCHEME_LADDER = ("bfs25d", "ring", "reducescatter", "allreduce")


# ---------------------------------------------------------------------------
# shard_map bodies (take *local* shards, use collectives explicitly)
# ---------------------------------------------------------------------------

def gram_allreduce(a_local: jax.Array, row_axis: str, *,
                   levels="auto", leaf: int = 256,
                   variant: str = "strassen", mode: str = "auto",
                   out_dtype=None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Paper-faithful: local ATA + one all-reduce over the row axis.

    Per-shard compute goes through the fused leaf-task pipeline on TPU
    (mode="auto"; see ata.py) — the collective schedule is unchanged.
    ``out_dtype`` defaults to the *input* dtype here (unlike plain
    ``ata``): accumulation is still fp32 inside the kernel, but the
    reduction moves C over the wire, and shipping bf16 cells as fp32
    would silently double the paper's bandwidth term.  Pass
    ``out_dtype=jnp.float32`` to reduce in full precision.
    Returns the full symmetric C, replicated over ``row_axis``.
    """
    c_local = ata_full(a_local, levels=levels, leaf=leaf, variant=variant,
                       mode=mode, interpret=interpret,
                       out_dtype=out_dtype or a_local.dtype)
    return jax.lax.psum(c_local, row_axis)


def gram_reducescatter(a_local: jax.Array, row_axis: str, *,
                       levels="auto", leaf: int = 256,
                       variant: str = "strassen", mode: str = "auto",
                       out_dtype=None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Beyond-paper: local ATA + reduce-scatter (C sharded by rows over
    ``row_axis``); bandwidth term / P, no replicated C."""
    c_local = ata_full(a_local, levels=levels, leaf=leaf, variant=variant,
                       mode=mode, interpret=interpret,
                       out_dtype=out_dtype or a_local.dtype)
    return jax.lax.psum_scatter(c_local, row_axis, scatter_dimension=0,
                                tiled=True)


def gram_ring(a_local: jax.Array, col_axis: str,
              row_axis: Optional[str] = None, *,
              levels="auto", leaf: int = 256,
              variant: str = "strassen", mode: str = "auto",
              out_dtype=None,
              interpret: Optional[bool] = None) -> jax.Array:
    """Half-ring symmetric collective gram (beyond-paper TPU schedule).

    Device layout: ``a_local`` is the (rows/R, cols/T) shard of A.
    Step 0 computes the diagonal block with ATA (the paper's symmetric
    recursion, half work); step s rotates column blocks by one hop around
    ``col_axis`` and computes one off-diagonal block with Strassen (the
    paper's HASA role). Symmetry halves the ring: floor(T/2) hops.

    Returns a stack of local blocks, shape (floor(T/2)+1, n_loc, n_loc):
    entry s on device c is C[c, (c - s) % T] (lower-circulant layout; see
    ``ring_layout_coords``), already reduced over ``row_axis`` if given.
    """
    T = jax.lax.axis_size(col_axis)       # static: drives the hop loop
    c = jax.lax.axis_index(col_axis)
    n_loc = a_local.shape[1]
    half = T // 2

    perm = [(i, (i + 1) % T) for i in range(T)]

    # Step 0: diagonal block — symmetric, use ATA (half the multiplications).
    out_dtype = out_dtype or a_local.dtype   # wire dtype (see gram_allreduce)
    blocks = [ata_full(a_local, levels=levels, leaf=leaf, variant=variant,
                       mode=mode, out_dtype=out_dtype,
                       interpret=interpret)]

    cur = a_local
    for s in range(1, half + 1):
        # Issue the rotate for this step; XLA's async collective-permute
        # overlaps it with the *previous* iteration's block product because
        # there is no data dependence between them.
        cur = jax.lax.ppermute(cur, col_axis, perm)
        # Device c now holds column block (c - s) % T.  The A_loc^t
        # operand runs through the leaf-program executor's trans_a index
        # maps — no transposed copy of the shard in HBM (reference mode
        # materializes it, as before).
        blk = strassen_matmul(a_local, cur, trans_a=True, levels=levels,
                              leaf=leaf, variant=variant, mode=mode,
                              out_dtype=out_dtype, interpret=interpret)
        if s == half and T % 2 == 0:
            # At the antipodal step each unordered pair {c, c-T/2} appears on
            # both devices: keep it only on c < T/2 (SPMD runs the same
            # program everywhere; masking is the "incomplete level" analogue).
            # jnp.where, not multiply-by-mask: 0 * Inf = NaN would let a
            # non-finite discarded block poison the stack (and, under
            # bfs25d, the psum that merges group stacks).
            blk = jnp.where(c < half, blk, jnp.zeros_like(blk))
        blocks.append(blk)

    out = jnp.stack(blocks)  # (half+1, n_loc, n_loc)
    if row_axis is not None:
        out = jax.lax.psum(out, row_axis)
    return out


def ring_stack_len(T: int) -> int:
    """Stack entries of the half-ring layout: floor(T/2) + 1."""
    return T // 2 + 1


def gram_bfs25d(a_local: jax.Array, col_axis: str, rep_axis: str,
                row_axis: Optional[str] = None, *,
                levels="auto", leaf: int = 256,
                variant: str = "strassen", mode: str = "auto",
                out_dtype=None, col_size: Optional[int] = None,
                rep_size: Optional[int] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """Communication-avoiding 2.5D half-ring gram (see module docstring).

    Device layout: ``a_local`` is the (rows/R, cols/T) shard of A,
    *replicated* over ``rep_axis`` (size c) — the 2.5D extra-memory axis.
    The half-ring's block tasks are distributed BFS-style over the c
    replication groups:

    * step 0 (diagonal, ATA — the paper's symmetric half-work recursion)
      is computed by every group (SPMD) and kept on group 0 only;
    * off-diagonal step s in 1..floor(T/2) (Strassen — the paper's HASA
      role) belongs to group (s-1) mod c.  Group r reaches its first step
      with ONE skewing ``ppermute`` over the combined (rep, col) axes
      (rotation by r+1 inside each group's ring) and then advances by c
      hops per ``ppermute``, so each group performs only
      ``ceil(floor(T/2)/c)`` sequential hops.

    Each group scatters its blocks into disjoint slots of an oversized
    stack (slot = global ring step; groups own disjoint residues mod c,
    masked slots hold exact zeros via ``jnp.where``), and one ``psum``
    over (rep, row) merges the stacks.  The result is identical in
    layout to ``gram_ring``: shape (floor(T/2)+1, n_loc, n_loc), entry s
    on ring device d is C[d, (d - s) % T] (``ring_layout_coords``),
    replicated over ``rep_axis``.
    """
    if col_size is None or rep_size is None:
        raise ValueError(
            "gram_bfs25d needs static col_size/rep_size — pass "
            "mesh.shape[col_axis] and mesh.shape[rep_axis]")
    T, c = col_size, rep_size
    half = T // 2
    n_off = -(-half // c)              # sequential hops per group
    r = jax.lax.axis_index(rep_axis)
    d = jax.lax.axis_index(col_axis)
    n_loc = a_local.shape[1]
    out_dtype = out_dtype or a_local.dtype   # wire dtype (see gram_allreduce)

    # Diagonal (ATA): computed by every replication group — the block is
    # 1 of the ~half/c + 1 per-device tasks, so the duplication is bounded
    # — and kept on group 0 only (jnp.where: exact zeros elsewhere, a
    # correctness requirement for the merging psum below).
    diag = ata_full(a_local, levels=levels, leaf=leaf, variant=variant,
                    mode=mode, out_dtype=out_dtype, interpret=interpret)
    diag = jnp.where(r == 0, diag, jnp.zeros_like(diag))

    # Oversized stack: slot s holds ring step s; slots beyond ``half``
    # only ever receive masked (zero) blocks and are sliced off before the
    # psum.  Sized so every group's last write index (n_off*c) is in
    # bounds — dynamic_update_slice must never clamp.
    stack = jnp.zeros((1 + n_off * c, n_loc, n_loc), out_dtype)
    stack = stack.at[0].set(diag)

    if n_off > 0:
        # Skew: group r starts at step s0 = r + 1.  One ppermute over the
        # *combined* (rep, col) axes realizes all groups' different
        # rotations at once (linear index = rep * T + col).
        skew = []
        for rr in range(c):
            for j in range(T):
                skew.append((rr * T + j, rr * T + (j + rr + 1) % T))
        cur = jax.lax.ppermute(a_local, (rep_axis, col_axis), skew)
        hop = [(i, (i + c) % T) for i in range(T)]
        for t in range(n_off):
            if t > 0:
                # Advance every group by c hops in one message; XLA's async
                # collective-permute overlaps it with the previous block
                # product (same pattern as gram_ring).
                cur = jax.lax.ppermute(cur, col_axis, hop)
            s = r + 1 + t * c          # this group's global ring step
            blk = strassen_matmul(a_local, cur, trans_a=True, levels=levels,
                                  leaf=leaf, variant=variant, mode=mode,
                                  out_dtype=out_dtype, interpret=interpret)
            valid = s <= half
            if T % 2 == 0:
                # antipodal dedup, as in gram_ring (jnp.where — see there)
                valid = valid & ((s != half) | (d < half))
            blk = jnp.where(valid, blk, jnp.zeros_like(blk))
            stack = jax.lax.dynamic_update_slice(
                stack, blk[None].astype(out_dtype), (s, 0, 0))

    out = stack[:half + 1]
    axes = (rep_axis,) if row_axis is None else (rep_axis, row_axis)
    return jax.lax.psum(out, axes)


def ring_layout_coords(T: int) -> list[tuple[int, int, int]]:
    """(device, step, global_block_row, global_block_col) ownership map of
    the half-ring layout, as (c, s, i, j) with (i, j) in the lower triangle."""
    coords = []
    half = T // 2
    for dev in range(T):
        for s in range(half + 1):
            if s == half and T % 2 == 0 and dev >= half:
                continue  # masked duplicate
            j = (dev - s) % T
            i, jj = (dev, j) if dev >= j else (j, dev)  # mirror wraps upper
            coords.append((dev, s, i, jj))
    return coords


# ---------------------------------------------------------------------------
# pjit-level wrapper
# ---------------------------------------------------------------------------

def default_gram_axes(mesh: Mesh) -> dict:
    """Map a mesh onto ``distributed_gram``'s (row, col, rep) axis kwargs
    by the repo's naming convention — "data" rows, "model" ring, "rep"
    replication — falling back to positional order for foreign names."""
    names = list(mesh.axis_names)
    row = "data" if "data" in names else next(
        (a for a in names if a != "rep"), names[0])
    # never reuse the row axis as the ring axis (a ("model",)-only mesh
    # has row == "model"; P(row, row) in_specs would fail at compile time)
    col = "model" if ("model" in names and row != "model") else next(
        (a for a in names if a not in (row, "rep")), None)
    rep = "rep" if "rep" in names else None
    return {"row_axis": row, "col_axis": col, "rep_axis": rep}


def feasible_schemes(m: int, n: int, mesh: Mesh, *,
                     row_axis: str = "data",
                     col_axis: Optional[str] = None,
                     rep_axis: Optional[str] = None) -> list[str]:
    """Schemes runnable for an (m, n) A on ``mesh`` with the given axes
    (shard_map divisibility + axis availability)."""
    sizes = dict(mesh.shape)
    out = []
    if row_axis in sizes and m % sizes[row_axis] == 0:
        out += ["allreduce"]
        if n % sizes[row_axis] == 0:
            out += ["reducescatter"]
        if col_axis in sizes and n % sizes[col_axis] == 0:
            out += ["ring"]
            if rep_axis in sizes:
                out += ["bfs25d"]
    return out


def scheme_fallback_chain(m: int, n: int, mesh: Mesh, *,
                          scheme: str = "auto",
                          row_axis: str = "data",
                          col_axis: Optional[str] = None,
                          rep_axis: Optional[str] = None,
                          dtype_bytes: int = 4,
                          out_bytes: Optional[int] = None) -> list[str]:
    """Ordered list of schemes the serving layer should try for an
    (m, n) gram on ``mesh``: the preferred scheme first (the cost-model
    winner under ``scheme="auto"``, else ``scheme`` itself when
    feasible), then every other feasible scheme in ``SCHEME_LADDER``
    order — strictly degrading toward the paper-faithful allreduce.
    Empty when nothing is feasible (callers fall back to local)."""
    feas = feasible_schemes(m, n, mesh, row_axis=row_axis,
                            col_axis=col_axis, rep_axis=rep_axis)
    if not feas:
        return []
    if scheme == "auto":
        from . import cost_model
        sizes = dict(mesh.shape)
        ranked = cost_model.rank_gram_schemes(
            m, n,
            rows=sizes.get(row_axis, 1),
            ring=sizes.get(col_axis) if col_axis else None,
            rep=sizes.get(rep_axis) if rep_axis else None,
            dtype_bytes=dtype_bytes,
            out_bytes=out_bytes if out_bytes is not None else dtype_bytes,
            schemes=feas)
        head = ranked[0].scheme
    else:
        head = scheme if scheme in feas else None
    chain = [] if head is None else [head]
    chain += [s for s in SCHEME_LADDER if s in feas and s not in chain]
    return chain


def shrink_mesh(mesh: Mesh, axis: Optional[str] = None) -> Optional[Mesh]:
    """The surviving sub-mesh after losing one slice of ``axis`` (a dead
    replica group): same axis names, ``axis`` one shorter — slice 0 of
    ``axis`` is dropped, mirroring "the failed group's devices are gone".

    ``axis=None`` picks for least damage: the replication axis when it
    has size > 1 (bfs25d degrades to smaller c — or to plain ring at
    c=1 — with no resharding of the row/col layout), else the largest
    axis.  Returns None when the mesh is a single device (nothing left
    to shrink — the serving layer goes fully local).
    """
    sizes = dict(mesh.shape)
    if axis is None:
        if sizes.get("rep", 1) > 1:
            axis = "rep"
        else:
            axis = max(sizes, key=lambda a: sizes[a])
    if sizes.get(axis, 1) <= 1:
        shrinkable = [a for a, s in sizes.items() if s > 1]
        if not shrinkable:
            return None
        axis = max(shrinkable, key=lambda a: sizes[a])
    idx = mesh.axis_names.index(axis)
    devices = mesh.devices.take(range(1, sizes[axis]), axis=idx)
    return Mesh(devices, mesh.axis_names)


def distributed_gram(a: jax.Array, mesh: Mesh, *,
                     scheme: str = "allreduce",
                     row_axis: str = "data",
                     col_axis: Optional[str] = None,
                     rep_axis: Optional[str] = None,
                     levels="auto", leaf: int = 256,
                     variant: str = "strassen", mode: str = "auto",
                     out_dtype=None,
                     interpret: Optional[bool] = None,
                     assemble: bool = True) -> jax.Array:
    """Compute C = A^t A for a globally sharded A on ``mesh``.

    scheme:
      "allreduce"      — paper-faithful (rows sharded, psum).  C replicated.
      "reducescatter"  — C sharded by rows over ``row_axis``.
      "ring"           — rows x cols sharded, half-ring schedule. With
                         ``assemble`` (testing/solvers) the dense C is
                         rebuilt replicated; production keeps the circulant
                         block layout (sharded over ``col_axis``) —
                         n(n+1)/2-ish storage, zero post-processing.
      "bfs25d"         — 2.5D: ring + a replication axis ``rep_axis`` that
                         distributes the Strassen block tasks BFS-style
                         across replication groups (fewer, larger
                         messages; c-fold A memory).  Same output layout
                         as "ring".
      "auto"           — rank the feasible schemes with
                         ``cost_model.rank_gram_schemes`` (bytes moved +
                         message count + per-device flops) and run the
                         cheapest.

    ``levels`` is the depth of every shard's local kernels; ``"auto"``
    (the default) resolves on each shard's own shape, as ``ata`` and
    ``strassen_matmul`` resolve it.
    """
    if scheme == "auto":
        from . import cost_model
        cands = feasible_schemes(a.shape[0], a.shape[1], mesh,
                                 row_axis=row_axis, col_axis=col_axis,
                                 rep_axis=rep_axis)
        if not cands:
            raise ValueError(
                f"no feasible scheme for shape {a.shape} on mesh axes "
                f"{dict(mesh.shape)}")
        sizes = dict(mesh.shape)
        ranked = cost_model.rank_gram_schemes(
            a.shape[0], a.shape[1],
            rows=sizes.get(row_axis, 1),
            ring=sizes.get(col_axis) if col_axis else None,
            rep=sizes.get(rep_axis) if rep_axis else None,
            # ppermutes ship A (input dtype); reductions ship C (wire
            # dtype — the schemes default out_dtype to the input dtype)
            dtype_bytes=jnp.dtype(a.dtype).itemsize,
            out_bytes=jnp.dtype(out_dtype or a.dtype).itemsize,
            schemes=cands)
        scheme = ranked[0].scheme

    if scheme in ("allreduce", "reducescatter"):
        body = {
            "allreduce": gram_allreduce,
            "reducescatter": gram_reducescatter,
        }[scheme]
        fn = functools.partial(body, row_axis=row_axis, levels=levels,
                               leaf=leaf, variant=variant, mode=mode,
                               out_dtype=out_dtype, interpret=interpret)
        out_spec = P() if scheme == "allreduce" else P(row_axis)
        # named_scope: the resolved scheme lands in the HLO metadata, so
        # a profile (or HLO census) attributes traffic to the scheme the
        # cost model actually picked
        with jax.named_scope(f"gram_dist:{scheme}"):
            return jax.shard_map(
                fn, mesh=mesh, in_specs=P(row_axis, None),
                out_specs=out_spec, check_vma=False,
            )(a)

    if scheme in ("ring", "bfs25d"):
        if col_axis is None:
            raise ValueError(f"{scheme} scheme needs col_axis")
        T = mesh.shape[col_axis]
        n = a.shape[1]

        if scheme == "ring":
            def body(a_local):
                return gram_ring(a_local, col_axis, row_axis,
                                 levels=levels, leaf=leaf, variant=variant,
                                 mode=mode, out_dtype=out_dtype,
                                 interpret=interpret)
        else:
            if rep_axis is None:
                raise ValueError("bfs25d scheme needs rep_axis")
            c = mesh.shape[rep_axis]

            def body(a_local):
                return gram_bfs25d(a_local, col_axis, rep_axis, row_axis,
                                   levels=levels, leaf=leaf, variant=variant,
                                   mode=mode, out_dtype=out_dtype,
                                   col_size=T, rep_size=c,
                                   interpret=interpret)

        with jax.named_scope(f"gram_dist:{scheme}"):
            stacks = jax.shard_map(
                body, mesh=mesh,
                in_specs=P(row_axis, col_axis),
                # stack: (half+1, n/T, n/T) per device -> gather cols of
                # blocks
                out_specs=P(None, None, col_axis),
                check_vma=False,
            )(a)
        if not assemble:
            return stacks        # production: circulant layout, sharded
        # stacks: (half+1, n/T, n) — device c's column of blocks at slot c.
        return assemble_ring_gram(stacks, T, n)

    raise ValueError(f"unknown scheme {scheme!r}")


def assemble_ring_gram(stacks: jax.Array, T: int, n: int) -> jax.Array:
    """Assemble the dense symmetric C from half-ring output.

    ``stacks``: (half+1, n_loc, n) where [:, :, c*n_loc:(c+1)*n_loc] is
    device c's block stack (entry s = C[c, (c-s)%T] contribution).
    """
    n_loc = n // T
    c = jnp.zeros((n, n), stacks.dtype)
    half = T // 2
    for dev in range(T):
        for s in range(half + 1):
            if s == half and T % 2 == 0 and dev >= half:
                continue
            blk = stacks[s, :, dev * n_loc:(dev + 1) * n_loc]  # C[dev, j]
            j = (dev - s) % T
            if dev >= j:
                c = jax.lax.dynamic_update_slice(c, blk, (dev * n_loc, j * n_loc))
            else:  # wrapped: this is C[dev, j] with j > dev — mirror it
                c = jax.lax.dynamic_update_slice(c, blk.T, (j * n_loc, dev * n_loc))
    return symmetrize_from_lower(jnp.tril(c))
