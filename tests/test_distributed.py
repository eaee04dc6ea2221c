"""Multi-device parity for ALL distributed-gram schemes, on 8 forced-host
devices via the ``multidevice`` marker (tests/conftest.py): each marked
test re-runs itself in a child pytest where XLA_FLAGS forces the device
count, so the main pytest process keeps the default 1-device platform.

Covers, per the half-ring/2.5D layout contract of ``core.distributed``:
odd and even ring sizes, odd and even replication factors, rectangular
(m != n) shards, fp32/bf16 wire dtypes, ``assemble=False`` layouts, the
``scheme="auto"`` cost-model dispatch, and the antipodal-dedup
non-finite regression (jnp.where vs multiply-by-mask).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import (assemble_ring_gram, distributed_gram,
                        feasible_schemes, ring_layout_coords)

AX3 = ("rep", "data", "model")
KW2 = dict(row_axis="data", col_axis="model")
KW3 = dict(row_axis="data", col_axis="model", rep_axis="rep")

# (mesh shape, axis names, distributed_gram axis kwargs) per scheme —
# odd and even ring sizes T and replication factors c, with and without
# a nontrivial row axis.  Meshes smaller than 8 use a device subset.
MESHES = {
    "allreduce": [((8,), ("data",), {}),
                  ((2, 4), ("data", "model"), KW2)],
    "reducescatter": [((8,), ("data",), {}),
                      ((4,), ("data",), {})],
    "ring": [((2, 4), ("data", "model"), KW2),      # even ring, 2 rows
             ((1, 8), ("data", "model"), KW2),      # even ring, row size 1
             ((2, 3), ("data", "model"), KW2)],     # odd ring (6 devices)
    "bfs25d": [((2, 1, 4), AX3, KW3),               # even ring, even rep
               ((2, 2, 2), AX3, KW3),               # 2x2x2, all axes real
               ((4, 1, 2), AX3, KW3),               # rep 4
               ((3, 1, 2), AX3, KW3),               # odd rep (6 devices)
               ((2, 1, 3), AX3, KW3)],              # odd ring (6 devices)
}


def _mesh(shape, names):
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), names)


def _oracle(a):
    a64 = np.asarray(a, np.float64)
    return a64.T @ a64


@pytest.mark.multidevice(8)
@pytest.mark.parametrize("scheme", sorted(MESHES))
def test_scheme_parity_8dev(scheme, multidevice_count):
    """Every scheme x mesh x recursion depth x dtype x (rectangular and
    square) shard shape matches the float64 dense oracle."""
    shapes = [(120, 48), (48, 48)]      # m=120: rows divide 1/2/3/4/8
    cases = [                           # classical leaf, 1 and 2 levels
        (0, jnp.float32, 1e-4),
        (1, jnp.float32, 1e-4),
        (1, jnp.bfloat16, 5e-2),
        (2, jnp.float32, 1e-4),
    ]
    for mesh_shape, names, kw in MESHES[scheme]:
        mesh = _mesh(mesh_shape, names)
        for m, n in shapes:
            for levels, dtype, tol in cases:
                a = jax.random.normal(
                    jax.random.PRNGKey(0), (m, n)).astype(dtype)
                # jitted: an eager shard_map dispatches the recursion's
                # ops one by one, ~20x slower for the same program
                got = jax.jit(lambda x: distributed_gram(
                    x, mesh, scheme=scheme, levels=levels, leaf=8, **kw))(a)
                got = np.asarray(jax.device_get(got), np.float64)
                want = _oracle(a)
                err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
                assert err < tol, (scheme, mesh_shape, (m, n), levels,
                                   str(dtype), err)


@pytest.mark.multidevice(8)
@pytest.mark.parametrize("scheme,mesh_shape,names,kw", [
    ("ring", (2, 4), ("data", "model"), KW2),
    ("ring", (1, 8), ("data", "model"), KW2),
    ("ring", (2, 3), ("data", "model"), KW2),
    ("bfs25d", (2, 1, 4), AX3, KW3),
    ("bfs25d", (2, 1, 3), AX3, KW3),
    ("bfs25d", (4, 1, 2), AX3, KW3),
])
def test_half_ring_layout_contract(scheme, mesh_shape, names, kw,
                                   multidevice_count):
    """``assemble=False`` returns the documented circulant block layout:
    stack entry s, ring device d == C[d, (d - s) % T]; the masked
    antipodal duplicates are EXACT zeros; assemble_ring_gram rebuilds the
    dense oracle."""
    m, n = 96, 48
    T = mesh_shape[-1]
    n_loc = n // T
    half = T // 2
    a = jax.random.normal(jax.random.PRNGKey(1), (m, n), jnp.float32)
    mesh = _mesh(mesh_shape, names)
    stacks = distributed_gram(a, mesh, scheme=scheme, levels=1, leaf=8,
                              assemble=False, **kw)
    stacks = np.asarray(jax.device_get(stacks), np.float64)
    assert stacks.shape == (half + 1, n_loc, n)
    want = _oracle(a)

    owned = set()
    for dev, s, i, j in ring_layout_coords(T):
        owned.add((dev, s))
        jdev = (dev - s) % T
        got = stacks[s][:, dev * n_loc:(dev + 1) * n_loc]
        blk = want[dev * n_loc:(dev + 1) * n_loc,
                   jdev * n_loc:(jdev + 1) * n_loc]
        np.testing.assert_allclose(got, blk, rtol=1e-4, atol=1e-4,
                                   err_msg=f"dev={dev} s={s}")
    # slots NOT in the ownership map are the antipodal duplicates: zeros
    for dev in range(T):
        for s in range(half + 1):
            if (dev, s) not in owned:
                got = stacks[s][:, dev * n_loc:(dev + 1) * n_loc]
                assert np.all(got == 0.0), (dev, s)

    dense = np.asarray(
        assemble_ring_gram(jnp.asarray(stacks, jnp.float32), T, n),
        np.float64)
    np.testing.assert_allclose(dense, want, rtol=1e-4, atol=1e-4)


@pytest.mark.multidevice(8)
def test_antipodal_mask_is_select_not_multiply(multidevice_count):
    """Regression: the even-ring antipodal dedup must use jnp.where, not
    multiply-by-mask — 0 * Inf = NaN would leak a discarded non-finite
    block into the stack (and poison the bfs25d merging psum)."""
    m, n, T = 64, 48, 4
    n_loc, half = n // T, T // 2
    a = np.array(jax.random.normal(jax.random.PRNGKey(2), (m, n)),
                 np.float32)
    a[0, 40] = np.inf            # lives in ring column block 3
    a = jnp.asarray(a)

    mesh = _mesh((2, 4), ("data", "model"))
    stacks = distributed_gram(a, mesh, scheme="ring", levels=1, leaf=8,
                              assemble=False, **KW2)
    stacks = np.asarray(jax.device_get(stacks))
    # discarded antipodal slots (s=half, dev >= half) are exact zeros even
    # though device 3's discarded product contains the Inf column block
    for dev in range(half, T):
        got = stacks[half][:, dev * n_loc:(dev + 1) * n_loc]
        assert np.all(got == 0.0), dev

    # bfs25d relies on those exact zeros for its merging psum: entries of
    # C that the oracle keeps finite must stay finite (no 0*Inf=NaN).
    # levels=0 (classical leaves): Strassen's own operand sums would turn
    # Inf into NaN at finite-oracle entries regardless of the mask.
    mesh3 = _mesh((2, 1, 4), AX3)
    dense = np.asarray(jax.device_get(
        distributed_gram(a, mesh3, scheme="bfs25d", levels=0, leaf=8,
                         **KW3)), np.float64)
    want = _oracle(a)
    finite = np.isfinite(want)
    assert finite[:40, :40].all()
    np.testing.assert_allclose(dense[finite], want[finite],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.multidevice(8)
def test_auto_scheme_matches_oracle(multidevice_count):
    """scheme="auto" picks a feasible scheme via the comm cost model and
    matches the oracle on 1-, 2- and 3-axis meshes."""
    cases = [
        ((8,), ("data",), {}),
        ((2, 4), ("data", "model"), KW2),
        ((2, 2, 2), AX3, KW3),
    ]
    for mesh_shape, names, kw in cases:
        mesh = _mesh(mesh_shape, names)
        for m, n in [(512, 32), (64, 64)]:
            a = jax.random.normal(jax.random.PRNGKey(3), (m, n), jnp.float32)
            assert feasible_schemes(m, n, mesh, **kw)
            got = np.asarray(jax.device_get(
                distributed_gram(a, mesh, scheme="auto", levels=1, leaf=8,
                                 **kw)), np.float64)
            want = _oracle(a)
            err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
            assert err < 1e-4, (mesh_shape, (m, n), err)


def test_feasible_schemes_single_device_logic():
    """Pure axis/divisibility logic — no multi-device platform needed
    (feasible_schemes only reads ``mesh.shape``)."""
    from types import SimpleNamespace as NS
    mesh = NS(shape={"rep": 2, "data": 2, "model": 4})
    assert feasible_schemes(64, 48, mesh, **KW3) == \
        ["allreduce", "reducescatter", "ring", "bfs25d"]
    # n not divisible by the ring axis: ring family drops out
    assert feasible_schemes(64, 46, mesh, **KW3) == \
        ["allreduce", "reducescatter"]
    # n not divisible by the row axis: reducescatter drops out
    assert feasible_schemes(63, 50, NS(shape={"data": 7})) == ["allreduce"]
    # m not divisible by the row axis: nothing fits
    assert feasible_schemes(65, 48, NS(shape={"data": 2})) == []
    # missing col axis: no ring family
    assert "ring" not in feasible_schemes(64, 48, NS(shape={"data": 2}),
                                          col_axis="model")


def test_default_gram_axes_never_duplicates_row_as_col():
    """A mesh with a 'model' axis but no 'data' axis must not map row and
    col onto the same axis (P(model, model) would fail at compile time)."""
    from types import SimpleNamespace as NS
    from repro.core import default_gram_axes

    ax = default_gram_axes(NS(axis_names=("model",)))
    assert ax["row_axis"] == "model" and ax["col_axis"] is None
    ax = default_gram_axes(NS(axis_names=("rep", "model")))
    assert ax == {"row_axis": "model", "col_axis": None, "rep_axis": "rep"}
    ax = default_gram_axes(NS(axis_names=("rep", "data", "model")))
    assert ax == {"row_axis": "data", "col_axis": "model",
                  "rep_axis": "rep"}
    ax = default_gram_axes(NS(axis_names=("x", "y")))
    assert ax == {"row_axis": "x", "col_axis": "y", "rep_axis": None}
