"""Streaming Gram accumulator: any chunking == one-shot ata_full.

The hypothesis-driven any-chunking property lives in test_properties.py
(gated on hypothesis availability); here the same invariant is pinned by
deterministic parametrized cases — fp32/bf16, ragged final chunk,
levels 0-2 — plus the sharded/distributed streaming variants on 8
forced-host devices via the ``multidevice`` marker (tests/conftest.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import gram
from repro.core.ata import ata_full


def _oracle(a):
    a64 = np.asarray(a, np.float64)
    return a64.T @ a64


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5),
                                       (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("levels", [0, 1, 2])
@pytest.mark.parametrize("chunks", [
    [(0, 96)],                       # one shot through the stream
    [(0, 32), (32, 64), (64, 96)],   # even chunks
    [(0, 40), (40, 89), (89, 96)],   # ragged, incl. a 7-row tail
    [(0, 1), (1, 2), (2, 96)],       # degenerate 1-row chunks
])
def test_stream_matches_one_shot(dtype, tol, levels, chunks):
    m, n = 96, 24
    a = jax.random.normal(jax.random.PRNGKey(0), (m, n)).astype(dtype)
    st = gram.stream_init(n)
    for lo, hi in chunks:
        st = gram.stream_update(st, a[lo:hi], levels=levels, leaf=8)
    got = np.asarray(gram.stream_finalize(st), np.float64)
    want = _oracle(a)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() / scale < tol
    assert int(st.rows) == m


def test_stream_matches_ata_full_fused_interpret():
    """The fused Pallas path (interpret mode) agrees with streaming too."""
    a = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    st = gram.stream_init(32)
    for lo, hi in [(0, 48), (48, 64)]:
        st = gram.stream_update(st, a[lo:hi], levels=1, mode="fused",
                                block=16, interpret=True)
    got = np.asarray(gram.stream_finalize(st), np.float64)
    want = _oracle(a)
    assert np.abs(got - want).max() / np.abs(want).max() < 5e-5


def test_stream_finalize_tril_only():
    a = jax.random.normal(jax.random.PRNGKey(2), (20, 10), jnp.float32)
    st = gram.stream_update(gram.stream_init(10), a, levels=1, leaf=4)
    low = np.asarray(gram.stream_finalize(st, symmetrize=False))
    assert np.abs(np.triu(low, 1)).max() == 0.0
    full = np.asarray(gram.stream_finalize(st))
    np.testing.assert_allclose(full, full.T, rtol=1e-6)


def test_stream_state_is_packed():
    """The accumulator holds n(n+1)/2 words — the paper's storage bound —
    not a dense n^2 buffer."""
    st = gram.stream_init(64)
    assert st.packed.shape == (64 * 65 // 2,)
    assert st.n == 64


def test_stream_rejects_mismatched_chunk():
    st = gram.stream_init(8)
    with pytest.raises(ValueError):
        gram.stream_update(st, jnp.zeros((4, 9)))


def test_normalized_second_moment():
    """C / rows is the running second moment — the typical consumer
    reading (preconditioners, whitening)."""
    a = jax.random.normal(jax.random.PRNGKey(3), (200, 12), jnp.float32)
    st = gram.stream_init(12)
    for lo in range(0, 200, 50):
        st = gram.stream_update(st, a[lo:lo + 50], levels=1, leaf=4)
    c = np.asarray(gram.stream_finalize(st)) / int(st.rows)
    want = _oracle(a) / 200
    np.testing.assert_allclose(c, want, rtol=1e-4, atol=1e-5)


@pytest.mark.multidevice(8)
def test_sharded_streaming_8dev(multidevice_count):
    """Row-sharded streaming (reduce-scatter state) == sequential, on 8
    forced-host devices (ported from the old ad-hoc subprocess script to
    the ``multidevice`` marker)."""
    from jax.sharding import PartitionSpec as P

    P_DEV, m, n = 8, 128, 64
    a = jax.random.normal(jax.random.PRNGKey(0), (m, n), jnp.float32)
    want = _oracle(a)

    mesh = jax.make_mesh((P_DEV,), ("data",))

    def stream(chunks):
        # per-device: fold row-sharded chunks into the block-row shard of C
        c = gram.sharded_init(n, P_DEV)
        for chunk in chunks:
            c = gram.update_sharded(c, chunk, "data", levels=1, leaf=8)
        return c

    chunk_bounds = [(0, 48), (48, 128)]   # ragged: 48 and 80 rows
    chunks = tuple(a[lo:hi] for lo, hi in chunk_bounds)
    got = jax.shard_map(
        stream, mesh=mesh,
        in_specs=(P("data", None),),     # pytree prefix: every chunk by rows
        out_specs=P("data", None), check_vma=False,
    )(chunks)
    got = np.asarray(jax.device_get(got), np.float64)
    assert got.shape == (n, n)
    err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert err < 1e-4, err


@pytest.mark.multidevice(8)
@pytest.mark.parametrize("scheme", ["reducescatter", "ring", "bfs25d"])
def test_distributed_streaming_composes_with_schemes(scheme,
                                                     multidevice_count):
    """pjit-level distributed streaming: any chunking through
    distributed_init/update/finalize == one-shot oracle, for the
    reduce-scatter state AND the half-ring/2.5D circulant stack states."""
    from jax.sharding import Mesh

    m, n = 96, 48
    a = jax.random.normal(jax.random.PRNGKey(1), (m, n), jnp.float32)
    want = _oracle(a)

    if scheme == "reducescatter":
        mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
        kw = dict(row_axis="data", col_axis=None)
    elif scheme == "ring":
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        kw = dict(row_axis="data", col_axis="model")
    else:
        mesh = Mesh(np.array(jax.devices()).reshape(2, 1, 4),
                    ("rep", "data", "model"))
        kw = dict(row_axis="data", col_axis="model", rep_axis="rep")

    state = gram.distributed_init(
        n, mesh, scheme=scheme,
        **{k: v for k, v in kw.items() if k != "rep_axis"})
    for lo, hi in [(0, 32), (32, 96)]:   # ragged chunks, rows divide axes
        state = gram.distributed_update(state, a[lo:hi], mesh,
                                        scheme=scheme, levels=1, leaf=8,
                                        **kw)
    got = np.asarray(jax.device_get(gram.distributed_finalize(
        state, mesh, scheme=scheme,
        col_axis=kw.get("col_axis"))), np.float64)
    err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert err < 1e-4, (scheme, err)
