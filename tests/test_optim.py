"""Optimizers: AdamW / ATA-Shampoo convergence + equivalences +
gradient compression error-feedback properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (adamw, shampoo, apply_updates, warmup_cosine,
                         int8_quantize, int8_dequantize, ErrorFeedback,
                         lowrank_basis)


def _run_quadratic(opt, steps=120, shape=(8, 6)):
    """min ||W - T||^2 for a 2-D param (exercises the Shampoo path)."""
    key = jax.random.PRNGKey(0)
    target = jax.random.normal(key, shape)
    params = {"w": jnp.zeros(shape)}
    state = opt.init(params)

    @jax.jit
    def step(params, state, i):
        grads = jax.tree.map(lambda w: 2 * (w - target), params)
        updates, state, _ = opt.update(grads, state, params, i)
        return apply_updates(params, updates), state

    for i in range(steps):
        params, state = step(params, state, jnp.int32(i))
    return float(jnp.sum((params["w"] - target) ** 2))


def test_adamw_converges():
    loss = _run_quadratic(adamw(0.05, weight_decay=0.0))
    assert loss < 1e-2, loss


def test_shampoo_converges():
    loss = _run_quadratic(
        shampoo(0.05, weight_decay=0.0, block_size=8, precond_interval=5,
                ata_levels=1, ata_leaf=2))
    assert loss < 1e-2, loss


def test_shampoo_strassen_equals_classical():
    """The ATA variant (paper's Strassen recursion) must be numerically
    equivalent to classical grams inside Shampoo."""
    kw = dict(weight_decay=0.0, block_size=8, precond_interval=3,
              ata_leaf=2)
    opt_s = shampoo(0.05, ata_levels=2, ata_variant="strassen", **kw)
    opt_c = shampoo(0.05, ata_levels=0, ata_variant="classical", **kw)
    key = jax.random.PRNGKey(1)
    target = jax.random.normal(key, (8, 6))
    outs = []
    for opt in (opt_s, opt_c):
        params = {"w": jnp.zeros((8, 6))}
        state = opt.init(params)
        for i in range(10):
            grads = jax.tree.map(lambda w: 2 * (w - target), params)
            updates, state, _ = opt.update(grads, state, params,
                                           jnp.int32(i))
            params = apply_updates(params, updates)
        outs.append(np.asarray(params["w"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-5)


def test_shampoo_ata_mode_reference_matches_default():
    """ata_mode= is threaded to the batched Gram path; forcing the
    reference recursion must match the auto-dispatched default."""
    kw = dict(weight_decay=0.0, block_size=8, precond_interval=3,
              ata_levels=1, ata_leaf=2)
    opt_auto = shampoo(0.05, **kw)
    opt_ref = shampoo(0.05, ata_mode="reference", **kw)
    target = jax.random.normal(jax.random.PRNGKey(2), (8, 6))
    outs = []
    for opt in (opt_auto, opt_ref):
        params = {"w": jnp.zeros((8, 6))}
        state = opt.init(params)
        for i in range(6):
            grads = jax.tree.map(lambda w: 2 * (w - target), params)
            updates, state, _ = opt.update(grads, state, params,
                                           jnp.int32(i))
            params = apply_updates(params, updates)
        outs.append(np.asarray(params["w"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


def test_shampoo_blocks_large_dim():
    """dims > block_size are split into independent blocks; still converges
    and the gram stats have the blocked shape."""
    opt = shampoo(0.05, weight_decay=0.0, block_size=4, precond_interval=5,
                  ata_leaf=2)
    params = {"w": jnp.zeros((8, 6))}     # 2x2 blocks of (4, 3)... 4|8, 6->pad
    state = opt.init(params)
    gr = state["gram"]["w"]
    assert gr["l"].shape == (2 * 2, 4, 4)
    assert gr["r"].shape == (2 * 2, 4, 4) or gr["r"].shape == (4, 3, 3)


def test_shampoo_1d_falls_back_to_adam():
    opt_s = shampoo(0.05, weight_decay=0.0)
    opt_a = adamw(0.05, weight_decay=0.0, b2=0.95)
    params = {"b": jnp.ones((16,))}
    ss, sa = opt_s.init(params), opt_a.init(params)
    grads = {"b": jnp.linspace(-1, 1, 16)}
    us, _, _ = opt_s.update(grads, ss, params, jnp.int32(0))
    ua, _, _ = opt_a.update(grads, sa, params, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(us["b"]), np.asarray(ua["b"]),
                               rtol=1e-6)


def test_warmup_cosine_shape():
    s = warmup_cosine(1.0, warmup=10, total=100)
    assert float(s(jnp.int32(0))) < 0.2
    assert abs(float(s(jnp.int32(10))) - 1.0) < 0.11
    assert float(s(jnp.int32(99))) < 0.2


def test_int8_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(2), (256,)) * 3
    q, scale = int8_quantize(x)
    err = np.abs(np.asarray(int8_dequantize(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_lowrank_basis_orthonormal_and_exact_for_lowrank_grads():
    """The Gram-derived basis is orthonormal, and a gradient that is
    exactly rank-r is reconstructed exactly by its rank-r projection."""
    key = jax.random.PRNGKey(3)
    u = jax.random.normal(key, (64, 3))
    v = jax.random.normal(jax.random.PRNGKey(4), (12, 3))
    g = u @ v.T                                  # exactly rank 3, tall
    q = lowrank_basis(g, 3, levels=1, leaf=4)
    qq = np.asarray(q.T @ q)
    np.testing.assert_allclose(qq, np.eye(3), atol=1e-5)
    recon = np.asarray((g @ q) @ q.T)
    np.testing.assert_allclose(recon, np.asarray(g), rtol=1e-4, atol=1e-4)


def test_lowrank_psum_error_feedback_invariant():
    """Inside shard_map (1-device axis): emitted + residual tracks the true
    gradient, and tall 2-D leaves take the low-rank path (residual is the
    orthogonal complement, not a quantization residual)."""
    from repro.optim import lowrank_psum
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("pod",))
    g = {"tall": jax.random.normal(jax.random.PRNGKey(5), (64, 8)),
         "bias": jnp.linspace(-1, 1, 16)}
    ef = ErrorFeedback.init(g)

    def body(grads, resid):
        out, new_ef = lowrank_psum(grads, "pod", ErrorFeedback(resid),
                                   rank=4, levels=1, leaf=4)
        return out, new_ef.residual

    out, resid = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()),
                               check_vma=False)(g, ef.residual)
    # emitted + residual == true gradient, leafwise (EF invariant, 1 dev)
    for k in g:
        np.testing.assert_allclose(np.asarray(out[k] + resid[k]),
                                   np.asarray(g[k]), rtol=1e-4, atol=1e-5)
    # the tall leaf went low-rank: its emission has rank <= 4
    s = np.linalg.svd(np.asarray(out["tall"]), compute_uv=False)
    assert (s > 1e-4 * s[0]).sum() <= 4


def test_error_feedback_accumulates_residual():
    """With error feedback, the SUM of quantized emissions tracks the sum
    of true gradients (residual never lost) — key convergence property."""
    g = jnp.full((64,), 0.003)            # much smaller than typical scale
    big = jnp.zeros((64,)).at[0].set(1.0)  # forces a coarse scale
    ef_resid = jnp.zeros((64,))
    emitted = jnp.zeros((64,))
    for _ in range(50):
        gt = g + big
        q, s = int8_quantize(gt + ef_resid)
        deq = int8_dequantize(q, s)
        ef_resid = gt + ef_resid - deq
        emitted = emitted + deq
    total_true = 50 * (g + big)
    # emitted + residual == exact running sum (error feedback invariant)
    np.testing.assert_allclose(np.asarray(emitted + ef_resid),
                               np.asarray(total_true), rtol=1e-5, atol=1e-5)
    # and the residual itself stays bounded by one quantization step
    assert float(jnp.abs(ef_resid).max()) < float(s) * 1.0 + 1e-6
