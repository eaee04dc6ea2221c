"""Gradient parity for the fused Pallas paths' custom VJPs
(kernels/strassen_fused.py).

The fused backward is itself a leaf-task schedule now (DESIGN.md §11):
``dA = A (S + S^t)`` runs ``plan_symm`` through ``fused_symm_matmul``
(packed cotangent, mirrored upper-triangle reads), and the matmul VJP runs
both products through the schedule executor with the transposes folded
into the index maps.  Everything here checks those kernels against
``jax.grad`` of the reference recursion / dense oracles — fp32 and bf16,
square and rectangular 257x511 (prime-ish, exercises the padding path),
levels 0-3, plus the dense / packed / streamed entry points at the
512x512 <= 1e-5 acceptance bar and the backward HBM-traffic acceptance.
Runs in interpret mode off-TPU like the forward-parity suite.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ata import ata
from repro.core.schedule import plan_symm, evaluate_symm_plan
from repro.core.strassen import strassen_matmul
from repro.core.symmetry import pack_tril_blocks
from repro.kernels.strassen_fused import (
    ata_bwd_traffic_model, fused_ata_packed, fused_symm_matmul,
)


def _rel(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.abs(got - want).max() / (np.abs(want).max() + 1e-9)


# ---------------------------------------------------------------------------
# The symm executor itself (the backward engine), against dense oracles.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("levels", [0, 1, 2])
@pytest.mark.parametrize("m,n,bs", [(32, 32, 8), (24, 48, 8), (16, 16, 16)])
def test_fused_symm_matmul_matches_dense(levels, m, n, bs):
    """X @ Sym from packed-lower-only storage: upper tiles are mirrored
    (j, i) reads with the transpose folded into the index maps."""
    rng = np.random.RandomState(levels + m)
    x = jnp.asarray(rng.randn(m, n), jnp.float32)
    s = rng.randn(n, n)
    sym = np.tril(s) + np.tril(s, -1).T
    stack = pack_tril_blocks(jnp.asarray(sym, jnp.float32), bs)
    got = fused_symm_matmul(x, stack, levels=levels, bm=8, interpret=True)
    assert _rel(np.asarray(got)[:, :n], np.asarray(x, np.float64) @ sym) \
        < 1e-5


@pytest.mark.parametrize("levels", [0, 1, 2])
def test_fused_symm_matmul_diag_sym(levels):
    """diag_sym=True computes X @ (S + S^t) — the Gram-VJP operand — with
    the diagonal tiles doubled symmetrically in VMEM."""
    rng = np.random.RandomState(7 + levels)
    x = jnp.asarray(rng.randn(40, 32), jnp.float32)
    s = np.tril(rng.randn(32, 32))
    stack = pack_tril_blocks(jnp.asarray(s, jnp.float32), 8)
    got = fused_symm_matmul(x, stack, levels=levels, bm=8, diag_sym=True,
                            interpret=True)
    assert _rel(got, np.asarray(x, np.float64) @ (s + s.T)) < 1e-5


@pytest.mark.parametrize("variant", ["strassen", "winograd", "classical"])
def test_symm_plan_dense_evaluation(variant):
    """plan_symm evaluated densely in numpy reproduces X @ Sym reading
    only the lower triangle — correct independent of the executor."""
    rng = np.random.RandomState(3)
    for levels in (1, 2):
        B = 1 << levels
        x = rng.randn(B * 3, B * 2)
        s = rng.randn(B * 2, B * 2)
        sym = np.tril(s) + np.tril(s, -1).T
        np.testing.assert_allclose(
            evaluate_symm_plan(plan_symm(levels, variant), x, np.tril(s)),
            x @ sym, rtol=1e-9, atol=1e-9)


def test_fused_symm_bf16_accumulates_fp32():
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(32, 32)).astype(jnp.bfloat16)
    s = rng.randn(32, 32)
    sym = np.tril(s) + np.tril(s, -1).T
    stack = pack_tril_blocks(jnp.asarray(sym), 8).astype(jnp.bfloat16)
    got = fused_symm_matmul(x, stack, levels=1, bm=8, interpret=True)
    assert got.dtype == jnp.float32          # promoted accumulation dtype
    want = np.asarray(x.astype(jnp.float32), np.float64) \
        @ np.asarray(jnp.asarray(sym).astype(jnp.bfloat16).astype(
            jnp.float32), np.float64)
    assert _rel(got, want) < 5e-2


# ---------------------------------------------------------------------------
# Dense-entry grad parity vs the reference recursion: dtypes x shapes x
# levels 0-3 (levels swept at the small square; the rectangular padded
# case at the depths the shape supports).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_fused_ata_grad_matches_reference(dtype, tol, levels):
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)

    def loss(x, mode):
        c = ata(x, levels=levels, leaf=8, mode=mode, block=8,
                interpret=True, out_dtype=jnp.float32)
        return jnp.vdot(w, c)

    g_fused = jax.grad(lambda x: loss(x, "fused"))(a)
    g_ref = jax.grad(lambda x: loss(x, "reference"))(a)
    assert g_fused.shape == a.shape and g_fused.dtype == a.dtype
    assert _rel(g_fused, g_ref) < tol


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("levels", [1, 2])
def test_fused_ata_grad_rectangular(dtype, tol, levels):
    """257x511: prime-ish shape exercises the pad path of forward AND
    backward (the packed cotangent spans the padded 512 grid)."""
    a = jax.random.normal(jax.random.PRNGKey(2), (257, 511)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(3), (511, 511), jnp.float32)

    def loss(x, mode):
        c = ata(x, levels=levels, leaf=32, mode=mode, block=64,
                interpret=True, out_dtype=jnp.float32)
        return jnp.vdot(w, c)

    g_fused = jax.grad(lambda x: loss(x, "fused"))(a)
    g_ref = jax.grad(lambda x: loss(x, "reference"))(a)
    assert _rel(g_fused, g_ref) < tol


def test_fused_vs_dense_bwd_engines_agree():
    """bwd="fused" (symm schedule) and bwd="dense" (dense-dot baseline)
    are the same math; benchmarks rely on both staying selectable."""
    a = jax.random.normal(jax.random.PRNGKey(4), (96, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(5), (64, 64), jnp.float32)

    def g(bwd):
        return jax.grad(lambda x: jnp.vdot(w, ata(
            x, levels=2, mode="fused", bwd=bwd, block=16,
            interpret=True)))(a)

    assert _rel(g("fused"), g("dense")) < 1e-5


# ---------------------------------------------------------------------------
# Packed-cotangent path: fused_ata_packed's custom VJP consumes the packed
# stack directly (no dense unpack anywhere in the backward).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 5e-2)])
def test_packed_cotangent_grad(dtype, tol):
    a = jax.random.normal(jax.random.PRNGKey(6), (48, 32)).astype(dtype)
    bn = 8

    def loss_packed(x):
        p, _ = fused_ata_packed(x, levels=1, bk=bn, bn=bn,
                                out_dtype=jnp.float32, interpret=True)
        return (p * p).sum()

    # dense oracle for the same loss: the packed stack is the block-lower
    # triangle with FULL diagonal tiles
    n = 32
    t = n // bn
    mask = np.zeros((n, n), np.float32)
    for i in range(t):
        mask[i * bn:(i + 1) * bn, :(i + 1) * bn] = 1.0

    def loss_dense(x):
        xf = x.astype(jnp.float32)
        c = jnp.dot(xf.T, xf, preferred_element_type=jnp.float32) * mask
        return (c * c).sum()

    gp = jax.grad(loss_packed)(a)
    gd = jax.grad(loss_dense)(a)
    assert gp.dtype == a.dtype
    assert _rel(gp, gd) < tol


def test_packed_grad_traces_no_dense_cotangent():
    """The packed VJP must not build any dense (n, n) buffer beyond dA
    itself: the cotangent flows packed-stack -> symm kernel -> dA.  The
    dense-dot baseline, by contrast, scatters/unpacks/symmetrizes at n^2
    repeatedly.  (Asserted on the jaxpr — an HLO census of the interpret
    lowering would measure the Pallas emulation, not the kernel.)"""
    n, bn = 256, 32
    a = jnp.ones((n, n), jnp.float32)

    def make_loss(bwd):
        def loss(x):
            p, _ = fused_ata_packed(x, levels=1, bk=bn, bn=bn,
                                    interpret=True, bwd=bwd)
            return (p * p).sum()
        return loss

    def dense_outputs(bwd):
        jaxpr = jax.make_jaxpr(jax.grad(make_loss(bwd)))(a)
        return sum(1 for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
                   if getattr(v.aval, "shape", None) == (n, n))

    assert dense_outputs("fused") <= 1        # dA, nothing else
    assert dense_outputs("dense") >= 4        # unpack + S + S^t + dot ...


# ---------------------------------------------------------------------------
# Streamed entry point: gram.stream updates differentiate through the
# fused packed kernel (stack -> packed-vector gather keeps it dense-free).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["fused", "reference"])
def test_stream_update_differentiable(mode):
    from repro import gram

    n = 32
    a = jax.random.normal(jax.random.PRNGKey(8), (40, n), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), (n * (n + 1) // 2,),
                          jnp.float32)

    def loss(x):
        st = gram.stream_init(n)
        st = gram.stream_update(st, x, levels=1, leaf=8, mode=mode,
                                block=8, interpret=True)
        return jnp.vdot(w, st.packed)

    g = jax.grad(loss)(a)
    # oracle: vdot(w, pack_tril(tril(x^t x)))
    wd = np.zeros((n, n), np.float32)
    wd[np.tril_indices(n)] = np.asarray(w)
    g_oracle = jax.grad(
        lambda x: jnp.vdot(jnp.asarray(wd), jnp.tril(x.T @ x)))(a)
    assert _rel(g, g_oracle) < 1e-4


# ---------------------------------------------------------------------------
# Matmul VJP through the schedule executor (transposes folded into the
# index maps — no a^t / b^t copies).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("mkn,block,levels", [
    ((64, 64, 64), 16, 1), ((257, 64, 511), 128, 1),
    ((33, 17, 9), 8, 2), ((24, 40, 32), 8, 0),
])
def test_fused_matmul_grads_match_reference(dtype, tol, mkn, block, levels):
    m, k, n = mkn
    a = jax.random.normal(jax.random.PRNGKey(10), (m, k)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(11), (k, n)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(12), (m, n), jnp.float32)

    def loss(x, y, mode):
        c = strassen_matmul(x, y, levels=levels, leaf=16, mode=mode,
                            block=block, interpret=True,
                            out_dtype=jnp.float32)
        return jnp.vdot(w, c)

    gaf, gbf = jax.grad(lambda x, y: loss(x, y, "fused"), (0, 1))(a, b)
    gar, gbr = jax.grad(lambda x, y: loss(x, y, "reference"), (0, 1))(a, b)
    assert gaf.dtype == a.dtype and gbf.dtype == b.dtype
    assert _rel(gaf, gar) < tol
    assert _rel(gbf, gbr) < tol


def test_fused_ata_grad_diagonal_factor():
    """The VJP's S + S^t doubles the tril cotangent's diagonal — exactly
    the quadratic form's derivative; pin it against the dense oracle
    d/dA vdot(W, tril(A^tA)) computed by autodiff of the jnp expression."""
    a = jax.random.normal(jax.random.PRNGKey(5), (24, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(6), (16, 16), jnp.float32)

    g_fused = jax.grad(lambda x: jnp.vdot(w, ata(
        x, levels=1, leaf=8, mode="fused", block=8, interpret=True,
        out_dtype=jnp.float32)))(a)
    g_oracle = jax.grad(lambda x: jnp.vdot(w, jnp.tril(x.T @ x)))(a)
    assert _rel(g_fused, g_oracle) < 1e-4


# ---------------------------------------------------------------------------
# Acceptance: 512x512 fp32 grad parity <= 1e-5 for the dense, packed and
# streamed entry points; backward HBM model >= 2x under the dense baseline
# at 4096^2 with no dense n^2 cotangent buffer.
# ---------------------------------------------------------------------------

def test_acceptance_512_grad_parity_all_entry_points():
    n = 512
    a = jax.random.normal(jax.random.PRNGKey(20), (n, n), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(21), (n, n), jnp.float32)

    # dense entry
    g_fused = jax.grad(lambda x: jnp.vdot(w, ata(
        x, levels=2, mode="fused", block=128, interpret=True)))(a)
    g_ref = jax.grad(lambda x: jnp.vdot(w, ata(
        x, levels=2, leaf=64, mode="reference")))(a)
    assert _rel(g_fused, g_ref) < 1e-5

    # packed entry: same cotangent expressed on the packed stack
    wp = pack_tril_blocks(jnp.tril(w), 128)

    def loss_packed(x):
        p, _ = fused_ata_packed(x, levels=2, bk=128, bn=128,
                                interpret=True)
        return jnp.vdot(wp, p)

    g_packed = jax.grad(loss_packed)(a)
    assert _rel(g_packed, g_ref) < 1e-5

    # streamed entry
    from repro import gram
    wv = jnp.asarray(np.asarray(w)[np.tril_indices(n)])

    def loss_stream(x):
        st = gram.stream_init(n)
        st = gram.stream_update(st, x, levels=2, leaf=64, mode="fused",
                                block=128, interpret=True)
        return jnp.vdot(wv, st.packed)

    g_stream = jax.grad(loss_stream)(a)
    wd = np.zeros((n, n), np.float32)
    wd[np.tril_indices(n)] = np.asarray(wv)
    g_stream_ref = jax.grad(lambda x: jnp.vdot(
        jnp.asarray(wd), ata(x, levels=2, leaf=64, mode="reference")))(a)
    assert _rel(g_stream, g_stream_ref) < 1e-5


def test_acceptance_bwd_traffic_4096():
    """The backward of a 4096^2 Gram: the fused symm kernel moves >= 2x
    less HBM-materialized intermediate than the dense-dot baseline, and
    the packed path has NO dense n^2 cotangent buffer at all."""
    model = ata_bwd_traffic_model(4096, 4096, levels=2, bk=256, bn=256,
                                  cotangent="dense")
    fused_b = model["intermediate_bytes"]
    dense_b = model["dense_baseline"]["intermediate_bytes"]
    assert dense_b >= 2 * fused_b > 0, (dense_b, fused_b)
    # the only fused temporary is the packed stack — strictly below one
    # dense square
    assert fused_b <= model["packed_stack_bytes"] < 4096 * 4096 * 4
    # packed-cotangent entry: zero intermediates (shape is tile-aligned)
    packed = ata_bwd_traffic_model(4096, 4096, levels=2, bk=256, bn=256,
                                   cotangent="packed")
    assert packed["intermediate_bytes"] == 0
    assert packed["intermediate_ratio_dense_over_fused"] is None
    # the model is a real model: write term is exactly dA, its steps are
    # the real contributions of every output tile's destination, and its
    # padded grid covers the whole contribution sweep
    assert model["write_bytes"] == 4096 * 4096 * 4
    from repro.core.schedule import plan_symm as _ps
    plan = _ps(model["levels"], "strassen")
    T = 4096 // 256
    q = T // plan.blocks
    grid = (4096 // 256) * T * plan.max_contributions * q
    assert model["padded_grid_steps"] == grid
    tiles_per_dest = (4096 // 256) * T // plan.n_dests()
    n_contribs = sum(len(cs) for cs in plan.by_dest().values())
    assert model["grid_steps"] == tiles_per_dest * n_contribs * q
    assert model["grid_steps"] < grid
