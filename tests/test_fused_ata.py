"""Fused leaf-task pipeline: schedule + Pallas kernel (interpret mode).

Covers the acceptance criteria of the fused-pipeline PR:
  * numerical parity of the fused path with tril(a.T @ a) across odd /
    rectangular shapes, bf16 and fp32, levels 0-3 (interpret mode on CPU);
  * fp32 parity vs the reference recursion at 512x512 within 1e-5;
  * schedule property: signed leaf contributions reproduce the operation
    and its exact multiplication count from core/cost_model;
  * HBM-materialized intermediates: reference recursion >= 2x the fused
    pipeline at levels=2.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import ata, ata_full, strassen_matmul
from repro.core.schedule import (
    plan_ata, plan_matmul, evaluate_ata_plan, evaluate_matmul_plan,
)
from repro.core.cost_model import ata_mults_exact, strassen_mults_exact
from repro.core.symmetry import unpack_tril_blocks
from repro.kernels.strassen_fused import (
    fused_ata, fused_ata_packed, fused_matmul, ata_traffic_model,
)
from repro.roofline.hlo_census import hbm_intermediate_census


def _rand(shape, dtype=jnp.float32, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def _oracle(a):
    af = np.asarray(a, np.float64)
    return np.tril(af.T @ af)


# ---------------------------------------------------------------------------
# Fused kernel parity (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n", [
    (16, 16), (32, 24), (24, 40), (64, 64), (57, 31),
])
@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_fused_ata_matches_oracle(m, n, levels):
    a = _rand((m, n), seed=levels + 1)
    got = fused_ata(a, levels=levels, bk=8, bn=8, interpret=True)
    want = _oracle(a)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(got, np.float64) - want).max() / scale < 1e-5
    assert np.abs(np.triu(np.asarray(got), 1)).max() == 0.0


@pytest.mark.parametrize("levels", [1, 2])
def test_fused_ata_odd_rectangular(levels):
    a = _rand((257, 511), seed=7)
    got = fused_ata(a, levels=levels, bk=64, bn=64, interpret=True)
    want = _oracle(a)
    scale = max(np.abs(want).max(), 1.0)
    assert got.shape == (511, 511)
    assert np.abs(np.asarray(got, np.float64) - want).max() / scale < 1e-5


@pytest.mark.parametrize("variant", ["strassen", "winograd", "classical"])
def test_fused_ata_variants(variant):
    a = _rand((48, 32), seed=9)
    got = fused_ata(a, levels=2, variant=variant, bk=8, bn=8, interpret=True)
    want = _oracle(a)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_fused_ata_bf16_accumulates_fp32():
    a = _rand((128, 64), dtype=jnp.bfloat16, seed=3)
    got = fused_ata(a, levels=2, bk=16, bn=16, interpret=True)
    assert got.dtype == jnp.float32   # promoted accumulation dtype
    want = _oracle(a.astype(jnp.float32))
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(got, np.float64) - want).max() / scale < 3e-2
    # explicit downcast knob
    got16 = fused_ata(a, levels=1, bk=16, bn=16, out_dtype=jnp.bfloat16,
                      interpret=True)
    assert got16.dtype == jnp.bfloat16


def test_fused_packed_layout_matches_syrk_convention():
    a = _rand((64, 32), seed=5)
    packed, n_pad = fused_ata_packed(a, levels=1, bk=16, bn=16,
                                     interpret=True)
    t = n_pad // 16
    assert packed.shape == (t * (t + 1) // 2 * 16, 16)
    dense = jnp.tril(unpack_tril_blocks(packed, n_pad, 16, symmetrize=False))
    np.testing.assert_allclose(np.asarray(dense)[:32, :32], _oracle(a),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(16, 16, 16), (33, 17, 9), (24, 40, 32)])
@pytest.mark.parametrize("levels", [0, 1, 2, 3])
@pytest.mark.parametrize("variant", ["strassen", "winograd"])
def test_fused_matmul_matches_dot(m, k, n, levels, variant):
    a, b = _rand((m, k), seed=1), _rand((k, n), seed=2)
    got = fused_matmul(a, b, levels=levels, variant=variant,
                       bm=8, bk=8, bn=8, interpret=True)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(got, np.float64) - want).max() / scale < 1e-5


# ---------------------------------------------------------------------------
# API integration: ata(..., mode=...) / strassen_matmul(..., mode=...)
# ---------------------------------------------------------------------------

def test_ata_mode_fused_equals_reference():
    a = _rand((96, 64), seed=11)
    fused = ata(a, levels=2, mode="fused", block=16, interpret=True)
    ref = ata(a, levels=2, leaf=16, mode="reference")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    full = ata_full(a, levels=1, mode="fused", block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(full).T,
                               rtol=0, atol=0)


def test_strassen_matmul_mode_fused():
    a, b = _rand((40, 24), seed=12), _rand((24, 56), seed=13)
    got = strassen_matmul(a, b, levels="auto", leaf=8, mode="fused",
                          block=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)


def test_fused_under_jit():
    a = _rand((64, 48), seed=14)
    f = jax.jit(lambda x: ata(x, levels=2, mode="fused", block=16,
                              interpret=True))
    np.testing.assert_allclose(np.asarray(f(a)),
                               np.asarray(ata(a, levels=2, leaf=16,
                                              mode="reference")),
                               rtol=1e-5, atol=1e-4)


def test_mode_validation():
    a = _rand((8, 8), seed=15)
    with pytest.raises(ValueError):
        ata(a, mode="bogus")
    # fused cannot honor leaf hooks — explicit request must fail loudly
    with pytest.raises(ValueError):
        ata(a, mode="fused", base_syrk=lambda x: x)
    with pytest.raises(ValueError):
        strassen_matmul(a, a, mode="fused", base_matmul=lambda x, y: x @ y)


def test_fused_ata_grad_matches_reference():
    """Dense fused path carries a custom VJP, so mode='auto'->fused on
    TPU keeps jax.grad working; check it against the reference grad."""
    a = _rand((48, 32), seed=21)
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(22), (32, 32)))
    def loss(fn):
        return lambda x: jnp.sum(fn(x) * g)
    fused = jax.grad(loss(lambda x: ata(
        x, levels=2, mode="fused", block=8, interpret=True)))(a)
    ref = jax.grad(loss(lambda x: ata(
        x, levels=2, leaf=8, mode="reference")))(a)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # and through ata_full (the shampoo/solver path)
    gf = jax.grad(lambda x: ata_full(x, levels=1, mode="fused", block=8,
                                     interpret=True).sum())(a)
    gr = jax.grad(lambda x: ata_full(x, levels=1, leaf=8,
                                     mode="reference").sum())(a)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-4, atol=1e-4)


def test_fused_matmul_grad():
    a, b = _rand((24, 16), seed=23), _rand((16, 8), seed=24)
    da, db = jax.grad(
        lambda x, y: strassen_matmul(x, y, levels=1, mode="fused", block=8,
                                     interpret=True).sum(),
        argnums=(0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(da),
                               np.ones((24, 8)) @ np.asarray(b).T,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db),
                               np.asarray(a).T @ np.ones((24, 8)),
                               rtol=1e-5, atol=1e-5)


def test_fused_fan_in_clamp():
    """Deep winograd plans exceed the VMEM operand budget; the executor
    must clamp rather than schedule 2*16 gathered tiles per step."""
    from repro.kernels.strassen_fused import _ata_geometry, MAX_OPERAND_TERMS
    geo = _ata_geometry(1 << 12, 1 << 12, 3, "winograd", 256, 256)
    assert geo["plan"].max_terms <= MAX_OPERAND_TERMS
    assert geo["levels"] < 3
    # strassen L3 fan-in (4) fits and is untouched
    geo = _ata_geometry(1 << 12, 1 << 12, 3, "strassen", 256, 256)
    assert geo["levels"] == 3
    # parity still holds where the clamp engages
    a = _rand((64, 64), seed=25)
    got = fused_ata(a, levels=3, variant="winograd", bk=8, bn=8,
                    interpret=True)
    np.testing.assert_allclose(np.asarray(got), _oracle(a),
                               rtol=1e-4, atol=1e-4)


def test_fan_in_clamp_warns_once_with_clamped_value():
    """The MAX_OPERAND_TERMS clamp used to silently shallow the schedule;
    it must warn (naming the clamped value), exactly once per distinct
    clamp, and the shallower plan must actually be used."""
    import warnings as _warnings
    from repro.kernels import strassen_fused as sf

    sf._CLAMP_WARNED.clear()
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        # shape alone allows 3+ levels (4096/256 tiles); winograd ATA L3
        # fan-in is 16 > MAX_OPERAND_TERMS -> clamp to 2 with a warning
        geo = sf._ata_geometry(1 << 12, 1 << 12, 3, "winograd", 256, 256)
        assert geo["levels"] == 2 < 3          # the shallower plan is used
        msgs = [str(w.message) for w in caught
                if "MAX_OPERAND_TERMS" in str(w.message)]
        assert len(msgs) == 1, msgs
        assert "levels=3" in msgs[0] and "clamped to levels=2" in msgs[0]
        # same clamp again -> no second warning
        sf._ata_geometry(1 << 12, 1 << 12, 3, "winograd", 256, 256)
        msgs = [str(w.message) for w in caught
                if "MAX_OPERAND_TERMS" in str(w.message)]
        assert len(msgs) == 1, msgs
    # shape-driven clamps stay silent (expected behaviour, not a surprise)
    sf._CLAMP_WARNED.clear()
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        sf._ata_geometry(128, 128, 3, "strassen", 256, 256)
        assert not [w for w in caught
                    if "MAX_OPERAND_TERMS" in str(w.message)]


def test_smem_table_clamp():
    """The scalar-prefetch tables live in SMEM: bb322 matmul at levels 3
    fits the operand fan-in but not the table budget, so it clamps; the
    defaults' deepest programs (ata/aat levels 3, dps levels 2, Strassen
    matmul/symm levels 3) keep their levels."""
    import warnings as _warnings
    from repro.core.leaf_ir import compile_program
    from repro.kernels import strassen_fused as sf

    prog = compile_program("matmul", 3, "bb322")
    assert prog.max_terms <= sf.MAX_OPERAND_TERMS
    assert sf._table_bytes(prog) > sf.SMEM_TABLE_BYTES
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")
        assert sf._fan_in_clamp("matmul", 3, "bb322") == 2
    for kind, levels, gram in [("ata", 3, "strassen"), ("aat", 3, "strassen"),
                               ("ata", 2, "dps"), ("rank_k", 3, "strassen"),
                               ("matmul", 3, "strassen"),
                               ("symm", 3, "strassen")]:
        assert sf._fan_in_clamp(kind, levels, "strassen", gram) == levels


def test_dimension_semantics_parity_interpret():
    """All three Pallas grids now declare dimension_semantics (output
    tiles "parallel", contribution/K sweeps "arbitrary") so TPU megacore
    can partition output tiles; results must be bit-for-bit unchanged in
    interpret mode."""
    from repro.kernels import ops

    a = _rand((96, 64), seed=31)
    want = _oracle(a)
    # syrk grid (parallel, arbitrary)
    got = ops.syrk(a, bk=16, bn=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # fused-ATA grid (parallel, arbitrary, arbitrary)
    got = fused_ata(a, levels=2, bk=16, bn=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # fused-matmul grid (parallel, parallel, arbitrary, arbitrary)
    b = _rand((64, 48), seed=32)
    got = fused_matmul(a, b, levels=2, bm=16, bk=16, bn=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_fused_level_clamp_avoids_empty_leaves():
    """Small inputs must not pad to 2^levels x block per dim: the unroll
    depth clamps so each leaf holds at least one tile of real data."""
    model = ata_traffic_model(128, 128, levels=2, bk=256, bn=256)
    assert model["padded_shape"] == (256, 256)      # not (1024, 1024)
    a = _rand((128, 100), seed=16)
    got = ata(a, levels=2, mode="fused", block=256, interpret=True)
    assert got.shape == (100, 100)
    np.testing.assert_allclose(np.asarray(got), _oracle(a),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Schedule properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_schedule_mult_count_matches_cost_model(levels):
    """The schedule's signed leaf contributions sum to exactly the
    multiplication count of Algorithm 1 from core/cost_model (leaf=0 pins
    the cost recursion to the same fixed unroll depth)."""
    plan = plan_ata(levels, "strassen")
    B = plan.blocks
    for mb, nb in [(4, 4), (8, 4), (6, 10)]:
        assert plan.mult_count(mb, nb) == ata_mults_exact(
            mb * B, nb * B, leaf=0, levels=levels)
    mm = plan_matmul(levels, "strassen")
    assert mm.mult_count(8, 4, 6) == strassen_mults_exact(
        8 * B, 6 * B, 4 * B, leaf=0, levels=levels)
    # Strassen saves multiplications over classical from level 1 on
    if levels:
        cl = plan_matmul(levels, "classical")
        assert len(mm.products) == 7 ** levels < len(cl.products)


@pytest.mark.parametrize("levels", [1, 2, 3])
@pytest.mark.parametrize("variant", ["strassen", "winograd"])
def test_schedule_dense_evaluation(levels, variant):
    """Plans evaluated densely in numpy reproduce the operations — the
    schedule is correct independent of the Pallas executor."""
    rng = np.random.RandomState(levels)
    B = 1 << levels
    a = rng.randn(B * 3, B * 2)
    np.testing.assert_allclose(
        evaluate_ata_plan(plan_ata(levels, variant), a),
        np.tril(a.T @ a), rtol=1e-9, atol=1e-9)
    b = rng.randn(B * 2, B * 4)
    np.testing.assert_allclose(
        evaluate_matmul_plan(plan_matmul(levels, variant), a, b),
        a @ b, rtol=1e-9, atol=1e-9)


def test_schedule_destinations_lower_triangular():
    for levels in range(4):
        plan = plan_ata(levels)
        for p in plan.products:
            for di, dj, *_ in p.dests:
                assert di >= dj, "upper-triangular destination scheduled"
        # every lower-triangular leaf destination is covered
        B = plan.blocks
        assert set(plan.by_dest()) == {
            (i, j) for i in range(B) for j in range(i + 1)}


# ---------------------------------------------------------------------------
# Acceptance: 512x512 parity at 1e-5 + HBM intermediate ratio >= 2x
# ---------------------------------------------------------------------------

def test_acceptance_512_parity_and_hbm_ratio():
    a = _rand((512, 512), seed=20)
    fused = fused_ata(a, levels=2, bk=128, bn=128, interpret=True)
    ref = ata(a, levels=2, leaf=64, mode="reference")
    want = _oracle(a)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(fused, np.float64) - want).max() / scale < 1e-5
    assert np.abs(np.asarray(ref, np.float64) - want).max() / scale < 1e-5

    # reference recursion materializes operand sums / M_i / pad+concat
    # copies in HBM (visible in its compiled HLO); the fused kernel's only
    # HBM temporaries are pad copies (here: none — shape is tile-aligned).
    ref_hlo = jax.jit(
        lambda x: ata(x, levels=2, leaf=64, mode="reference")
    ).lower(a).compile().as_text()
    ref_bytes = hbm_intermediate_census(ref_hlo)["total_bytes"]
    model = ata_traffic_model(512, 512, levels=2, bk=128, bn=128)
    fused_bytes = model["intermediate_bytes"]
    assert ref_bytes >= 2 * fused_bytes and ref_bytes > 1_000_000, (
        ref_bytes, fused_bytes)
    # the analytic side must be a real model, not a constant: its write
    # term is exactly the packed output, its read term covers the padded
    # contribution sweep, and misaligned shapes surface the pad copy.
    t = 512 // 128
    n_tri = t * (t + 1) // 2
    assert model["write_bytes"] == n_tri * 128 * 128 * 4
    plan = plan_ata(2, "strassen")
    assert model["grid_steps"] == n_tri * plan.max_contributions * 1
    assert model["read_bytes"] == (model["grid_steps"] * 2 * plan.max_terms
                                   * 128 * 128 * 4)
    misaligned = ata_traffic_model(257, 511, levels=2, bk=64, bn=64)
    assert misaligned["padded_shape"] == (512, 512)
    assert misaligned["intermediate_bytes"] == 512 * 512 * 4
