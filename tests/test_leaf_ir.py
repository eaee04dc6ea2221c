"""The leaf-program IR (core/leaf_ir.py): algebra registry, compiler
counts vs the cost-model closed forms, the numpy interpreter vs dense
oracles, and the fused executor parity of the two NEW capabilities the IR
bought — the aat (A A^t) row gram and the accumulating rank-k update —
including the PR acceptance bounds (512^2 fp32 <= 1e-5; bf16 levels 0-3).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import ata
from repro.core.cost_model import (aat_mults_exact, ata_mults_exact,
                                   ir_leaf_count, ir_max_terms,
                                   symm_leaf_count)
from repro.core.leaf_ir import (PROGRAM_KINDS, algebra_dims,
                                compile_program, get_algebra,
                                get_gram_algebra, interpret_program,
                                register_algebra, register_gram_algebra,
                                registered_algebras,
                                registered_gram_algebras)
from repro.gram import stream
from repro.kernels import ops
from repro.kernels.strassen_fused import (
    aat_traffic_model, fused_aat, fused_aat_packed, fused_ata_packed,
    fused_rank_k_update, rank_k_traffic_model,
)


def _rand(shape, dtype=jnp.float32, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_ships_three_algebras():
    assert set(registered_algebras()) >= {"strassen", "winograd",
                                          "classical"}
    assert len(get_algebra("strassen")) == 7
    assert len(get_algebra("classical")) == 8
    with pytest.raises(ValueError):
        get_algebra("nope")
    with pytest.raises(ValueError):
        register_algebra("strassen", get_algebra("strassen"))  # duplicate
    with pytest.raises(ValueError):
        register_algebra("bad", ((((0, 0, 2),), ((0, 0, 1),),
                                  ((0, 0, 1),)),))              # bad sign


def test_registering_a_new_algebra_compiles_and_evaluates():
    """A new variant is one register_algebra call: the 2x2 classical
    table under a fresh name compiles every kind and matches the oracle
    through the interpreter — variants are data, not code."""
    name = "classical-copy-test"
    if name not in registered_algebras():
        register_algebra(name, get_algebra("classical"))
    rng = np.random.RandomState(0)
    a = rng.randn(8, 4)
    got = interpret_program(compile_program("ata", 2, name), a)
    np.testing.assert_allclose(got, np.tril(a.T @ a), atol=1e-9)
    got = interpret_program(compile_program("aat", 1, name), a)
    np.testing.assert_allclose(got, np.tril(a @ a.T), atol=1e-9)


def test_fused_matmul_both_trans_forward_and_grads():
    """C = a^t b^t with BOTH transposes folded into the index maps, and
    its fused VJP (regression: the two-flag case routed through the
    single-flag branch and returned wrong gradients)."""
    from repro.kernels.strassen_fused import fused_matmul
    a = _rand((40, 16), seed=31)          # stored (k, m)
    b = _rand((24, 40), seed=32)          # stored (n, k)
    out = fused_matmul(a, b, levels=1, bm=8, bk=8, bn=8, trans_a=True,
                       trans_b=True, interpret=True)
    want = np.asarray(a, np.float64).T @ np.asarray(b, np.float64).T
    assert np.abs(np.asarray(out, np.float64) - want).max() < 1e-4
    da, db = jax.grad(
        lambda p, q: fused_matmul(p, q, levels=1, bm=8, bk=8, bn=8,
                                  trans_a=True, trans_b=True,
                                  interpret=True).sum(),
        argnums=(0, 1))(a, b)
    g = np.ones((16, 24))
    wa = np.asarray(b, np.float64).T @ g.T       # dA = B^t g^t, (k, m)
    wb = g.T @ np.asarray(a, np.float64).T       # dB = g^t A^t, (n, k)
    np.testing.assert_allclose(np.asarray(da), wa, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(db), wb, rtol=1e-4, atol=1e-4)


def test_reregistration_invalidates_executor_tables():
    """register_algebra(overwrite=True) must clear the executor's lowered
    scalar-prefetch tables, not just the program cache — a stale table
    would make the kernel silently run the OLD algebra."""
    from repro.kernels.strassen_fused import _program_tables, fused_matmul
    a = _rand((8, 8), seed=33)
    _ = fused_matmul(a, a, levels=1, bm=8, bk=8, bn=8, interpret=True)
    assert _program_tables.cache_info().currsize > 0
    register_algebra("strassen", get_algebra("strassen"), overwrite=True)
    assert _program_tables.cache_info().currsize == 0


def test_unknown_kind_and_bad_trans_rejected():
    with pytest.raises(ValueError):
        compile_program("gemm", 1)
    with pytest.raises(ValueError):
        compile_program("ata", 1, trans_a=True)
    with pytest.raises(ValueError):
        compile_program("matmul", -1)


# ---------------------------------------------------------------------------
# Counts + interpreter vs closed forms / oracles.  The exhaustive sweep
# runs unconditionally; the hypothesis property (random leaf shapes over
# the same space) adds fuzzed coverage where hypothesis is installed.
# ---------------------------------------------------------------------------

def _check_counts_and_interpreter(kind, variant, levels, mb, nb,
                                  gram="strassen"):
    """Compiled LeafProgram leaf/term counts == cost-model closed forms;
    numpy interpreter == dense oracle."""
    prog = compile_program(kind, levels, variant, gram=gram)
    assert len(prog.ops) == ir_leaf_count(kind, levels, variant, gram=gram)
    assert prog.max_terms == ir_max_terms(kind, levels, variant, gram=gram)
    Bm, Bk, Bn = prog.blocks_m, prog.blocks_k, prog.blocks_n
    # gram kinds: mult_count ties to the recursion closed forms too
    # (ata_mults_exact models the paper's 7-product HASA — the 8-product
    # classical table and the dps gram recursion deliberately differ)
    if variant in ("strassen", "winograd") and gram == "strassen":
        if kind in ("ata", "rank_k"):
            assert prog.mult_count(mb, nb) == ata_mults_exact(
                mb * Bm, nb * Bn, leaf=0, levels=levels)
        elif kind == "aat":
            assert prog.mult_count(mb, nb) == aat_mults_exact(
                mb * Bm, nb * Bn, leaf=0, levels=levels)

    rng = np.random.RandomState(levels * 7 + mb)
    if kind in ("ata", "rank_k"):
        a = rng.randn(Bm * mb, Bn * nb)
        c0 = (np.tril(rng.randn(Bn * nb, Bn * nb))
              if kind == "rank_k" else None)
        got = interpret_program(prog, a, c0=c0)
        want = np.tril(a.T @ a) + (c0 if c0 is not None else 0.0)
    elif kind == "aat":
        a = rng.randn(Bm * mb, Bn * nb)
        got = interpret_program(prog, a)
        want = np.tril(a @ a.T)
    elif kind == "matmul":
        a = rng.randn(Bm * mb, Bk * nb)
        b = rng.randn(Bk * nb, Bn * mb)
        got = interpret_program(prog, a, b)
        want = a @ b
    else:                                   # symm
        a = rng.randn(Bm * mb, Bn * nb)
        s = rng.randn(Bn * nb, Bn * nb)
        got = interpret_program(prog, a, s)
        want = a @ (np.tril(s) + np.tril(s, -1).T)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


def _kind_variant_grid():
    """(kind, variant, gram) combos the compiler accepts, enumerated
    from the LIVE registries — a newly registered algebra or gram table
    is automatically swept (the satellite's dynamic parametrization)."""
    out = []
    for kind in PROGRAM_KINDS:
        for v in registered_algebras():
            dm, dk, dn = algebra_dims(v)
            if kind in ("ata", "aat", "rank_k"):
                if (dm, dk, dn) != (2, 2, 2):
                    continue            # gram table expansion needs 2x2x2
                out.extend((kind, v, g)
                           for g in registered_gram_algebras())
            elif kind == "symm":
                if dk != dn:
                    continue            # Sym operand splits k like n
                out.append((kind, v, "strassen"))
            else:
                out.append((kind, v, "strassen"))
    return out


@pytest.mark.parametrize("kind,variant,gram", _kind_variant_grid())
def test_program_counts_and_interpreter_match(kind, variant, gram):
    """Every registered algebra/gram x kind x levels 0-3 (the
    satellite's exhaustive grid at fixed leaf shape)."""
    # rect tables fan out fast (bb422 symm @ 4 = 14^4 ops) — depth 3 is
    # plenty for them
    depth = 4 if max(algebra_dims(variant)) == 2 else 3
    for levels in range(depth):
        _check_counts_and_interpreter(kind, variant, levels, 3, 2,
                                      gram=gram)


def test_gram_programs_cover_lower_triangle_exactly():
    """Every gram-kind destination satisfies di >= dj and the programs
    cover each lower-triangular leaf destination — for every registered
    square variant x gram algebra."""
    variants = [v for v in registered_algebras()
                if algebra_dims(v) == (2, 2, 2)]
    for variant in variants:
        for gram in registered_gram_algebras():
            for levels in range(4):
                for kind in ("ata", "aat", "rank_k"):
                    prog = compile_program(kind, levels, variant,
                                           gram=gram)
                    B = prog.blocks
                    for p in prog.ops:
                        for di, dj, *_ in p.dests:
                            assert di >= dj, (kind,
                                              "upper-triangular "
                                              "destination")
                    assert set(prog.by_dest()) == {
                        (i, j) for i in range(B) for j in range(i + 1)}


try:
    from hypothesis import given, settings, strategies as st, HealthCheck
    _HAVE_HYPOTHESIS = True
except ImportError:                              # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    SET = dict(deadline=None, max_examples=40,
               suppress_health_check=[HealthCheck.too_slow])

    @given(st.sampled_from(_kind_variant_grid()),
           st.integers(0, 3), st.integers(1, 3), st.integers(1, 3))
    @settings(**SET)
    def test_program_counts_and_interpreter_property(kvg, levels, mb, nb):
        """Fuzzed leaf shapes over the same algebra x kind x levels
        space (the satellite's hypothesis property)."""
        kind, variant, gram = kvg
        _check_counts_and_interpreter(kind, variant, levels, mb, nb,
                                      gram=gram)


# ---------------------------------------------------------------------------
# Fused executor parity: aat
# ---------------------------------------------------------------------------

def _aat_oracle(a):
    af = np.asarray(a, np.float64)
    return np.tril(af @ af.T)


@pytest.mark.parametrize("m,n", [(16, 16), (32, 24), (24, 40), (57, 31)])
@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_fused_aat_matches_oracle(m, n, levels):
    a = _rand((m, n), seed=levels + 1)
    got = fused_aat(a, levels=levels, bm=8, bk=8, interpret=True)
    want = _aat_oracle(a)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(got, np.float64) - want).max() / scale < 1e-5
    assert np.abs(np.triu(np.asarray(got), 1)).max() == 0.0


@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_fused_aat_bf16(levels):
    a = _rand((48, 40), jnp.bfloat16, seed=levels)
    got = np.asarray(fused_aat(a, levels=levels, bm=8, bk=8,
                               interpret=True), np.float64)
    want = _aat_oracle(a.astype(jnp.float32))
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() / scale < 2e-2     # bf16 operand noise


def test_fused_aat_packed_layout_and_gram_of_api():
    a = _rand((40, 24), seed=3)
    packed, m_pad = fused_aat_packed(a, levels=1, bm=8, bk=8,
                                     interpret=True)
    t = m_pad // 8
    assert packed.shape == (t * (t + 1) // 2 * 8, 8)
    # the public surface: ata(x, gram_of="rows") in both modes
    got_f = ata(a, gram_of="rows", levels=1, mode="fused", block=8,
                interpret=True)
    got_r = ata(a, gram_of="rows", levels=1, leaf=8, mode="reference")
    want = _aat_oracle(a)
    assert np.abs(np.asarray(got_f, np.float64) - want).max() < 1e-4
    assert np.abs(np.asarray(got_r, np.float64) - want).max() < 1e-4


def test_fused_aat_grad_matches_dense():
    a = _rand((24, 16), seed=5)
    g = jax.grad(lambda x: fused_aat(x, levels=1, bm=8, bk=8,
                                     interpret=True).sum())(a)
    # dA = (S + S^t) A with S = tril(ones)
    s = np.tril(np.ones((24, 24)))
    want = (s + s.T) @ np.asarray(a, np.float64)
    np.testing.assert_allclose(np.asarray(g, np.float64), want,
                               rtol=1e-5, atol=1e-5)


def test_acceptance_aat_512_parity():
    """PR acceptance: fused-vs-dense parity <= 1e-5 at 512^2 fp32 for the
    row gram."""
    a = _rand((512, 512), seed=21)
    got = fused_aat(a, levels=2, bm=128, bk=128, interpret=True)
    want = _aat_oracle(a)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(got, np.float64) - want).max() / scale < 1e-5


# ---------------------------------------------------------------------------
# Fused executor parity: rank_k (accumulating update)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_rank_k_chunked_equals_one_shot(levels):
    a = _rand((96, 64), seed=levels)
    stack, _ = fused_ata_packed(a[:40], levels=levels, bk=8, bn=8,
                                interpret=True)
    for chunk in (a[40:41], a[41:96]):
        stack = fused_rank_k_update(stack, chunk, levels=levels, bk=8,
                                    interpret=True)
    one, _ = fused_ata_packed(a, levels=levels, bk=8, bn=8, interpret=True)
    np.testing.assert_allclose(np.asarray(stack), np.asarray(one),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_rank_k_bf16_chunks(levels):
    a = _rand((64, 32), jnp.bfloat16, seed=levels + 9)
    st_ = stream.stack_init(32, block=8)
    for chunk in (a[:30], a[30:]):
        st_ = stream.stack_update(st_, chunk, levels=levels, block=8,
                                  interpret=True)
    got = np.asarray(stream.stack_finalize(st_, 32, symmetrize=False),
                     np.float64)
    a64 = np.asarray(a.astype(jnp.float32), np.float64)
    want = np.tril(a64.T @ a64)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() / scale < 2e-2


def test_acceptance_rank_k_512_parity():
    """PR acceptance: the accumulating update at 512^2 fp32 within 1e-5
    of the dense oracle (two chunks through the packed state)."""
    a = _rand((512, 512), seed=22)
    st_ = stream.stack_init(512, block=128)
    st_ = stream.stack_update(st_, a[:256], levels=2, block=128,
                              interpret=True)
    st_ = stream.stack_update(st_, a[256:], levels=2, block=128,
                              interpret=True)
    got = np.asarray(stream.stack_finalize(st_, 512, symmetrize=False),
                     np.float64)
    a64 = np.asarray(a, np.float64)
    want = np.tril(a64.T @ a64)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() / scale < 1e-5
    assert int(st_.rows) == 512


def test_rank_k_ragged_chunk_and_level_clamp():
    """Chunks narrower than the stack span are zero-padded (exact) and
    levels clamp to depths the fixed stack layout divides."""
    st_ = stream.stack_init(24, block=8)          # T = 3 tiles
    a = _rand((20, 24), seed=7)
    # T=3 is not divisible by 2^levels for levels>0 -> clamps to 0
    st_ = stream.stack_update(st_, a[:11], levels=2, block=8,
                              interpret=True)
    st_ = stream.stack_update(st_, a[11:], levels=2, block=8,
                              interpret=True)
    got = np.asarray(stream.stack_finalize(st_, 24, symmetrize=False))
    a64 = np.asarray(a, np.float64)
    np.testing.assert_allclose(got, np.tril(a64.T @ a64),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        stream.stack_update(st_, _rand((4, 40), seed=1), block=8)


def test_rank_k_streamed_grad_is_dense_free_capable():
    """jax.grad flows through a stacked streamed update (packed
    cotangent pass-through + symm backward)."""
    a = _rand((24, 16), seed=11)

    def loss(x):
        st_ = stream.stack_init(16, block=8)
        st_ = stream.stack_update(st_, x, levels=1, block=8,
                                  interpret=True)
        return st_.stack.sum()

    g = np.asarray(jax.grad(loss)(a), np.float64)
    # oracle: d sum(stack)/dA — stack holds tril blocks with FULL
    # diagonal tiles, so the cotangent S is block-lower with full diags
    a64 = np.asarray(a, np.float64)
    s = np.zeros((16, 16))
    for i in range(2):
        for j in range(i + 1):
            s[i * 8:(i + 1) * 8, j * 8:(j + 1) * 8] = 1.0
    want = a64 @ (s + s.T)
    np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# IR-driven traffic models for the new kinds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", PROGRAM_KINDS)
@pytest.mark.parametrize("levels", [1, 2, 3])
def test_count_table_holds_each_rows_real_prefix(kind, levels):
    """The pipelined kernel walks only what the count table calls real:
    per destination its contributions, per contribution its left and
    right terms.  Real entries lead every table row, so the counts are
    prefix lengths and every slot behind them is padding."""
    from repro.kernels.strassen_fused import _program_tables
    prog = compile_program(kind, levels, "strassen")
    n_c, tmax = prog.max_contributions, prog.max_terms
    sign, _, lsgn, _, rsgn, counts = _program_tables(kind, levels,
                                                     "strassen")
    sign = sign.reshape(-1, n_c)
    lsgn, rsgn = lsgn.reshape(-1, n_c, tmax), rsgn.reshape(-1, n_c, tmax)
    counts = counts.reshape(-1, n_c + 1)
    assert counts.shape[0] == prog.n_dests()
    walked = 0
    for (di, dj), contribs in prog.by_dest().items():
        ld = prog.dest_index(di, dj)
        assert counts[ld, 0] == len(contribs)
        assert not sign[ld, len(contribs):].any()
        assert not counts[ld, 1 + len(contribs):].any()
        for c, contrib in enumerate(contribs):
            n_left, n_right = counts[ld, 1 + c] >> 16, counts[ld, 1 + c] \
                & 0xFFFF
            assert (n_left, n_right) == (len(contrib.left),
                                         len(contrib.right))
            assert sign[ld, c] != 0
            assert lsgn[ld, c, :n_left].all() and rsgn[ld, c, :n_right].all()
            assert not lsgn[ld, c, n_left:].any()
            assert not rsgn[ld, c, n_right:].any()
        walked += counts[ld, 0]
    assert walked == counts[:, 0].sum() == len(prog.contributions())


@pytest.mark.parametrize("kind", PROGRAM_KINDS)
def test_table_bytes_count_every_lowered_word(kind):
    """``_table_bytes`` (the SMEM guard of the fan-in clamp) counts the
    count table too, and the deepest programs still fit SMEM."""
    from repro.kernels.strassen_fused import (SMEM_TABLE_BYTES,
                                              _program_tables, _table_bytes)
    prog = compile_program(kind, 3, "strassen")
    tables = _program_tables(kind, 3, "strassen")
    assert _table_bytes(prog) == sum(t.nbytes for t in tables)
    assert _table_bytes(prog) <= SMEM_TABLE_BYTES


def test_aat_traffic_model_is_real():
    prog = compile_program("aat", 2, "strassen")
    t = aat_traffic_model(512, 512, levels=2, bm=128, bk=128)
    n_tri = 4 * 5 // 2
    tile = 128 * 128 * 4
    assert t["write_bytes"] == n_tri * tile
    # one output tile per leaf destination: the pipelined kernel walks
    # each destination's real contributions and fetches their real terms
    contribs = [c for cs in prog.by_dest().values() for c in cs]
    fetches = sum(len(c.left) + len(c.right) for c in contribs)
    assert t["grid_steps"] == len(contribs) * 1
    assert t["read_bytes"] == fetches * tile
    assert t["padded_grid_steps"] == n_tri * prog.max_contributions * 1
    assert t["padded_read_bytes"] == (t["padded_grid_steps"] * 2
                                      * prog.max_terms * tile)
    assert t["skipped_fetch_share"] == pytest.approx(
        1 - fetches / (t["padded_grid_steps"] * 2 * prog.max_terms))
    assert t["intermediate_bytes"] == 0
    mis = aat_traffic_model(257, 511, levels=2, bm=64, bk=64)
    assert mis["padded_shape"] == (512, 512)
    assert mis["intermediate_bytes"] == 512 * 512 * 4


def test_rank_k_traffic_beats_streamed_baseline():
    """The accumulating kernel reads the state once and writes it once;
    the status-quo streamed update additionally materializes, re-reads
    and re-writes the delta stack — the model must show the saving."""
    t = rank_k_traffic_model(4096, 1024, levels=2, bk=256, bn=256)
    fused = t["read_bytes"] + t["write_bytes"] + t["intermediate_bytes"]
    base = (t["baseline"]["read_bytes"] + t["baseline"]["write_bytes"]
            + t["baseline"]["intermediate_bytes"])
    assert base > fused
    assert t["baseline"]["intermediate_bytes"] >= t["state_bytes"]
    assert t["intermediate_bytes"] == 0     # aligned shape, no pad copy


# ---------------------------------------------------------------------------
# ops-level consumers
# ---------------------------------------------------------------------------

def test_ops_rank_k_update_jit_donation_roundtrip():
    a = _rand((32, 16), seed=13)
    t = 2
    stack = jnp.zeros((t * (t + 1) // 2 * 8, 8), jnp.float32)
    out = ops.rank_k_update(stack, a, levels=1, bk=8, interpret=True)
    one, _ = fused_ata_packed(a, levels=1, bk=8, bn=8,
                              out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(one),
                               rtol=1e-5, atol=1e-5)


def test_ops_aat_fused_entry_points():
    a = _rand((40, 24), seed=14)
    want = _aat_oracle(a)
    got = np.asarray(ops.aat_fused(a, levels=1, bm=8, bk=8,
                                   interpret=True), np.float64)
    assert np.abs(got - want).max() < 1e-4
    packed = ops.aat_fused_packed(a, levels=1, bm=8, bk=8, interpret=True)
    assert packed.ndim == 2 and packed.shape[1] == 8


# ---------------------------------------------------------------------------
# The DPS gram algebra: counts below strassen-gram, fused parity
# ---------------------------------------------------------------------------

def test_dps_leaf_counts_beat_strassen_gram():
    """G(l) = 2 G(l-1) + 3 t^(l-1) vs the paper's 4 G(l-1) + 2 t^(l-1):
    the dps scheme does strictly fewer leaf products at every level > 0,
    and the compiled programs realize exactly the closed forms."""
    dps_want = (1, 5, 31, 209)
    str_want = (1, 6, 38, 250)
    for lv in range(4):
        dps = ir_leaf_count("ata", lv, "strassen", gram="dps")
        base = ir_leaf_count("ata", lv, "strassen", gram="strassen")
        assert dps == dps_want[lv]
        assert base == str_want[lv]
        if lv > 0:
            assert dps < base
        assert len(compile_program("ata", lv, gram="dps").ops) == dps


def test_dps_interpreter_and_mult_count():
    """The dps program is exact (rational coefficients survive the IR)
    and its scalar mult count undercuts the strassen gram's at equal
    levels and leaf shape."""
    rng = np.random.RandomState(3)
    a = rng.randn(12, 8)
    prog = compile_program("ata", 2, gram="dps")
    np.testing.assert_allclose(interpret_program(prog, a),
                               np.tril(a.T @ a), rtol=1e-9, atol=1e-9)
    base = compile_program("ata", 2, gram="strassen")
    assert prog.mult_count(3, 2) < base.mult_count(3, 2)


def test_acceptance_dps_ata_512_parity():
    """PR acceptance: a registered DPS gram algebra through the fused
    executor — parity <= 1e-5 at 512^2 fp32."""
    a = _rand((512, 512), seed=23)
    got = ops.ata_fused(a, levels=2, gram="dps", bk=128, bn=128,
                        interpret=True)
    a64 = np.asarray(a, np.float64)
    want = np.tril(a64.T @ a64)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(got, np.float64) - want).max() / scale < 1e-5


@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_acceptance_dps_bf16_levels(levels):
    """PR acceptance: dps gram at bf16, levels 0-3 (level 3's 16-term
    operands exceed MAX_OPERAND_TERMS and clamp with a warning — the
    result must still be correct)."""
    a = _rand((64, 64), jnp.bfloat16, seed=levels + 40)
    got = np.asarray(ops.ata_fused(a, levels=levels, gram="dps", bk=8,
                                   bn=8, interpret=True), np.float64)
    a64 = np.asarray(a.astype(jnp.float32), np.float64)
    want = np.tril(a64.T @ a64)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() / scale < 2e-2


def test_dps_aat_and_rank_k_parity():
    """The same gram table drives the row gram and the accumulating
    update."""
    a = _rand((48, 32), seed=24)
    got = fused_aat(a, levels=2, variant="strassen", gram="dps", bm=8,
                    bk=8, interpret=True)
    assert np.abs(np.asarray(got, np.float64)
                  - _aat_oracle(a)).max() < 1e-4
    stack, _ = fused_ata_packed(a[:20], levels=1, gram="dps", bk=8, bn=8,
                                interpret=True)
    stack = fused_rank_k_update(stack, a[20:], levels=1, gram="dps", bk=8,
                                interpret=True)
    one, _ = fused_ata_packed(a, levels=1, gram="dps", bk=8, bn=8,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(stack), np.asarray(one),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Rectangular base cases through the fused matmul executor
# ---------------------------------------------------------------------------

def _matmul_oracle(a, b):
    return np.asarray(a, np.float64) @ np.asarray(b, np.float64)


def test_acceptance_bb322_matmul_512_parity():
    """PR acceptance: a <3, 2, 2>-style rectangular base case through
    compile_program AND the fused executor — parity <= 1e-5 at 512^2
    fp32."""
    from repro.kernels.strassen_fused import fused_matmul
    prog = compile_program("matmul", 2, "bb322")
    assert (prog.blocks_m, prog.blocks_k, prog.blocks_n) == (9, 4, 4)
    a = _rand((512, 512), seed=25)
    b = _rand((512, 512), seed=26)
    got = fused_matmul(a, b, levels=2, variant="bb322", bm=64, bk=64,
                       bn=64, interpret=True)
    want = _matmul_oracle(a, b)
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(np.asarray(got, np.float64) - want).max() / scale < 1e-5


@pytest.mark.parametrize("levels", [0, 1, 2, 3])
def test_bb322_matmul_bf16_levels(levels):
    from repro.kernels.strassen_fused import fused_matmul
    a = _rand((54, 16), jnp.bfloat16, seed=levels + 50)
    b = _rand((16, 16), jnp.bfloat16, seed=levels + 60)
    got = np.asarray(fused_matmul(a, b, levels=levels, variant="bb322",
                                  bm=2, bk=2, bn=2, interpret=True),
                     np.float64)
    want = _matmul_oracle(a.astype(jnp.float32), b.astype(jnp.float32))
    scale = max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() / scale < 2e-2


def test_bb422_matmul_parity_and_trans():
    from repro.kernels.strassen_fused import fused_matmul
    a = _rand((64, 32), seed=27)
    b = _rand((32, 16), seed=28)
    got = fused_matmul(a, b, levels=1, variant="bb422", bm=8, bk=8, bn=8,
                       interpret=True)
    assert np.abs(np.asarray(got, np.float64)
                  - _matmul_oracle(a, b)).max() < 1e-4
    # rect split + folded transpose compose
    got_t = fused_matmul(jnp.asarray(np.asarray(a).T), b, levels=1,
                         variant="bb422", bm=8, bk=8, bn=8, trans_a=True,
                         interpret=True)
    assert np.abs(np.asarray(got_t, np.float64)
                  - _matmul_oracle(a, b)).max() < 1e-4


# ---------------------------------------------------------------------------
# Satellite regressions: cost-model derivation, registration validation,
# per-instance caches
# ---------------------------------------------------------------------------

def test_symm_leaf_count_derived_from_registered_table():
    """symm_leaf_count must be t**levels of the ACTUAL registered table,
    not a hardcoded (8 if classical else 7)**levels — regression via a
    toy 6-product <6, 1, 1> classical split."""
    name = "toy-611-test"
    if name not in registered_algebras():
        # C[i, 0] = A[i, 0] * B[0, 0]: six scalar products, one per
        # output row — trivially correct, deliberately not 7 or 8 wide
        register_algebra(
            name,
            tuple((((i, 0, 1),), ((0, 0, 1),), ((i, 0, 1),))
                  for i in range(6)),
            dims=(6, 1, 1))
    for lv in range(3):
        want = 6 ** lv
        assert symm_leaf_count(lv, name) == want
        assert want not in (7 ** lv, 8 ** lv) or lv == 0
        # dk == dn == 1, so the symm kind compiles: the closed form must
        # match the program the executor would actually run
        assert len(compile_program("symm", lv, name).ops) == want
    assert symm_leaf_count(2, "classical") == 64
    assert symm_leaf_count(2, "strassen") == 49


def test_register_algebra_rejects_malformed_tables():
    """Empty tables/quad lists and malformed rows must fail with clear
    ValueErrors at registration, not crash mid-compile on tuple
    unpacking."""
    with pytest.raises(ValueError, match="non-empty"):
        register_algebra("bad-empty-test", ())
    with pytest.raises(ValueError, match="empty a_quads"):
        register_algebra("bad-equad-test",
                         (((), ((0, 0, 1),), ((0, 0, 1),)),))
    with pytest.raises(ValueError, match=r"\(a, b, dest\) triple"):
        register_algebra("bad-arity-test", ((((0, 0, 1),), ((0, 0, 1),)),))
    with pytest.raises(ValueError, match=r"\(row, col, coeff\)"):
        register_algebra("bad-quad-test",
                         ((((0, 0),), ((0, 0, 1),), ((0, 0, 1),)),))
    with pytest.raises(ValueError, match="nonzero finite real"):
        register_algebra("bad-coeff-test",
                         ((((0, 0, 0),), ((0, 0, 1),), ((0, 0, 1),)),))
    # structurally fine but algebraically wrong: the levels=1 numeric
    # identity smoke-check catches it at registration time
    with pytest.raises(ValueError, match="identity"):
        register_algebra(
            "bad-algebra-test",
            tuple((((i, j, 1),), ((j, kq, 1),), ((i, kq, 2),))
                  for i in range(2) for j in range(2) for kq in range(2)))
    for n in ("bad-empty-test", "bad-equad-test", "bad-arity-test",
              "bad-quad-test", "bad-coeff-test", "bad-algebra-test"):
        assert n not in registered_algebras()


def test_register_gram_algebra_validation():
    base = get_gram_algebra("strassen")
    with pytest.raises(ValueError, match="already registered"):
        register_gram_algebra("strassen", **base)
    with pytest.raises(ValueError, match="empty term list"):
        register_gram_algebra("bad-gram-test",
                              sym=(((), ((0, 0, 1, 0),)),), mm=base["mm"])
    with pytest.raises(ValueError, match=r"\(g, o, coeff\)"):
        register_gram_algebra("bad-gram-test",
                              sym=((((0, 0),), ((0, 0, 1, 0),)),),
                              mm=base["mm"])
    with pytest.raises(ValueError, match=r"\(di, dj, coeff, trans\)"):
        register_gram_algebra("bad-gram-test",
                              sym=((((0, 0, 1),), ((0, 0, 1),)),),
                              mm=base["mm"])
    with pytest.raises(ValueError, match="lower triangle"):
        register_gram_algebra("bad-gram-test",
                              sym=((((0, 0, 1),), ((0, 1, 1, 0),)),),
                              mm=base["mm"])
    with pytest.raises(ValueError, match="sym dest"):
        register_gram_algebra("bad-gram-test",
                              sym=((((0, 0, 1),), ((1, 0, 1, 1),)),),
                              mm=base["mm"])
    with pytest.raises(ValueError, match="at least one mm"):
        register_gram_algebra("bad-gram-test", sym=base["sym"], mm=())
    with pytest.raises(ValueError, match="at least one sym"):
        register_gram_algebra("bad-gram-test", sym=(), mm=base["mm"])
    # structurally valid, numerically wrong (C11 doubled)
    wrong_sym = ((((0, 0, 2),), ((0, 0, 1, 0),)),) + base["sym"][1:]
    with pytest.raises(ValueError, match="identity"):
        register_gram_algebra("bad-gram-test", sym=wrong_sym,
                              mm=base["mm"])
    assert "bad-gram-test" not in registered_gram_algebras()


def test_program_caches_die_with_program():
    """contributions()/by_dest() memoize per instance — a module-level
    lru_cache keyed on the program would pin every program ever compiled
    for process lifetime (regression: autotune sweeps compile many)."""
    import dataclasses
    import gc
    import weakref
    # dataclasses.replace with a fresh _cache gives an instance the
    # compile_program lru_cache does NOT hold
    prog = dataclasses.replace(compile_program("ata", 2), _cache={})
    assert prog.contributions() and prog.by_dest()
    assert "contributions" in prog._cache and "by_dest" in prog._cache
    ref = weakref.ref(prog)
    del prog
    gc.collect()
    assert ref() is None, "program (and its memoized tables) leaked"
