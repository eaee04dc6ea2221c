"""Distributed-gram communication cost model (core.cost_model):
per-scheme wire bytes / message rounds / flops and the ranking that
drives ``distributed_gram(scheme="auto")``.  Pure closed forms — no
devices; the modeled-vs-measured comparison lives in
benchmarks/bench_distributed.py."""
import pytest

from repro.core.cost_model import (GRAM_SCHEMES, GramCommCost,
                                   choose_gram_scheme, gram_comm_cost,
                                   rank_gram_schemes)


def test_reducescatter_strictly_dominates_allreduce():
    for rows in (2, 4, 8, 64):
        ar = gram_comm_cost("allreduce", 4096, 512, rows=rows)
        rs = gram_comm_cost("reducescatter", 4096, 512, rows=rows)
        assert rs.wire_bytes < ar.wire_bytes
        assert rs.messages < ar.messages
        assert rs.flops == ar.flops


def test_bfs25d_replication_cuts_ring_wire_bytes():
    """Same (rows, ring) grid, replication c >= 2 added: the permute phase
    ships ceil(half/c) instead of half hops — per-device wire bytes drop."""
    for c in (2, 4):
        ring = gram_comm_cost("ring", 8192, 1024, rows=2, ring=8)
        bfs = gram_comm_cost("bfs25d", 8192, 1024, rows=2, ring=8, rep=c)
        assert bfs.wire_bytes < ring.wire_bytes
        assert bfs.mem_input_factor == c
        assert bfs.devices == ring.devices * c


def test_bfs25d_fewer_rounds_at_matched_device_count():
    """At equal P (trading row sharding for replication), bfs25d's skewed
    BFS walk needs fewer sequential collective rounds than the ring."""
    ring = gram_comm_cost("ring", 8192, 1024, rows=2, ring=8)      # P=16
    bfs = gram_comm_cost("bfs25d", 8192, 1024, rows=1, ring=8, rep=2)
    assert bfs.devices == ring.devices == 16
    assert bfs.messages < ring.messages


def test_dtype_bytes_scale_wire_not_messages():
    f32 = gram_comm_cost("ring", 1024, 256, rows=2, ring=4, dtype_bytes=4)
    bf16 = gram_comm_cost("ring", 1024, 256, rows=2, ring=4, dtype_bytes=2)
    assert f32.wire_bytes == 2 * bf16.wire_bytes
    assert f32.messages == bf16.messages


def test_rank_covers_requested_schemes_and_sorts_by_time():
    ranked = rank_gram_schemes(4096, 512, rows=2, ring=4, rep=2)
    assert sorted(r.scheme for r in ranked) == sorted(GRAM_SCHEMES)
    times = [r.time() for r in ranked]
    assert times == sorted(times)
    # restricting the candidate set restricts the ranking
    only = rank_gram_schemes(4096, 512, rows=8,
                             schemes=["allreduce", "reducescatter"])
    assert {r.scheme for r in only} == {"allreduce", "reducescatter"}


def test_auto_picks_row_reduction_for_tall_skinny():
    """m >> n: C is tiny, A is huge — shipping A around a ring loses to
    one reduce-scatter of C."""
    assert choose_gram_scheme(1 << 20, 128, rows=8, ring=4, rep=2) in \
        ("reducescatter", "allreduce")
    assert choose_gram_scheme(1 << 20, 128, rows=8) == "reducescatter"


def test_auto_picks_ring_family_for_wide():
    """n >> m/P: the n^2 reduction of C dominates — the ring family, which
    only ever ships (m/R)(n/T) shards and the packed stack, wins."""
    assert choose_gram_scheme(512, 8192, rows=2, ring=4, rep=2) in \
        ("ring", "bfs25d")


def test_model_crossover_between_shapes():
    """The allreduce-vs-ring ranking flips between a tall-skinny and a
    wide shape on the same mesh — the crossover bench_distributed.py
    reproduces with measured (HLO census) volumes."""
    def gap(m, n):
        ar = gram_comm_cost("allreduce", m, n, rows=2)
        ring = gram_comm_cost("ring", m, n, rows=2, ring=4)
        return ar.wire_bytes - ring.wire_bytes
    assert gap(4096, 128) < 0          # tall-skinny: allreduce cheaper
    assert gap(256, 2048) > 0          # wide: ring cheaper


def test_mixed_dtype_charges_permute_at_input_width():
    """bf16 A reduced into fp32 C: the ring's ppermutes ship 2-byte A
    shards while every reduction ships 4-byte C — out_bytes must not
    inflate the permute term."""
    mixed = gram_comm_cost("ring", 4096, 512, rows=2, ring=4,
                           dtype_bytes=2, out_bytes=4)
    all4 = gram_comm_cost("ring", 4096, 512, rows=2, ring=4,
                          dtype_bytes=4, out_bytes=4)
    all2 = gram_comm_cost("ring", 4096, 512, rows=2, ring=4,
                          dtype_bytes=2, out_bytes=2)
    assert all2.wire_bytes < mixed.wire_bytes < all4.wire_bytes
    # row-reduction schemes ship only C: input width is irrelevant
    assert gram_comm_cost("allreduce", 4096, 512, rows=2, dtype_bytes=2,
                          out_bytes=4).wire_bytes == \
        gram_comm_cost("allreduce", 4096, 512, rows=2, dtype_bytes=4,
                       out_bytes=4).wire_bytes


def test_cost_is_a_pure_dataclass():
    cst = gram_comm_cost("allreduce", 64, 32, rows=2)
    assert isinstance(cst, GramCommCost)
    assert cst.time(alpha=0.0, ici_bw=1.0, flop_rate=1e30) == \
        pytest.approx(cst.wire_bytes)


def test_invalid_scheme_and_missing_ring_raise():
    with pytest.raises(ValueError):
        gram_comm_cost("nope", 64, 32, rows=2)
    with pytest.raises(ValueError):
        gram_comm_cost("ring", 64, 32, rows=2)          # ring size missing
    with pytest.raises(ValueError):
        gram_comm_cost("bfs25d", 64, 32, rows=2)


def test_device_peaks_keyed_by_device_kind():
    """One published peaks table, keyed by ``jax.Device.device_kind``; an
    unknown kind is an error, never a silent v5e default."""
    from repro.core.cost_model import device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v6e"):
        device_peaks("TPU v6e")
