import math
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event

FIXTURES = Path(__file__).parent / "fixtures"


def ev(name, s, e):
    return Event(name, s, e)


def test_union_merges_overlaps_and_clips():
    got = tr.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 30)], 1, 25)
    assert got == [(1, 4), (5, 12), (20, 25)]
    assert tr.length(got) == 3 + 7 + 5


def test_subtract_leaves_the_uncovered_parts():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (29, 40)]
    assert tr.subtract(a, b) == [(0, 2), (4, 8), (22, 29)]
    assert tr.subtract(a, []) == a
    assert tr.subtract([], b) == []


def test_busy_and_idle_share_of_a_window():
    ops = [ev("fusion", 0, 40), ev("custom-call", 30, 60), ev("copy", 80, 90)]
    assert tr.busy_ns(ops, 0, 100) == 70
    assert tr.idle_share(ops, 0, 100) == pytest.approx(30.0)
    # only the part inside the window counts
    assert tr.idle_share(ops, 50, 100) == pytest.approx(60.0)


def test_exposed_collective_time_excludes_overlapped_compute():
    ops = [ev("all-gather.5", 0, 10), ev("fusion.1", 5, 20),
           ev("reduce-scatter.1", 18, 30), ev("collective-permute-done", 40, 45),
           ev("convolution.2", 42, 50)]
    # exposed: all-gather 0-5, reduce-scatter 20-30, permute 40-42
    assert tr.exposed_collective_ns(ops, 0, 100) == 5 + 10 + 2


def test_top_ops_sums_instances_in_the_window():
    ops = [ev("a", 0, 10), ev("b", 10, 40), ev("a", 50, 70), ev("c", 95, 120)]
    assert tr.top_ops(ops, 0, 100, k=2) == [["b", 30e-9], ["a", 30e-9]] or \
        tr.top_ops(ops, 0, 100, k=2) == [["a", 30e-9], ["b", 30e-9]]
    assert ["c", 5e-9] in tr.top_ops(ops, 0, 100)


def test_idle_gaps_named_by_the_host_span_over_them():
    ops = [ev("k", 0, 10), ev("k", 30, 40), ev("k", 90, 100)]
    host = [ev("bench:window", 0, 100), ev("bench:submit", 10, 30),
            ev("bench:drain", 35, 100), ev("gram_exec:64x64", 40, 60)]
    gaps = dict(tr.idle_gaps(ops, host, 0, 100))
    # the innermost span names each part; nothing covers nothing
    assert gaps == {"bench:submit": 20e-9, "gram_exec:64x64": 20e-9,
                    "bench:drain": 30e-9}
    assert dict(tr.idle_gaps(ops, host[:2], 0, 100)) == {
        "bench:submit": 20e-9, "untracked": 50e-9}


@pytest.mark.parametrize("q,want", [(50, 3), (95, 5), (100, 5), (1, 1)])
def test_nearest_rank_percentile(q, want):
    assert tr.percentile([5, 1, 4, 2, 3], q) == want


def test_percentile_ranks_failures_last():
    vals = [1.0] * 18 + [math.inf, math.inf]
    assert tr.percentile(vals, 90) == 1.0
    assert tr.percentile(vals, 95) == math.inf
    with pytest.raises(ValueError):
        tr.percentile([], 50)


HLO = """
  %all-gather.5 = f32[5000,10000]{1,0} all-gather(%param.1), channel_id=1
  %fusion.387 = f32[1250,1250]{0,1} fusion(%custom-call.2, %all-gather.5), kind=kOutput, calls=%fused_computation.616
  %fusion = f32[5040,10000]{1,0} fusion(%bitcast_add_fusion), kind=kCustom, calls=%all-reduce-scatter
  %collective-permute-start = (f32[40,10000]) collective-permute-start(%slice.1228), channel_id=26
  ROOT %collective-permute-done = f32[40,10000]{1,0} collective-permute-done(%collective-permute-start)
"""


def test_collectives_found_in_the_compiled_program():
    named = tr.collective_ops(HLO)
    assert named == {"all-gather.5", "fusion", "collective-permute-start",
                     "collective-permute-done"}
    ops = [ev("fusion", 0, 10), ev("fusion.387", 5, 20)]
    assert tr.exposed_collective_ns(ops, 0, 100, named) == 5
    assert tr.exposed_collective_ns(ops, 0, 100) == 0


def test_recorded_chip_trace():
    """A 10 s window of ``normal-eq-16k.stream`` traced on one v5e: 40
    chunk updates, each one ``fused_rank_k`` kernel."""
    t = tr.load(str(FIXTURES / "stream_window.xplane.pb"))
    assert set(t.devices) == {0}
    lo, hi = t.window()
    assert (hi - lo) / 1e9 == pytest.approx(10.104669745)
    ops = t.devices[0]
    assert tr.busy_ns(ops, lo, hi) / 1e9 == pytest.approx(8.894715958)
    assert tr.idle_share(ops, lo, hi) == pytest.approx(11.974204, abs=1e-5)
    top = tr.top_ops(ops, lo, hi, k=1)[0]
    assert top[0] == "fused_rank_k_l3_strassen_strassen_pd2.1"
    assert top[1] == pytest.approx(8.828217344)
    assert sum(1 for e in ops if e.name == top[0]) == 40
    assert sum(1 for e in t.host if e.name == "bench:update") == 40
    gaps = dict(tr.idle_gaps(ops, t.host, lo, hi))
    assert sum(gaps.values()) == pytest.approx((hi - lo) / 1e9 - 8.894715958)
    assert tr.exposed_collective_ns(ops, lo, hi) == 0
