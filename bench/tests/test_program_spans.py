"""The program's spans on the device trace's clock: what a CPU profiler
session holds of them through ``trace_reduce.load``, how ``idle_gaps``
names a gap under one, and the two readers of them (``compiles``,
``idle_in_program_ms``)."""
import gc
import time

import pytest

from bench import trace_reduce as tr
from bench.cell import Reading, Window, load_module, reader_of
from bench.tests.toy import REPO
from bench.trace_reduce import Event

MS = 1_000_000


@pytest.fixture
def tracer():
    from repro.obs import trace
    saved = trace.get_tracer()
    t = trace.set_tracer(trace.Tracer(enabled=True))
    yield t
    trace.set_tracer(saved)


@pytest.fixture
def no_auto_gc():
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


@pytest.fixture
def no_compile_cache():
    """A compile is then a backend compile alone, with no cache lookup
    or write around it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _profiled(tmp_path, body):
    """Run ``body()`` in a CPU profiler session under ``bench:window``;
    the session's file."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            body()
    finally:
        jax.profiler.stop_trace()
    return tr.find_xplane(str(tmp_path))


def test_live_span_nested_inside_a_bench_annotation(tmp_path, tracer):
    import jax
    from repro.obs import trace

    def body():
        with jax.profiler.TraceAnnotation("bench:call"):
            with trace.span("exec", bucket="64x64"):
                time.sleep(0.002)
    t = tr.load(_profiled(tmp_path, body))
    (call,) = [e for e in t.host if e.name == "bench:call"]
    (ex,) = [e for e in t.host if e.name == "gram_exec:exec"]
    assert call.start <= ex.start < ex.end <= call.end
    assert ex.end - ex.start >= 2 * MS
    # the name only: attributes stay in the ring
    (ring,) = [e for e in tracer.events() if e.name == "exec"]
    assert ring.attrs == {"bucket": "64x64"}


def test_span_gc_pause_and_compile_placed_within_1ms(
        tmp_path, tracer, no_auto_gc, no_compile_cache):
    """A live span, a collector pass and an after-the-fact ``compile``
    record, each on the trace's clock within 1 ms of where the profiler
    saw the work happen."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from repro.obs import trace

    def fresh_for_placement(x):
        return jnp.sin(x) * 3.0 + 1.0
    # the session's only compile is then the one under test
    x = jax.block_until_ready(jnp.ones((5, 9)))

    def body():
        with jax.profiler.TraceAnnotation("bench:span"):
            with trace.span("work"):
                time.sleep(0.003)
        garbage = [[i] for i in range(200_000)]
        garbage.append(garbage)
        del garbage
        with jax.profiler.TraceAnnotation("bench:collect"):
            gc.collect()
        jax.jit(fresh_for_placement)(x).block_until_ready()
        time.sleep(0.002)
    path = _profiled(tmp_path, body)
    t = tr.load(path)
    placed = trace.place(tracer.events(), t.host)

    def one(name, **attrs):
        (hit,) = [(s, e) for ev, s, e in placed if ev.name == name
                  and all(ev.attrs.get(k) == v for k, v in attrs.items())]
        return hit

    def truth(name):
        (h,) = [e for e in t.host if e.name == name]
        return h.start, h.end

    for got, want in ((one("work"), truth("bench:span")),
                      (one("gc", generation=2), truth("bench:collect"))):
        assert abs(got[0] - want[0]) < MS and abs(got[1] - want[1]) < MS
    (compiled,) = [(int(e.start_ns), int(e.end_ns))
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name == "backend_compile_and_load"]
    got = one("compile", phase="backend",
              fun_name="jit(fresh_for_placement)")
    assert abs(got[0] - compiled[0]) < MS and abs(got[1] - compiled[1]) < MS


def test_idle_gaps_names_a_gap_under_a_gc_span():
    ops = [Event("fusion.1", 0, 40), Event("fusion.1", 70, 100)]
    host = [Event(tr.WINDOW_SPAN, 0, 100), Event("bench:call", 0, 100),
            Event("gram_exec:gc", 45, 60)]
    gaps = dict(tr.idle_gaps(ops, host, 0, 100))
    assert gaps == {"gram_exec:gc": 15e-9, "bench:call": 15e-9}


def _reader(metric):
    return load_module(reader_of(REPO / "bench", metric), f"t_{metric}")


def _reading(spans, host, ops, calls=4, lo=0, hi=100 * MS):
    from bench.trace_reduce import Trace
    window = Window(0.1, {}, calls, 0, calls=calls)
    return Reading(None, window, Trace({0: ops}, host), spans, None, lo, hi)


def _record(name, t0, t1, mark, **attrs):
    from repro.obs.trace import TraceEvent
    return TraceEvent(name, "X", t0, t1, 0, None, None, 0, attrs, mark=mark)


OFF = 5 * MS        # the trace's clock minus perf_counter, in ns


def _synthetic():
    """A 100 ms window on the trace's clock: the device idle 20-30 ms
    (a live ``gc`` span over it) and 50-70 ms (a ``backend`` compile
    placed over 50-62 ms by its mark at 62 ms), calls over the rest."""
    s = 1e-9
    spans = [
        _record("gc", (20 * MS - OFF) * s, (30 * MS - OFF) * s,
                (20 * MS - OFF) * s, generation=0, collected=3),
        _record("compile", (50 * MS - OFF) * s, (62 * MS - OFF) * s,
                (62 * MS - OFF) * s, phase="backend", fun_name="jit(f)"),
        _record("compile", (40 * MS - OFF) * s, (49 * MS - OFF) * s,
                (49 * MS - OFF) * s, phase="lower", fun_name="jit(f)"),
        _record("compile", (-30 * MS - OFF) * s, (-20 * MS - OFF) * s,
                (-20 * MS - OFF) * s, phase="backend", fun_name="jit(g)"),
    ]
    host = [Event(tr.WINDOW_SPAN, 0, 100 * MS),
            Event("bench:call", 0, 100 * MS),
            Event("gram_exec:gc", 20 * MS, 30 * MS),
            Event("gram_exec:compile", 62 * MS, 62 * MS + 2_000),
            Event("gram_exec:compile", 49 * MS, 49 * MS + 2_000)]
    ops = [Event("fusion.1", 0, 20 * MS), Event("fusion.1", 30 * MS, 50 * MS),
           Event("fusion.1", 70 * MS, 100 * MS)]
    return spans, host, ops


def test_compiles_counts_backend_records_that_meet_the_window():
    spans, host, ops = _synthetic()
    read = _reader("compiles.gram").read
    assert read(_reading(spans, host, ops)) == 1
    assert read(_reading(spans, host, ops, lo=-40 * MS)) == 2
    assert read(_reading([], host[:2], ops)) == 0


def test_idle_in_program_ms_is_idle_under_program_spans_per_call():
    spans, host, ops = _synthetic()
    read = _reader("idle_in_program_ms.stream").read
    # 10 ms under gc, 12 ms under the placed compile and 2 us under its
    # mark; the other 8 ms (62-70) only under bench:call
    assert read(_reading(spans, host, ops)) == pytest.approx(22.002 / 4)
    assert read(_reading([], host[:2], ops)) == 0.0


def test_readers_say_nothing_without_the_program_hooks(monkeypatch):
    from repro.obs import trace
    monkeypatch.delattr(trace, "place")
    spans, host, ops = _synthetic()
    for metric in ("compiles.gram", "idle_in_program_ms.gram"):
        assert _reader(metric).read(_reading(spans, host, ops)) is None

