"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its per-layer metrics and
its correctness limits are all found by name: ``BENCHMARK.json`` at the
root of the checkout names them, and each lives in a file of its own
under ``bench/`` (``configs/<config>.json``, ``traffic/<traffic>.json``,
``drivers/<driver>.py`` named by the traffic file, ``layers/<metric>.py``
or the reader of the metric's family, ``limits/<workload>.json``).  A new
cell is new files and entries.  A driver's ``Cell(run)`` builds and warms
the cell, naming the parts of that set-up in ``phases``; ``window(s)``,
``release()`` and ``check()`` time it, free it and compare it.

The run refuses, with a non-zero exit and no result line, when JAX finds
no TPU or fewer chips than the cell asks for.  Otherwise it builds the
cell and warms every shape (set-up), measures for ``--seconds`` seconds
(``--trace 1`` under the profiler, with the program's spans on), reads
the peak device memory, frees the program's state, compares what the
timed path produced with the plain reference, and prints each number
compared beside its limit on stderr and, last, one JSON line on stdout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.cell import Reading, Run, load_module, resolve  # noqa: E402


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _prepare_env(root: Path) -> Path:
    """State the run keeps inside its checkout: JAX's compile cache at the
    fixed ``<checkout>/.jax_cache``, whatever the environment names; the
    program reads tuned winners from an autotune path the benchmark owns
    and keeps empty; and libtpu writes no logs to a fixed path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    state = root / ".bench_state"
    tuned = state / "autotune" / "none.json"
    if tuned.exists():
        tuned.unlink()
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(tuned)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return state


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, system: str = "program",
             require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object (``correct`` false
    when a reading exceeds its limit).  Raises on a fault of the run."""
    cell = resolve(root, workload)
    state = _prepare_env(root)
    sys.path.insert(0, str(root / "src"))
    t_import = time.perf_counter()
    import jax
    from repro.compile_cache import use_persistent_cache
    from bench import compare, work

    chips = int(cell.entry["chips"])
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"run.py: JAX found no TPU (platform "
                         f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell asks for {chips} chips, JAX "
                         f"found {len(devs)}")
    devs = devs[:chips]
    t_devices = time.perf_counter()
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    use_persistent_cache()
    peaks = work.peaks_of(devs[0].device_kind) if trace else None

    run = Run(cell, seed, devs, system, trace)
    driver = load_module(cell.driver, f"bench_driver_{cell.driver.stem}")
    sut = driver.Cell(run)
    t_cell = time.perf_counter()
    setup_s = t_cell - T_START
    phases = {"start_s": t_import - T_START,
              "devices_s": t_devices - t_import,
              "cell_s": t_cell - t_devices, **sut.phases}
    _say(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items()))

    log_dir = state / "trace" / workload
    spans: list = []
    if trace:
        from repro.obs import trace as obs_trace
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        tracer = obs_trace.set_tracer(
            obs_trace.Tracer(enabled=True, capacity=1 << 20))
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench:window"):
                win = sut.window(seconds)
        finally:
            jax.profiler.stop_trace()
            tracer.enabled = False
        spans = tracer.events()
    else:
        win = sut.window(seconds)

    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in devs) if require_tpu else 0
    sut.release()
    readings = sut.check()
    correct, table = compare.judge(readings, cell.limits)

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        metrics, busy, breakdown = _per_layer(run, win, log_dir, spans, peaks)
        device.update(busy)
    else:
        values = dict(win.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": int(win.attempted),
              "failed": int(win.failed), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"seconds": win.seconds, "calls": win.calls,
                        **win.extra, "readings": readings}
    result["setup"] = phases
    result["checks"] = table
    return result


def _per_layer(run: Run, win, log_dir: Path, spans, peaks):
    """(per-layer metrics, device busy and window seconds, breakdown) of a
    traced window."""
    from bench import trace_reduce as tr
    trace = tr.load(tr.find_xplane(str(log_dir)))
    lo, hi = trace.window()
    rd = Reading(run, win, trace, spans, peaks, lo, hi)
    out = {}
    for m, path in run.cell.per_layer:
        reader = load_module(path, f"bench_layer_{m['name']}")
        value = reader.read(rd)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    ev0 = rd.device_events(0)
    busy = rd.busy_s()
    if busy <= 0:
        raise ValueError("no operation ran on the device in the window")
    return (out, {"busy_s": busy, "window_s": (hi - lo) / 1e9},
            {"device_ops": tr.top_ops(ev0, lo, hi),
             "idle_gaps": tr.idle_gaps(ev0, trace.host, lo, hi)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, row in result["checks"].items():
        _say(f"check {name} {row['value']!r} limit {row['limit']!r}")
    _say(f"correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
