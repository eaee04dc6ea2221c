"""A benchmark cell's files, found by the names in ``BENCHMARK.json``,
and what the harness hands its drivers and per-layer readers."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH = "bench"


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""
    name: str
    entry: dict
    config: dict
    traffic: dict
    limits: dict
    driver: Path
    end_to_end: list
    per_layer: list             # (metric entry, reader path)


def resolve(root: Path, workload: str) -> Cell:
    """Find every file of one cell by the names in ``BENCHMARK.json``."""
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    base = root / BENCH
    config = _read_json(base / "configs" / f"{w['config']}.json")
    traffic = _read_json(base / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(base / "limits" / f"{workload}.json")
    driver = base / "drivers" / f"{traffic['driver']}.py"
    if not driver.is_file():
        raise FileNotFoundError(f"{driver} is missing")

    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layers = [(m, reader_of(base, m["name"])) for m in spec["per_layer"]
              if workload in m["workloads"]]
    return Cell(workload, w, config, traffic, limits["limits"], driver,
                e2e, layers)


def reader_of(base: Path, metric: str) -> Path:
    """The reader of a per-layer metric: ``layers/<metric>.py``, else the
    reader of its family, ``layers/<name before the first dot>.py``
    (``idle_share.gram`` and ``idle_share.stream`` read alike)."""
    own = base / "layers" / f"{metric}.py"
    family = base / "layers" / f"{metric.split('.')[0]}.py"
    for path in (own, family):
        if path.is_file():
            return path
    raise FileNotFoundError(f"{own} is missing, and so is {family}")


@dataclass
class Run:
    """What a driver is given: the cell, the seed, the chips, and which
    system stands in the timed path ("program", or a control)."""
    cell: Cell
    seed: int
    devices: list
    system: str = "program"
    trace: bool = False

    def key(self):
        """A JAX key from the whole seed (``jax.random.key`` alone keeps
        only its low 32 bits when 64-bit mode is off)."""
        import jax
        return jax.random.fold_in(jax.random.key(self.seed & 0xFFFFFFFF),
                                  (self.seed >> 32) & 0xFFFFFFFF)


@dataclass
class Window:
    """What a driver's window reports."""
    seconds: float              # the window's length on the host clock
    metrics: dict               # end-to-end name -> value
    attempted: int
    failed: int
    flop: float = 0.0           # classical work of the calls in it
    bytes: float = 0.0
    calls: int = 0
    collectives: frozenset = frozenset()    # op names that cross chips
    extra: dict = field(default_factory=dict)


@dataclass
class Reading:
    """What a per-layer reader is given."""
    run: Run
    window: Window
    trace: object               # trace_reduce.Trace, or None
    spans: list                 # the program's obs.trace events
    peaks: object               # work.Peaks of the chip
    lo: int = 0                 # the window on the trace's clock (ns)
    hi: int = 0

    def device_events(self, index: int = 0):
        return self.trace.devices.get(index, []) if self.trace else []

    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the chips."""
        from bench import trace_reduce as tr
        idx = range(len(self.run.devices))
        return sum(tr.busy_ns(self.device_events(i), self.lo, self.hi)
                   for i in idx) / len(idx) / 1e9
