"""From a profiler trace and the program's spans to per-layer numbers.

A trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes.  Its
device planes (``/device:TPU:<i>``) hold one line of XLA operations; its
host plane holds the ``TraceAnnotation`` spans of the benchmark
(``bench:*``) and of the program (``gram_exec:*``).  All event times are
nanoseconds on one clock.

Busy time is the union of the intervals in which an operation ran on a
device; idle share is 1 - busy / window.  A collective's exposed time is
the part of its intervals in which no other operation ran on that device.
"""
from __future__ import annotations

import glob
import heapq
import math
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

# XLA's collective operations, by the name the trace gives each instance
# (``all-reduce.3``, ``reduce-scatter-start.1``, ``all-gather-done``...).
COLLECTIVE = re.compile(r"^(all-reduce|reduce-scatter|all-gather|all-to-all|"
                        r"collective-permute|collective-broadcast|"
                        r"ragged-all-to-all)")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# The device line that holds one event per executed operation.
OPS_LINE = "XLA Ops"
# Host spans that say what the host was doing.
HOST_SPAN = re.compile(r"^(bench:|gram_exec:)")
WINDOW_SPAN = "bench:window"


@dataclass
class Event:
    name: str
    start: int      # ns
    end: int        # ns


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # index -> [Event]
    host: list = field(default_factory=list)      # [Event], host spans

    def window(self) -> tuple[int, int]:
        """(start, end) of the benchmark's window span."""
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"{len(spans)} {WINDOW_SPAN!r} spans in the "
                             "trace, expected one")
        return spans[0].start, spans[0].end


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """An operation's name from the trace's event name, which on a TPU is
    the whole HLO instruction: ``%fusion.3 = f32[...] fusion(...)`` ->
    ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Device operations per TPU and the host spans of one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.devices[int(m.group(1))] = [
                        Event(op_name(e.name), int(e.start_ns),
                              int(e.end_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(Event(e.name, int(e.start_ns), int(e.end_ns))
                               for e in line.events
                               if HOST_SPAN.match(e.name))
    return tr


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Sorted, disjoint union of ``(start, end)`` intervals clipped to
    [lo, hi]."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(merged_a, merged_b) -> list[tuple[int, int]]:
    """The parts of disjoint sorted intervals ``a`` outside ``b``."""
    out, j = [], 0
    for s, e in merged_a:
        cur = s
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            bs, be = merged_b[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(events, lo: int, hi: int) -> int:
    return length(union(((e.start, e.end) for e in events), lo, hi))


def idle_share(events, lo: int, hi: int) -> float:
    """Share of [lo, hi] in which no operation ran, in %."""
    return 100.0 * (1.0 - busy_ns(events, lo, hi) / (hi - lo))


_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = .*? ([\w-]+)\(")
_CALLS = re.compile(r"calls=%?([\w.-]+)")


def collective_ops(hlo_text: str) -> set[str]:
    """Names of a compiled program's instructions that exchange data
    between chips: collective opcodes, and fusions that call a collective
    computation (a reduce-scatter can run as ``fusion`` calling
    ``all-reduce-scatter``).  The trace names each operation so."""
    out = set()
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        calls = _CALLS.search(line)
        if COLLECTIVE.match(opcode) or (
                opcode == "fusion" and calls
                and COLLECTIVE.match(calls.group(1))):
            out.add(name)
    return out


def is_collective(name: str, named=frozenset()) -> bool:
    return name in named or bool(COLLECTIVE.match(name))


def exposed_collective_ns(events, lo: int, hi: int,
                          named=frozenset()) -> int:
    """Time in [lo, hi] in which a collective ran and nothing else did;
    ``named`` adds operations known to be collectives by name."""
    coll = union(((e.start, e.end) for e in events
                  if is_collective(e.name, named)), lo, hi)
    comp = union(((e.start, e.end) for e in events
                  if not is_collective(e.name, named)), lo, hi)
    return length(subtract(coll, comp))


def top_ops(events, lo: int, hi: int, k: int = 10):
    """[[name, seconds]] of the k operations that took most device time
    in [lo, hi], summed over their instances."""
    tot: dict[str, int] = defaultdict(int)
    for e in events:
        d = min(e.end, hi) - max(e.start, lo)
        if d > 0:
            tot[e.name] += d
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(events, host, lo: int, hi: int, k: int = 10):
    """[[name, seconds]]: the device's idle time in [lo, hi], each part of
    it named by the innermost host span over it ("untracked" where none
    is), summed by name, k longest."""
    busy = union(((e.start, e.end) for e in events), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    spans = [e for e in host if e.name != WINDOW_SPAN
             and e.end > lo and e.start < hi]
    marks = sorted({lo, hi, *(t for g in gaps for t in g),
                    *(min(max(t, lo), hi) for e in spans
                      for t in (e.start, e.end))})
    opening = defaultdict(list)
    for i, e in enumerate(spans):
        opening[max(e.start, lo)].append(i)
    active: list = []           # heap of (length, index): innermost first
    tot: dict[str, int] = defaultdict(int)
    g = 0
    for a, b in zip(marks, marks[1:]):
        for i in opening.get(a, ()):
            heapq.heappush(active, (spans[i].end - spans[i].start, i))
        while active and spans[active[0][1]].end <= a:
            heapq.heappop(active)
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g < len(gaps) and gaps[g][0] <= a and b <= gaps[g][1]:
            name = spans[active[0][1]].name if active else "untracked"
            tot[name] += b - a
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def percentile(values, q: float) -> float:
    """Nearest-rank q-th percentile (0 < q <= 100): the smallest value
    with at least q % of the sample at or below it.  ``inf`` values (a
    request that failed or never finished) rank above every other."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]
