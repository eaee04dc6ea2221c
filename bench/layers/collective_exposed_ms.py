"""Per call, the device time of collective operations on device 0 during
which no other operation ran there, in ms (trace)."""
from bench import trace_reduce as tr


def read(r):
    events = r.device_events(0)
    named = r.window.collectives
    if not r.window.calls or not any(tr.is_collective(e.name, named)
                                     for e in events):
        return None
    return (tr.exposed_collective_ns(events, r.lo, r.hi, named)
            / 1e6 / r.window.calls)
