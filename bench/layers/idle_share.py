"""Share of the window in which no operation ran on device 0, in %:
1 - the union of its operations' intervals over the window (trace)."""
from bench import trace_reduce as tr


def read(r):
    events = r.device_events(0)
    if not events:
        return None
    return tr.idle_share(events, r.lo, r.hi)
