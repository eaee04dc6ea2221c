"""Per call, device 0's idle time in the window under a program span, in
ms: each part of the idle named by the innermost host span over it
(``trace_reduce.idle_gaps``), over the trace's ``gram_exec:*`` spans and
the program's records placed on the trace's clock by
``repro.obs.trace.place``, summed over the ``gram_exec:`` names (trace).
None where the program puts no spans on the trace's clock."""
from bench import trace_reduce as tr


def read(r):
    try:
        from repro.obs.trace import MIRROR, place
    except ImportError:
        return None
    events = r.device_events(0)
    if not events or not r.window.calls:
        return None
    host = list(r.trace.host) + [
        tr.Event(MIRROR + e.name, start, end)
        for e, start, end in place(r.spans, r.trace.host) if end > start]
    names = {e.name for e in host}
    idle = sum(s for name, s in tr.idle_gaps(events, host, r.lo, r.hi,
                                             k=len(names) + 1)
               if name.startswith(MIRROR))
    return idle * 1e3 / r.window.calls
