"""The program's compiles in the window: its ``compile`` records of the
``backend`` phase (a backend compile or a persistent-cache load), each
placed on the trace's clock by ``repro.obs.trace.place``, that meet the
window (trace).  None where the program records no such thing."""
PHASE = "backend"


def read(r):
    try:
        from repro.obs.trace import place
    except ImportError:
        return None
    if r.trace is None:
        return None
    return sum(1 for e, start, end in place(r.spans, r.trace.host)
               if e.name == "compile" and e.attrs.get("phase") == PHASE
               and start < r.hi and end > r.lo)
