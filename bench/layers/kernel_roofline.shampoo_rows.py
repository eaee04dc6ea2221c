"""The rows-side accumulating kernel's share of its roofline, in %: the
classical work of the window's L updates (G G^t of every block: flop and
bytes of ``bench/work.py``, reported by ``drivers/shampoo_stats.py`` as
``l_flop`` and ``l_bytes``) at one chip's roofline, over device 0's time
in the operations named ``fused_rank_k_rows*`` (trace).  None where no
such kernel ran in the window."""
from bench import work

KERNEL = "fused_rank_k_rows"


def read(r):
    extra = r.window.extra
    if not extra.get("l_flop"):
        return None
    ns = sum(min(e.end, r.hi) - max(e.start, r.lo)
             for e in r.device_events(0)
             if e.name.startswith(KERNEL) and e.end > r.lo and e.start < r.hi)
    if ns <= 0:
        return None
    least = work.least_time(extra["l_flop"], extra["l_bytes"], r.peaks)
    return work.roofline_share(least.seconds, ns / 1e9)
