"""The window's classical Gram work at the chips' roofline, over the
device time it took, in %: the larger of flop / peak FLOP/s and bytes /
peak bytes/s (bench/work.py), over the busy union averaged over the
chips (trace)."""
from bench import work


def read(r):
    busy = r.busy_s()
    if busy <= 0 or r.window.flop <= 0:
        return None
    least = work.least_time(r.window.flop, r.window.bytes, r.peaks,
                            len(r.run.devices))
    return work.roofline_share(least.seconds, busy)
