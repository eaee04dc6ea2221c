"""The yardstick's arithmetic: classical work and bytes per call, the
chip's published peaks, and the roofline share built from them.

The work of a Gram is counted as the classical ``tril(AᵗA)``: for an
(m, n) operand, n(n+1)/2 lower-triangle entries of m multiply-adds each,
m·n·(n+1) flop.  It is the same count whatever implements the Gram, so
a change of kernel can neither hide nor inflate the work.  The Strassen
recursion performs fewer multiplications than this (0.852x at levels 3,
0.761x at levels 4), so a share read against it can exceed the MXU's true
share by up to 1/0.852; a reading above 105 % means the work or the time
is counted wrong, and :func:`roofline_share` raises rather than return it.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops: float        # dense bf16 MXU FLOP/s of one chip
    hbm_bytes_s: float  # HBM bytes/s of one chip
    hbm_bytes: float    # HBM capacity of one chip
    source: str


# Published peaks per chip, keyed by ``jax.Device.device_kind``.  A kind
# missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}

# A share of a roofline above this is a counting fault, not a reading.
SHARE_CEILING = 105.0


def peaks_of(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def gram_flop(m: int, n: int) -> float:
    """Classical flop of ``tril(AᵗA)`` for an (m, n) A: m·n·(n+1)."""
    return float(m) * n * (n + 1)


def gram_bytes(m: int, n: int, in_itemsize: int, out_elems: int,
               out_itemsize: int = 4, state_bytes: int = 0) -> float:
    """Least HBM traffic of one Gram call: A read once, the result
    written once, and an accumulated state (if any) read and written."""
    return (float(m) * n * in_itemsize + float(out_elems) * out_itemsize
            + 2.0 * state_bytes)


@dataclass(frozen=True)
class Least:
    seconds: float
    bound: str          # "compute" or "memory": the term that bounds it


def least_time(flop: float, nbytes: float, peaks: Peaks,
               chips: int = 1) -> Least:
    """The least time ``chips`` chips need for the work: the larger of
    flop over their peak FLOP/s and bytes over their HBM bandwidth."""
    t_c = flop / (chips * peaks.flops)
    t_m = nbytes / (chips * peaks.hbm_bytes_s)
    return Least(max(t_c, t_m), "compute" if t_c >= t_m else "memory")


def roofline_share(least_s: float, busy_s: float) -> float:
    """Least time over measured device time, in %.  Raises on a reading
    above :data:`SHARE_CEILING`, which only a miscount can give."""
    if busy_s <= 0:
        raise ValueError(f"device busy time {busy_s} s: nothing ran")
    share = 100.0 * least_s / busy_s
    if share > SHARE_CEILING:
        raise ValueError(
            f"roofline share {share:.2f} % exceeds {SHARE_CEILING} %: the "
            f"work ({least_s:.6g} s least) or the busy time "
            f"({busy_s:.6g} s) is miscounted")
    return share
