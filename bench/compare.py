"""The comparison that decides ``correct``: a Gram against its plain
reference, 256 x 256 tile by tile.

The reference is ``jnp.dot`` at ``Precision.HIGHEST`` (fp32 on the MXU)
of the operands the timed path was given; it imports nothing of the
program.  A missing, stale or misplaced tile is off by order 1 in that
tile, which an error over the whole matrix can average away, so both the
whole-matrix and the worst-tile relative Frobenius error are read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

TILE = 256
HIGHEST = jax.lax.Precision.HIGHEST


@jax.jit
def _squares(got, want):
    """Squared Frobenius norms of the error and of the reference, per
    TILE x TILE tile and per block of TILE rows."""
    pad = [(0, -d % TILE) for d in want.shape]
    diff = jnp.pad(got.astype(jnp.float32) - want, pad)
    ref = jnp.pad(want, pad)
    r, c = ref.shape

    def per_tile(x):
        return (x * x).reshape(r // TILE, TILE, c // TILE, TILE).sum((1, 3))

    return per_tile(diff), per_tile(ref)


def _worst(num, den):
    """Largest relative error over blocks; a block where the reference is
    zero counts as exact only if the result is zero there too."""
    ratio = jnp.where(den > 0, num / jnp.where(den > 0, den, 1),
                      jnp.where(num > 0, jnp.inf, 0))
    return float(jnp.sqrt(ratio.max()))


def errors(got, want) -> dict:
    """Relative Frobenius error of ``got`` against ``want``: over the
    whole matrix (``rel_fro``), in its worst TILE x TILE tile
    (``worst_tile_rel_fro``) and in its worst block of TILE rows
    (``worst_rows_rel_fro``), computed on the device."""
    if tuple(got.shape) != tuple(want.shape):
        inf = float("inf")
        return {"rel_fro": inf, "worst_tile_rel_fro": inf,
                "worst_rows_rel_fro": inf}
    num, den = _squares(jnp.asarray(got), jnp.asarray(want, jnp.float32))
    return {"rel_fro": float(jnp.sqrt(num.sum() / den.sum())),
            "worst_tile_rel_fro": _worst(num, den),
            "worst_rows_rel_fro": _worst(num.sum(1), den.sum(1))}


def worst(a: dict, b: dict) -> dict:
    """Entry-wise larger of two error readings."""
    return {k: max(a.get(k, 0.0), b[k]) for k in b}


def round_to(x, dtype):
    """``x`` rounded to ``dtype``'s precision, kept in float32.
    ``reduce_precision`` is an operation XLA keeps, where a pair of
    converts through a narrower type can be folded away."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(jnp.asarray(x, jnp.float32),
                                    exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def gram(a, gram_of: str = "cols", operand_dtype=None):
    """Full ``AᵗA`` (or ``AAᵗ``) in fp32 at HIGHEST precision; with
    ``operand_dtype`` the operand is first rounded to that type, which is
    what the control does in the program's place."""
    a = jnp.asarray(a).astype(jnp.float32)
    if operand_dtype is not None:
        a = round_to(a, operand_dtype)
    if gram_of == "rows":
        return jnp.dot(a, a.T, precision=HIGHEST)
    return jnp.dot(a.T, a, precision=HIGHEST)


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each reading that has a limit against it (a reading must not
    exceed it).  Returns (all within, {name: {"value", "limit"}}); a limit
    without a reading is a fault of the cell."""
    missing = set(limits) - set(readings)
    if missing:
        raise KeyError(f"no reading for the limits {sorted(missing)}")
    table = {k: {"value": float(readings[k]), "limit": float(limits[k])}
             for k in sorted(limits)}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    return ok, table
