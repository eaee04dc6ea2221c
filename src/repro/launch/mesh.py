"""Production meshes. Functions only — importing this module never touches
jax device state (device count is locked on first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis
    crosses DCI — keep only DP-style (per-step, overlappable) collectives
    on it."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_gram_mesh(n_devices=None, *, rep: int = 1, ring=None,
                   devices=None):
    """(rep, data, model) mesh for ``core.distributed.distributed_gram``
    (axis names match ``default_gram_axes``): ``rep`` is the 2.5D
    replication factor (bfs25d), ``ring`` the half-ring/column axis size
    (default: every non-replication device), rows take the rest.  Accepts
    a device subset so odd factors (rep=3, ring=3, ...) work on an
    8-device host platform."""
    import numpy as np
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    total = len(devs)
    if total % rep:
        raise ValueError(f"{total} devices not divisible by rep={rep}")
    inner = total // rep
    T = inner if ring is None else ring
    if T < 1 or inner % T:
        raise ValueError(f"{inner} devices per group not divisible by "
                         f"ring={T}")
    rows = inner // T
    grid = np.array(devs[:rep * rows * T]).reshape(rep, rows, T)
    return Mesh(grid, ("rep", "data", "model"))
