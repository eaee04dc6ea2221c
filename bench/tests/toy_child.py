"""Runs toy cells on the CPU in a process of their own, with the timed
path broken where a case asks, and prints one JSON line per case.

    python -m bench.tests.toy_child <root> '<json list of cases>'

A case is ``{"workload", "system", "fault", "seed", "seconds"}``; the
run skips the harness's look for a chip and is otherwise a whole run.
Faults, each planted in the program where the cell's work is produced:

- ``state_unchanged``: a stream update returns its state as it was;
- ``half_batch``: half the rows of every operand are left out and the
  Gram of the rest is doubled (the mean over the rest, scaled);
- ``no_exchange``: the collectives between chips return the local part;
- ``altered``: one corner of every result is scaled by 1.5.
"""
from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path


def _half(a):
    return a[:max(1, a.shape[0] // 2)]


def _alter(c):
    return c.at[..., :8, :8].multiply(1.5)


def _patches(fault):
    """(object, attribute, replacement) triples planting ``fault``."""
    import jax
    import repro.core
    import repro.core.distributed as dist
    import repro.gram.stream as stream
    ata_full = repro.core.ata_full
    stack_update, stack_finalize = stream.stack_update, stream.stack_finalize
    dgram = dist.distributed_gram
    if fault == "state_unchanged":
        return [(stream, "stack_update", lambda st, ch, **kw: st)]
    if fault == "half_batch":
        def upd(st, ch, **kw):
            h = _half(ch)
            return stack_update(stack_update(st, h, **kw), h, **kw)
        return [
            (repro.core, "ata_full",
             lambda a, **kw: 2 * ata_full(_half(a), **kw)),
            (dist, "ata_full", lambda a, **kw: 2 * ata_full(_half(a), **kw)),
            (stream, "stack_update", upd)]
    if fault == "no_exchange":
        def scatter(x, axis, scatter_dimension=0, tiled=False, **kw):
            k = jax.lax.axis_size(axis)
            part = x.shape[scatter_dimension] // k
            return jax.lax.dynamic_slice_in_dim(
                x, jax.lax.axis_index(axis) * part, part, scatter_dimension)
        return [(jax.lax, "psum", lambda x, axis, **kw: x),
                (jax.lax, "psum_scatter", scatter),
                (jax.lax, "ppermute", lambda x, axis, perm, **kw: x),
                (jax.lax, "all_gather",
                 lambda x, axis, axis_index_groups=None, tiled=False, **kw:
                 x if tiled else x[None])]
    if fault == "altered":
        return [
            (repro.core, "ata_full", lambda a, **kw: _alter(ata_full(a, **kw))),
            (stream, "stack_finalize",
             lambda st, n=None, **kw: _alter(stack_finalize(st, n, **kw))),
            (dist, "distributed_gram",
             lambda a, mesh, **kw: _alter(dgram(a, mesh, **kw)))]
    if fault is None:
        return []
    raise ValueError(f"unknown fault {fault!r}")


@contextlib.contextmanager
def planted(fault):
    saved = []
    try:
        for obj, name, new in _patches(fault):
            saved.append((obj, name, getattr(obj, name)))
            setattr(obj, name, new)
        yield
    finally:
        for obj, name, old in reversed(saved):
            setattr(obj, name, old)


def main(argv) -> int:
    root, cases = Path(argv[1]), json.loads(argv[2])
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    from bench import run
    for case in cases:
        with planted(case.get("fault")):
            res = run.run_cell(root, case["workload"], case["seed"],
                               case["seconds"], False,
                               system=case.get("system", "program"),
                               require_tpu=False)
        print(json.dumps({"case": case, "correct": res["correct"],
                          "checks": res["checks"],
                          "attempted": res["attempted"],
                          "metrics": res["metrics"],
                          "setup": res["setup"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
