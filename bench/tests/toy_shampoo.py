"""The Shampoo statistics cell at toy size, added to the toy benchmark of
``toy`` the way a change adds a cell, and run in a process of its own
with the faults only that cell can have:

- ``step_dropped``: the second statistics step returns its statistics
  as they were;
- ``beta_ignored``: the statistics step keeps the old statistics
  unscaled (beta 1) while it adds ``1 - beta2`` times the new Grams.

Every fault of ``toy_child`` can be planted too.

    python -m bench.tests.toy_shampoo <root> '<json list of cases>'
"""
from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

from bench.tests import toy, toy_child

WORKLOAD, REAL = "toy-shampoo.stats", "shampoo-sala-mlp.stats"
CONFIG = {"name": "toy-shampoo", "blocks": [[64, 32], [64, 32], [32, 64]],
          "beta2": 0.999, "dtype": "float32", "levels": "auto"}


def make_root(dst: Path) -> Path:
    """``toy.make_root`` with the toy Shampoo cell added, held to the
    correctness limits of the real cell it shrinks."""
    toy.make_root(dst)
    bench = dst / "bench"
    (bench / "configs" / "toy-shampoo.json").write_text(json.dumps(CONFIG))
    (bench / "limits" / f"{WORKLOAD}.json").write_text(
        (bench / "limits" / f"{REAL}.json").read_text())
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy-shampoo", "source": "toy",
                            "file": "bench/configs/toy-shampoo.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": WORKLOAD, "config": "toy-shampoo",
                              "traffic": "stats", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(WORKLOAD)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dst


def run_cases(tmp, cases, timeout=900):
    """Run ``cases`` (see ``toy_child``) on a toy root under ``tmp`` in a
    fresh process on one CPU device; one result per case."""
    root = make_root(tmp / "root")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"),
               PYTHONPATH=os.pathsep.join([str(toy.REPO),
                                           str(toy.REPO / "src")]))
    env.pop("REPRO_AUTOTUNE_CACHE", None)
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.toy_shampoo", str(root),
         json.dumps(cases)], cwd=toy.REPO, env=env, capture_output=True,
        text=True, timeout=timeout)
    if proc.returncode:
        raise AssertionError(f"toy_shampoo failed:\n{proc.stderr[-4000:]}")
    out = [json.loads(line) for line in proc.stdout.splitlines()
           if line.startswith("{")]
    assert len(out) == len(cases), proc.stdout
    return out


_toy_patches = toy_child._patches


def _patches(fault):
    """``toy_child``'s faults, and the statistics step's own."""
    import jax
    shampoo = importlib.import_module("repro.optim.shampoo")
    if fault == "step_dropped":
        update_stats, calls = shampoo.update_stats, []

        def dropped(st, g, *args, **kw):
            calls.append(1)
            return st if len(calls) == 2 else update_stats(st, g, *args, **kw)
        return [(shampoo, "update_stats", dropped)]
    if fault == "beta_ignored":
        step = shampoo._stats_step
        return [(shampoo, "_stats_step",
                 lambda st, g, beta, alpha, **kw:
                 step(st, g, jax.numpy.float32(1.0), alpha, **kw))]
    return _toy_patches(fault)


if __name__ == "__main__":
    toy_child._patches = _patches
    sys.exit(toy_child.main(sys.argv))
