"""Distributed-gram schemes: modeled vs measured communication volume.

The multi-device run needs ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` set before jax initializes, so the work happens in a
child process (``benchmarks._distributed_child``; same pattern as the
``multidevice`` pytest marker) which writes ``BENCH_distributed.json``:

* per (scheme x shape): closed-form per-device wire bytes / message
  rounds from ``core.cost_model.gram_comm_cost`` next to a
  ``collective_census`` of the actually-compiled post-SPMD HLO, + wall
  clock on the 8 fake devices;
* the allreduce-vs-ring crossover between a tall-skinny and a wide
  shape, asserted to flip identically in the model and the measurement —
  the evidence that ``distributed_gram(scheme="auto")`` ranks schemes on
  a model the compiled programs actually obey.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def run(quick: bool = False):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # the child is a fake-device CPU census; the parent may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "benchmarks._distributed_child"]
    if quick:
        cmd.append("--quick")
    out = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=1200)
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr)
        raise RuntimeError("bench_distributed child failed")
    assert "ALL_OK" in out.stdout
    return str(REPO / "artifacts" / "bench" / "BENCH_distributed.json")


if __name__ == "__main__":
    run("--quick" in sys.argv)
