"""A copy of the benchmark with toy cells added the way a later change
adds a cell: new files under ``bench/`` and new entries in
``BENCHMARK.json``, no edit to a harness file; and runs of those cells
in a process of their own (``toy_child``), since a run sets JAX's
config, the environment and the number of CPU devices, which must not
leak into other tests."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TOY_CONFIGS = {
    "toy-gram": {"m": 384, "n": 320, "dtype": "float32", "levels": "auto",
                 "distributed": {"mesh": {"ring": 2}, "scheme": "auto"}},
    "toy-stream": {"n": 320, "dtype": "bfloat16", "levels": "auto"},
}
TOY_TRAFFIC = {
    "toy-stream": {"driver": "row_stream", "chunk_rows": 128, "pool": 3},
}
# (workload, config, traffic, chips, end-to-end metric, the cell whose
# correctness limits it takes)
TOY_CELLS = [
    ("toy-gram.loop", "toy-gram", "loop", 1, "gram_s",
     "paper-gram-10k.loop"),
    ("toy-gram.mesh4", "toy-gram", "mesh4", 4, "gram_s",
     "paper-gram-10k.mesh4"),
    ("toy-stream.stream", "toy-stream", "toy-stream", 1, "rows_per_s",
     "normal-eq-16k.stream"),
]


def make_root(dst: Path) -> Path:
    """``dst`` holding ``BENCHMARK.json``, ``bench/`` and a link to the
    program, with the toy cells added.  Each toy cell is held to the
    correctness limits of the real cell it shrinks."""
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", dst / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, cfg in TOY_CONFIGS.items():
        (dst / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps({"name": name, **cfg}))
        spec["configs"].append({"name": name, "source": "toy",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "toy"})
    for name, mix in TOY_TRAFFIC.items():
        (dst / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    limits = dst / "bench" / "limits"
    for name, config, mix, chips, metric, real in TOY_CELLS:
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": mix, "chips": chips,
                                  "why": "toy"})
        e2e[metric]["workloads"].append(name)
        for m in spec["per_layer"]:
            if real in m["workloads"]:
                m["workloads"].append(name)
        shutil.copy(limits / f"{real}.json", limits / f"{name}.json")
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dst


def run_cases(tmp, cases, devices=1, timeout=900):
    """Run ``cases`` (see ``toy_child``) on a toy root under ``tmp`` in a
    fresh process on ``devices`` CPU devices; one result per case."""
    root = make_root(tmp / "root")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"),
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    env.pop("REPRO_AUTOTUNE_CACHE", None)
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={devices}")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.toy_child", str(root),
         json.dumps(cases)], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=timeout)
    if proc.returncode:
        raise AssertionError(f"toy_child failed:\n{proc.stderr[-4000:]}")
    out = [json.loads(line) for line in proc.stdout.splitlines()
           if line.startswith("{")]
    assert len(out) == len(cases), proc.stdout
    return out
