"""Runs of one cell on many seeds in one process: the readings that the
correctness limits are set from, and the control's readings.  Not part
of a benchmark run.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 5 \
        [--system program|float8_e4m3fn|bfloat16]

``--system`` puts a control in the program's place (see each driver).
Each run prints its result object as one JSON line, then the largest and
smallest reading of every number compared.  Set-up times here are not a
run's: the process compiles once for all seeds.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import run as run_mod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--system", default="program")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    readings: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_mod.run_cell(ROOT, args.workload, seed, args.seconds,
                               bool(args.trace), system=args.system)
        res["seed"], res["system"] = seed, args.system
        print(json.dumps(res), flush=True)
        for k, row in res["checks"].items():
            readings.setdefault(k, []).append(row["value"])
    for k, vals in sorted(readings.items()):
        print(f"{args.workload} {args.system} {k}: max {max(vals)!r} "
              f"min {min(vals)!r} over {len(vals)} seeds", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
