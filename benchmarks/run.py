"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

fig5  exec time   (measured CPU + modeled cluster)    <- paper Fig 5
fig6  speed-up                                        <- paper Fig 6
fig7  efficiency                                      <- paper Fig 7
fig8  Karp-Flatt                                      <- paper Fig 8
s3.1  multiplication counts vs (2/7) n^log2(7)        <- paper §3.1
s5    communication model + comm fraction             <- paper §5/§6.3.2
roofline  3-term roofline over dry-run artifacts      <- brief §Roofline
ata   fused-pipeline trajectory -> BENCH_ata.json     <- DESIGN.md §4
grads fused backward trajectory -> BENCH_grads.json   <- DESIGN.md §11
gram_service  batched vs sequential serving -> BENCH_gram_service.json
                                                      <- DESIGN.md §10
distributed  modeled vs measured comm volume per scheme (8 fake devices)
                                   -> BENCH_distributed.json <- DESIGN.md §5

``--smoke`` runs the fast interpret-mode kernel test suite plus the
quick distributed comm and backward benchmarks instead of the full
benchmarks (CI smoke target: validates the fused Pallas pipeline — both
directions — and the comm cost model on CPU in a couple of minutes).
"""
import argparse
import os
import subprocess
import sys
import time

from repro.compile_cache import use_persistent_cache

from . import (bench_exec_time, bench_speedup, bench_efficiency,
               bench_karpflatt, bench_flops, bench_comm, bench_roofline,
               bench_ata, bench_grads, bench_gram_service,
               bench_distributed)

ALL = [
    ("fig5_exec_time", bench_exec_time.run),
    ("fig6_speedup", bench_speedup.run),
    ("fig7_efficiency", bench_efficiency.run),
    ("fig8_karpflatt", bench_karpflatt.run),
    ("s31_flops", bench_flops.run),
    ("s5_comm", bench_comm.run),
    ("roofline", bench_roofline.run),
    ("ata_fused", bench_ata.run),
    ("grads", bench_grads.run),
    ("gram_service", bench_gram_service.run),
    ("distributed", bench_distributed.run),
]

SMOKE_TESTS = ["tests/test_fused_ata.py", "tests/test_fused_grads.py",
               "tests/test_kernels.py",
               "tests/test_core_ata.py", "tests/test_gram_stream.py",
               "tests/test_gram_engine.py", "tests/test_comm_cost.py"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="run the interpret-mode kernel tests plus the "
                         "quick distributed comm benchmark and exit")
    args = ap.parse_args(argv)
    use_persistent_cache()
    if args.smoke:
        # multidevice-marked tests are excluded (they pay a child
        # interpreter each and run in CI's dedicated multidevice job);
        # the quick distributed bench below is the multi-device signal
        # the interpret-mode suite is a CPU target: keep its child off
        # the chip
        rc = subprocess.call(
            [sys.executable, "-m", "pytest", "-q",
             "-m", "not multidevice", *SMOKE_TESTS],
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if rc == 0:
            bench_distributed.run(quick=True)
            bench_grads.run(quick=True)
        sys.exit(rc)
    failures = []
    for name, fn in ALL:
        if args.only and args.only not in name:
            continue
        print(f"\n=== {name} {'='*(60-len(name))}")
        t0 = time.perf_counter()
        try:
            fn(quick=args.quick)
            print(f"--- {name} ok in {time.perf_counter()-t0:.1f}s")
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            import traceback
            traceback.print_exc()
    if failures:
        print("\nFAILED:", failures)
        sys.exit(1)
    print("\nALL BENCHMARKS OK")


if __name__ == "__main__":
    main()
