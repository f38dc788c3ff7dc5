"""Benchmark driver — one section per paper table/figure plus the
kernel microbenches and the dry-run roofline summary.

  PYTHONPATH=src python -m benchmarks.run              # quick preset
  PYTHONPATH=src python -m benchmarks.run --preset mid # EXPERIMENTS.md scale
  PYTHONPATH=src python -m benchmarks.run --only table1,kernels

Prints ``name,...`` CSV rows (cached FL traces under experiments/paper/).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", default="quick",
                    choices=["quick", "mid", "paper"])
    ap.add_argument("--datasets", default="mnist,cifar")
    ap.add_argument("--only",
                    default="table1,table2,fig1,kernels,round,roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="run the tier-1 test command (the CI hook) and "
                         "exit with its status")
    args = ap.parse_args()
    if args.smoke:
        env = dict(os.environ)
        env["PYTHONPATH"] = "src" + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        raise SystemExit(subprocess.call(
            [sys.executable, "-m", "pytest", "-x", "-q"], env=env))
    only = set(args.only.split(","))
    datasets = args.datasets.split(",")

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import fig1, kernel_bench, round_bench, roofline_bench, table1, \
        table2

    for ds in datasets:
        if "table1" in only:
            rows = table1.run(ds, preset=args.preset)
            table1.emit(rows)
        if "table2" in only:
            rows = table2.run(ds, preset=args.preset)
            table2.emit(rows)
        if "fig1" in only:
            rows = fig1.run(ds, preset=args.preset)
            fig1.emit(rows)
    if "kernels" in only:
        kernel_bench.run()
    if "round" in only:
        round_bench.run()
    if "roofline" in only:
        roofline_bench.run()
    sys.stdout.flush()


if __name__ == "__main__":
    main()
