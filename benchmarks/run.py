"""Benchmark entrypoint: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only table2,table7

Output contract: CSV blocks on stdout (one per table; benchmarks/common.py).
The table8 bench additionally writes ``results/BENCH_kernels.json`` — the
machine-readable per-(method × kernel-mode) walltime + bytes-moved record
used to track the fused-kernel perf trajectory across PRs.
"""
from __future__ import annotations

import argparse
import time

BENCHES = ["table2", "table7", "table8", "table345", "fig4", "appA2", "qspsa",
           "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset of " + ",".join(BENCHES))
    ap.add_argument("--fast", action="store_true", help="shrink training-based benches")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(BENCHES)

    t0 = time.time()
    if "table2" in only:
        from benchmarks import table2_elements

        table2_elements.run()
    if "table7" in only:
        from benchmarks import table7_memory

        table7_memory.run()
    if "table8" in only:
        from benchmarks import table8_walltime

        table8_walltime.run()
    if "table345" in only:
        from benchmarks import table345_accuracy

        table345_accuracy.run(steps=40 if args.fast else 100,
                              seeds=(0,) if args.fast else (0, 1))
    if "fig4" in only:
        from benchmarks import fig4_loss_curves

        fig4_loss_curves.run(steps=40 if args.fast else 120)
    if "appA2" in only:
        from benchmarks import appA2_separable_error

        appA2_separable_error.run()
    if "qspsa" in only:
        from benchmarks import qspsa_variance

        qspsa_variance.run()
    if "roofline" in only:
        from benchmarks import roofline

        roofline.run()
    print(f"# benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
