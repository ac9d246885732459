"""One run of one benchmark cell, from the root of a checkout:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` and its files (see
``bench/cell.py``), refuses to run anywhere but on Mosaic kernels on a
TPU with the chips the cell asks for, and hands the cell to its driver.
The driver makes the inputs and weights from ``--seed``, warms up, runs
the measured window of ``--seconds``, checks the window's program against
the plain reference, and returns the result.  The last line of standard
output is that result as one JSON object; the numbers compared, each
beside its limit, are the last lines of standard error and the last key
of the result.  With ``--trace 1`` the window is profiled and the result
carries the cell's per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def run_cell(name: str, seed: int, seconds: float, trace: bool) -> dict:
    import jax

    from bench import cell as cells
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    cell = cells.load(name)
    harness.preflight(cell.chips)
    use_compile_cache()
    # every program, however quick to compile, comes from the cache after
    # a cell's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell.driver.run(cell, seed, seconds, trace, T_START)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    from bench.cell import CellError

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (harness.NoChip, CellError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    parts = result.pop("setup_parts", None)
    if parts:
        print("setup parts: " + json.dumps(parts), file=sys.stderr)
    checks = result.pop("checks")
    harness.report_checks(checks)
    result["checks"] = checks  # the last key of the line
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
