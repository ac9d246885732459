"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py <cell> <mode> <seed> [<seed> ...] [--seconds S]

For each seed, one line of JSON with the numbers the cell's comparison
takes.  ``mode`` is "program" (the sound program: the lower readings),
"control" (the plain reference computed with fp8 matrix products in the
program's place), or a fault planted under the timed path ("half_batch"
or "unchanged" for training cells, "altered_token" for serving cells).
No window is measured: a training cell reads its first steps, a serving
cell serves ``--seconds`` of its traffic.  All seeds run in one process,
so the programs compile once.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("mode")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax

    from bench import cell as cells
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    cell = cells.load(args.cell)
    harness.preflight(cell.chips)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    drv = cell.driver
    kind = cell.traffic["driver"]
    prog = None
    if kind == "zo" and args.mode not in ("control",):
        prog = drv.Program(cell, None if args.mode == "program"
                           else args.mode)
    for seed in args.seeds:
        t0 = time.perf_counter()
        if kind == "zo":
            got, ref = drv.readings(cell, seed, args.mode, prog)
            nums = {k: v["value"] for k, v in
                    drv.compare(got, ref, cell.limits).items()}
            nums["losses"] = got["losses"]
            nums["ref_losses"] = ref["losses"]
            nums["leaves"] = drv.leaf_gaps(got, ref)
        else:
            gap, n = drv.readings(cell, seed, args.seconds, args.mode)
            nums = {"logit_gap": gap, "tokens": n}
        print(json.dumps({"cell": args.cell, "mode": args.mode,
                          "seed": seed, **nums,
                          "s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
