"""Readings a cell's limits are set from: the program's numbers over many
seeds, and beside them the control's (the reference in bfloat16 in the
program's place) and each planted fault's, all at the cell's own size, in
one process.  Not run by the benchmark; a builder runs it on the chip:

    python benchmarks/tools/calibrate.py --workload wd-train-zipf --seeds 12 \\
        --seconds 2 --variants bf16,half_batch

and writes ``chiprun_out/calibrate-<workload>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--variants", default="bf16")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    from benchmarks import run as bench_run
    from benchmarks.harness.manifest import Manifest

    man = Manifest(ROOT)
    cell = man.workload(args.workload)
    device = bench_run.require_chips(cell["chips"])
    variants = [v for v in args.variants.split(",") if v]
    readings = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        result = bench_run.run_cell(man, cell, device, seed, args.seconds,
                                       False, t0, calibrate=variants)
        row = {"seed": seed, "correct": result["correct"],
               "failed": result["failed"], "attempted": result["attempted"],
               "program": {k: c["value"] for k, c in result["checks"].items()},
               "variants": result["extra"]["variants"],
               "seconds": time.perf_counter() - t0}
        readings.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"calibrate-{args.workload}.json"), "w") as f:
        json.dump(readings, f, indent=1)
    names = sorted(readings[0]["program"])
    for n in names:
        vals = [r["program"][n] for r in readings if r["program"][n] is not None]
        line = f"{n}: program max {max(vals):.3e} over {len(vals)} seeds"
        for var in readings[0]["variants"]:
            vv = [r["variants"][var].get(n) for r in readings]
            vv = [x for x in vv if x is not None]
            if vv:
                line += f"; {var} min {min(vv):.3e}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
