#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds, for the program and
for the control, in one process: the readings its limits are set from.

    python bench/calibrate.py --workload miranda-sweep \\
        --seeds 101,102,...,112 --control-seeds 101,102,103 --seconds 3

Per seed: the cell's set-up, a short window of the cell's own traffic,
and the comparison with the reference (the program's reading).  On the
control seeds, also the control's reading (the cell driver's
``control``: the computation one precision step below the
configuration's float32, bfloat16, standing in the program's place) and
a fault's (the driver's ``fault``: every compared answer handed to
another row or run).  Prints one JSON line per seed and a last line
with, per number, the lower reading (the largest the program gave),
the upper one (the smallest the control gave) and the smallest the
fault gave.  Needs the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    import jax
    try:
        devs = R.chips(jax, int(cell.spec["chips"]), True)
    except R.NoChip as e:
        R.log(f"calibrate: {e}")
        return 2
    R.enable_compile_cache(jax)
    drv = R.load_module(R.find(R.ROOT, "drivers", cell.mix["driver"], ".py"))
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    lower, upper, faults = {}, {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = R.Context(cell, seed, False, devs, None,
                        clock=R.CompileClock(jax))
        st = drv.setup(ctx)
        res = drv.window(st, args.seconds, ctx)
        kept = drv.release(st, ctx)
        del st
        gc.collect()
        row = {"seed": seed, "attempted": res["attempted"],
               "program": drv.check(kept, ctx)}
        for k, v in row["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        if seed in control_seeds:
            row["control"] = drv.control(kept, ctx)
            for k, v in row["control"].items():
                upper[k] = min(upper.get(k, float("inf")), v)
            row["fault"] = drv.fault(kept, ctx)
            for k, v in row["fault"].items():
                faults[k] = min(faults.get(k, float("inf")), v)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "fault": faults}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
