#!/usr/bin/env python3
"""Read the numbers a cell's correctness limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload imdb-shgn.forward \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 21,22,23 \\
        --fault-seeds 31,32,33 --seconds 2

In one process, so that set-up is paid once: for each of ``--seeds`` the
program's readings (the same timed path and check as a run, with a short
window); for each of ``--control-seeds`` the control's readings (the
reference computed in the precision below the configuration's, put in
the program's place); for each of ``--fault-seeds`` and each fault the
cell can have (``chipbench.faults``) the faulty program's readings.
Prints one JSON line per reading and a summary last: the largest
program reading (the lower end of a limit) and the smallest control and
fault readings (candidates for the upper end).  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import check, drivers, faults, runner  # noqa: E402
from chipbench.spec import load_cell  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def readings(drv, seed: int, seconds: float, control: bool = False):
    drv.prepare(seed)
    drv.warm(seconds)
    drv.window(seconds, trace=False)
    out = drv.control() if control else drv.produced()
    return drv.check(out), diagnostics(drv, out)


def diagnostics(drv, out):
    """What is not compared but shows where a reading comes from: the
    bare and widest logit gaps and the reference's reorder noise."""
    want = drivers._ref_logits(drv, "highest")
    noise = check.rms_gap(drivers._ref_logits(drv, "highest", reordered=True), want)
    return {"rms_gap": check.rms_gap(out, want), "reorder_noise": noise,
            "widest_gap": check.widest_gap(out, want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    if runner.accelerator(cell.chips) is None:
        return 2
    runner.enable_compile_cache()
    ctx = drivers.Context(cell.config)
    runs = [("program", drivers.no_fault, s, False) for s in args.seeds]
    runs += [("control", drivers.no_fault, s, True) for s in args.control_seeds]
    for name in faults.FAULTS_BY_ENTRY[cell.kind]:
        runs += [(name, faults.FAULTS[name], s, False) for s in args.fault_seeds]
    summary = {}
    built = {}
    for who, fault, seed, control in runs:
        if who not in built:
            built[who] = drivers.make_driver(ctx, cell.traffic, fault)
        r, diag = readings(built[who], seed, args.seconds, control)
        print(json.dumps({"who": who, "seed": seed, "readings": r, "diagnostics": diag}),
              flush=True)
        for k, v in r.items():
            lo, hi = summary.setdefault(who, {}).get(k, (v, v))
            summary[who][k] = (min(lo, v), max(hi, v))
    print(json.dumps({"summary": {w: {k: {"min": lo, "max": hi} for k, (lo, hi) in m.items()}
                                  for w, m in summary.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
