#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 benchmarks/chip/run.py --workload imdb-shgn.forward --seed 7 \\
        --seconds 10 --trace 0

Loads the cell named in ``BENCHMARK.json`` (its configuration and traffic
from the files under this directory), builds the graph and the program's
compiled model, draws weights and traffic from ``--seed``, warms every
shape the traffic uses, measures for ``--seconds``, then checks what the
timed path produced against the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1`` a
``breakdown``), and last ``checks``, each compared number beside its
limit; the same numbers are the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window with the JAX profiler and reports its per-layer
metrics.  With no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import runner  # noqa: E402
from chipbench.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    cell = load_cell(args.workload)
    device = runner.accelerator(cell.chips)
    if device is None:
        return 2
    runner.enable_compile_cache()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    try:
        result, checks = runner.run_cell(cell, args.seed, args.seconds, trace_dir,
                                         t_start=T_START, device=device)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: nothing may print after the result line
    os._exit(code)
