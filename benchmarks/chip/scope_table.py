#!/usr/bin/env python3
"""Where a forward cell's device time goes, scope by scope, on the chip.

    python3 benchmarks/chip/scope_table.py --workload imdb-shgn.forward \\
        --seed 7 --seconds 10

In one process: the cell's set-up, one untraced window and one traced
window of the same forwards (so the two ``forward_ms`` give the cost of
tracing), then the program's map from each instruction of its compiled
forward to its scope (``repro.obs.forward_scopes``, timed), joined with
the trace.  Prints one JSON object: per scope the kernels' and the other
ops' device ms per forward, per stage (FP, NA kernels, NA glue, SF, head,
unscoped) the same, the op kinds in each scope, the packing counts and
the frontend's stage times.

``--scale`` cuts the configuration's graph, ``--ahead-s`` sets how many
seconds of forwards the window keeps in flight, and ``--keep DIR`` keeps
the trace as ``DIR/scoped.xplane.pb`` beside its map
``DIR/scoped.scopes.json``: that is how the recorded trace the tests read
was made.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import drivers, runner, scopes, tracing  # noqa: E402
from chipbench.spec import load_cell  # noqa: E402


def table(red: tracing.Reduction, scope_of, forwards: int) -> dict:
    """Per scope and per stage, device ms per forward; op kinds per scope."""
    per = 1e3 / forwards
    ops = red.op_seconds
    kernel = scopes.seconds_by_scope(
        {op: s for op, s in ops.items() if scopes.is_kernel(op)}, scope_of)
    other = scopes.seconds_by_scope(
        {op: s for op, s in ops.items() if not scopes.is_kernel(op)}, scope_of)
    rows = {sc or "unscoped": {"kernel_ms": kernel.get(sc, 0.0) * per,
                               "other_ms": other.get(sc, 0.0) * per}
            for sc in set(kernel) | set(other)}
    kinds = {}
    for op, secs in ops.items():
        kk = kinds.setdefault(scope_of.get(op) or "unscoped", {})
        kk[tracing.base_name(op)] = kk.get(tracing.base_name(op), 0.0) + secs * per
    stages = scopes.stage_seconds(ops, scope_of)
    return {
        "busy_ms": red.busy_s * per,
        "ops_ms": sum(ops.values()) * per,
        "scoped_share": scopes.scoped_share(ops, scope_of),
        "stages_ms": {k: v * per for k, v in stages.items()},
        "scopes_ms": dict(sorted(rows.items())),
        "kinds_ms": {sc: dict(sorted(kk.items(), key=lambda kv: -kv[1]))
                     for sc, kk in sorted(kinds.items())},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--ahead-s", type=float, default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    from repro import obs

    cell = load_cell(args.workload)
    if cell.kind != "forward" or runner.accelerator(cell.chips) is None:
        return 2
    runner.enable_compile_cache()
    cfg = cell.config if args.scale is None else dict(cell.config, scale=args.scale)
    traffic = (cell.traffic if args.ahead_s is None
               else dict(cell.traffic, ahead_s=args.ahead_s))
    ctx = drivers.Context(cfg)
    drv = drivers.make_driver(ctx, traffic)
    drv.prepare(args.seed)
    drv.warm(args.seconds)
    plain = drv.window(args.seconds, trace=False)
    trace_dir = tempfile.mkdtemp(prefix="scope-table-")
    try:
        import jax

        jax.profiler.start_trace(trace_dir)
        try:
            win = drv.window(args.seconds, trace=True)
        finally:
            jax.profiler.stop_trace()
        path = tracing.find_xplane(trace_dir)
        red = tracing.reduce(tracing.load(path))
        t0 = time.perf_counter()
        scope_of = obs.forward_scopes(ctx.compiled)
        map_s = time.perf_counter() - t0
        if args.keep:
            keep = Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copyfile(path, keep / "scoped.xplane.pb")
            (keep / "scoped.scopes.json").write_text(
                json.dumps(dict(sorted(scope_of.items())), indent=0) + "\n")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = {
        "workload": cell.name, "seed": args.seed, "scale": cfg["scale"],
        "forward_ms_untraced": plain["e2e"]["forward_ms"],
        "forward_ms_traced": win["e2e"]["forward_ms"],
        "forwards_traced": win["forwards"], "scope_map_s": map_s,
        "instructions_mapped": len(scope_of),
        "packing": ctx.compiled.packing_counts(),
        "frontend_s": dict(ctx.compiled.frontend.timings),
        **table(red, scope_of, win["forwards"]),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
