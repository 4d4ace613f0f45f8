"""One run of one cell: set-up, window, trace reduction, check, result."""
from __future__ import annotations

import gc
import os
import sys
import time
from typing import Dict, Optional, Tuple

import numpy as np

from chipbench import check, drivers, peaks, tracing, work
from chipbench.spec import ROOT, Cell, load_metric


def accelerator(chips: int) -> Optional[Dict]:
    """The device JAX reports, or None (with the reason on stderr) when
    it is not a TPU or has fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chipbench: JAX found no TPU (platform {d.platform!r}); nothing was run",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX reports {len(devs)}",
              file=sys.stderr)
        return None
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache`` (a fixed path: the path is part of the
    cache's key).  Every program is cached, however fast it compiles, so
    a repeated run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def work_counts(ctx: drivers.Context) -> Dict:
    shapes = ctx.na_shapes()
    edges = {mp: s[0] for mp, s in shapes.items()}
    return {"forward_flops": work.forward_flops(ctx.cfg, ctx.nv, edges),
            "na_calls": work.na_kernel_work(ctx.cfg, ctx.nv, shapes)}


def run_cell(cell: Cell, seed: int, seconds: float, trace_dir: Optional[str], *,
             t_start: float, device: Dict, fault=None) -> Tuple[Dict, Dict]:
    """Run ``cell`` once; returns ``(result line, checks)``."""
    import jax

    marks = [("start", t_start), ("imports", time.perf_counter())]
    ctx = drivers.Context(cell.config)
    marks.append(("graph and frontend", time.perf_counter()))
    drv = drivers.make_driver(ctx, cell.traffic, fault)
    drv.prepare(seed)
    marks.append(("weights and data", time.perf_counter()))
    drv.warm(seconds)
    # what set-up made stays alive through the window: keep the
    # collector from walking it there
    gc.collect()
    gc.freeze()
    marks.append(("warm-up and compiles", time.perf_counter()))
    setup_s = marks[-1][1] - t_start
    print("set-up: " + ", ".join(f"{name} {b - a:.3f} s" for (_, a), (name, b)
                                 in zip(marks, marks[1:])), file=sys.stderr)
    full_before = gc.get_stats()[2]["collections"]
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        win = drv.window(seconds, trace=bool(trace_dir))
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    gc.unfreeze()
    print(f"window: {gc.get_stats()[2]['collections'] - full_before} full collections",
          file=sys.stderr)
    mem = memory_peak_bytes()
    drv.release()
    produced = drv.produced()
    readings = drv.check(produced)
    correct, checks = check.verdict(readings, cell.limits)
    correct = correct and win["attempted"] > 0 and win["failed"] == 0

    dev = dict(device, memory_peak_bytes=mem)
    result = {"correct": correct, "attempted": int(win["attempted"]),
              "failed": int(win["failed"])}
    if trace_dir:
        red = tracing.reduce(tracing.load(tracing.find_xplane(trace_dir)))
        run = {"cell": cell.name, "kind": cell.kind, "window": win, "trace": red,
               "peak": peaks.peak_for(device["kind"]), "work": work_counts(ctx)}
        metrics = {}
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
        result.update(metrics=metrics, device=dev, breakdown=tracing.breakdown(red))
    else:
        values = dict(win["e2e"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if values.get(m["name"]) is not None}
        result.update(metrics=metrics, device=dev)
    result["checks"] = checks
    return result, checks


def percentile(values, q: float) -> Optional[float]:
    v = np.asarray(values, np.float64)
    v = v[np.isfinite(v)]
    return float(np.percentile(v, q)) if v.size else None
