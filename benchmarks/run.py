"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see DESIGN.md §6 for the index
mapping benchmarks to the paper's figures).
"""
from __future__ import annotations

import sys
import time


def main() -> int:
    from benchmarks import extra, paper_figures as pf
    from benchmarks.pipeline_bench import bench_pipeline
    from repro.compile_cache import enable_compile_cache

    benches = [
        pf.bench_sgb_scaling,      # Fig. 2
        pf.bench_buffer_hitrate,   # Fig. 3
        pf.bench_thrashing,        # Fig. 4
        pf.bench_overall_speedup,  # Fig. 12
        pf.bench_ctt_speedup,      # Fig. 14
        pf.bench_ctt_redundancy,   # Fig. 15
        pf.bench_gfp_speedup,      # Fig. 16
        pf.bench_dram_access,      # Fig. 17
        pf.bench_bandwidth_util,   # Fig. 18
        extra.bench_kernels,
        extra.bench_moe_dispatch,
        extra.bench_restructure_cost,
        bench_pipeline,           # frontend pipeline: host/device/cached
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for bench in benches:
        if only and only not in bench.__name__:
            continue
        t0 = time.time()
        try:
            for line in bench():
                print(line, flush=True)
        except Exception as e:  # noqa: BLE001 — run the rest, then fail
            failed.append(bench.__name__)
            print(f"{bench.__name__},0.0,ERROR:{type(e).__name__}:{e}")
        print(f"# {bench.__name__} took {time.time() - t0:.1f}s", flush=True)
    if failed:
        print(f"# failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
