"""Replay a seeded traffic trace against the async HGNN serving engine.

The driver loads a committed trace config (``benchmarks/traces.py``
schema ``serve_trace_config/v1``: the workload *and* the ``ServePolicy``
to serve it under), expands it into its deterministic event list,
registers the tenant mix on one ``HGNNServeEngine``, and replays the
events on the wall clock — submits at their virtual arrival times,
``swap_params``/``swap_graph`` hot-swaps and armed fault injections at
their scheduled times.  It then resolves every future and emits a
``serve_trace/v1`` JSON point:

* end-to-end latency percentiles (``latency_ms.p50/p95/p99``) with the
  queueing-vs-compute split (``queue_ms``/``compute_ms``);
* the batching factor (requests per compiled forward) and the window
  counters (``window_timeouts``/``early_closes``);
* shed/degraded/retry counts and ``goodput`` — the fraction of
  *feasible* requests (deadline not scheduled-expired by the trace)
  that resolved to a response;
* ``unrecovered_fraction`` — feasible requests whose future resolved to
  neither a response nor a deadline shed (baseline 0.0: the zero
  baseline admits no regression at any tolerance).

``check_regression.py`` gates ``latency_ms.p99``, ``1 - goodput``, and
``unrecovered_fraction`` against the committed scale-0.15 baseline.

Run::

    PYTHONPATH=src:. python benchmarks/serve_bench.py \\
        benchmarks/trace_configs/serve_ci_scale0.15.json [out.json] [--time-scale 1.0]
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.traces import TraceConfig, generate_trace, load_config
from repro.api import ExecutorSpec, ServePolicy, Session
from repro.compile_cache import enable_compile_cache
from repro.core.hgnn import HGNNConfig
from repro.hetero import GraphDelta, make_dataset
from repro.serve import DeadlineExceeded, FaultInjector, HGNNRequest, HGNNServeEngine
from repro.serve.faults import TransientFault

HIDDEN = 32
NUM_CLASSES = 3


def _percentiles(values_us: List[float]) -> Optional[Dict[str, float]]:
    """``{p50, p95, p99, mean}`` in milliseconds, or ``None`` when empty."""
    if not values_us:
        return None
    arr = np.asarray(values_us) / 1e3
    return {
        "p50": float(np.percentile(arr, 50)),
        "p95": float(np.percentile(arr, 95)),
        "p99": float(np.percentile(arr, 99)),
        "mean": float(arr.mean()),
    }


def _register_tenants(engine: HGNNServeEngine, cfg: TraceConfig) -> Dict:
    """Register the trace's tenant mix; returns per-tenant replay state
    (the handle plus the off-path relation's id bounds for swap deltas).
    """
    graphs = {}
    tenants = {}
    for ts in cfg.tenants:
        if ts.dataset not in graphs:
            graphs[ts.dataset] = make_dataset(ts.dataset, seed=0, scale=cfg.scale)
        graph = graphs[ts.dataset]
        handle = engine.register(
            ts.name,
            graph,
            list(ts.targets),
            HGNNConfig(
                model=ts.model,
                hidden=HIDDEN,
                num_layers=2,
                num_classes=NUM_CLASSES,
                target_type=ts.target_type,
            ),
        )
        state = {"spec": ts, "handle": handle, "swaps": 0}
        if ts.offpath_relation:
            rel = graph.relations[ts.offpath_relation]
            state["offpath_bounds"] = (rel.num_src, rel.num_dst)
        tenants[ts.name] = state
    return tenants


def _warm_subset_buckets(engine: HGNNServeEngine, cfg: TraceConfig) -> None:
    """Trace the subset-forward buckets the replay will hit, outside the
    timed window (requests draw ``subset_min..subset_max`` ids and
    groups union up to ``num_nodes``, so the padded-bucket ladder from
    ``bucket_min`` up to ``num_nodes``'s bucket gets one tracing forward
    each — replay latency then measures serving, not jit).
    """
    for ts in cfg.tenants:
        size = engine.policy.bucket_min
        while True:
            n = min(size, ts.num_nodes)
            engine.submit(HGNNRequest(-1, ts.name, nodes=np.arange(n, dtype=np.int64)))
            engine.step()
            if size >= ts.num_nodes:
                break
            size *= 2


def replay(
    cfg: TraceConfig, policy: ServePolicy, *, time_scale: float = 1.0, seed_offset: int = 1000
) -> Dict:
    """Run one trace against a fresh engine and return the
    ``serve_trace/v1`` point (see the module docstring for the fields).

    ``time_scale`` compresses the virtual clock (2.0 replays a trace in
    half its virtual duration — arrival *pattern* preserved, absolute
    rates doubled); the committed CI trace replays at 1.0.
    """
    events = generate_trace(cfg)
    session = Session(ExecutorSpec())
    injector = FaultInjector(seed=cfg.seed)
    engine = HGNNServeEngine(session=session, policy=policy, faults=injector)
    tenants = _register_tenants(engine, cfg)
    _warm_subset_buckets(engine, cfg)
    delta_rng = np.random.default_rng(cfg.seed)
    stats0 = engine.stats()

    engine.run()
    submitted: List = []  # (event, future)
    t0 = time.perf_counter()
    for ev in events:
        lag = ev.t / time_scale - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        if ev.kind == "request":
            req = HGNNRequest(
                ev.rid,
                ev.tenant,
                nodes=np.asarray(ev.nodes, dtype=np.int64),
                deadline_ms=ev.deadline_ms,
            )
            submitted.append((ev, engine.submit(req)))
        elif ev.kind == "swap_params":
            state = tenants[ev.tenant]
            state["swaps"] += 1
            state["handle"].swap_params(state["handle"].compiled.init(seed_offset + state["swaps"]))
        elif ev.kind == "swap_graph":
            state = tenants[ev.tenant]
            num_src, num_dst = state["offpath_bounds"]
            delta = GraphDelta.insert(
                state["spec"].offpath_relation,
                delta_rng.integers(0, num_src, 4),
                delta_rng.integers(0, num_dst, 4),
            )
            state["handle"].swap_graph(delta)
        elif ev.kind == "fault":
            injector.inject(ev.site, exc=TransientFault(f"trace fault @ {ev.t:.3f}s"), times=1)

    latency_us: List[float] = []
    queue_us: List[float] = []
    compute_us: List[float] = []
    served = shed_scheduled = shed_deadline = failed = feasible = 0
    for ev, fut in submitted:
        scheduled_expired = ev.deadline_ms is not None and ev.deadline_ms <= 0
        feasible += 0 if scheduled_expired else 1
        try:
            resp = fut.result(timeout=120)
        except DeadlineExceeded:
            if scheduled_expired:
                shed_scheduled += 1
            else:
                shed_deadline += 1
            continue
        except Exception:
            failed += 1
            continue
        served += 1
        latency_us.append(resp.latency_us)
        queue_us.append(resp.queue_us)
        compute_us.append(resp.compute_us)
    engine.stop()
    wall_s = time.perf_counter() - t0
    stats1 = engine.stats()

    def _delta(key: str) -> float:
        return stats1[key] - stats0[key]

    forwards = max(1, int(_delta("forwards")))
    point = {
        "schema": "serve_trace/v1",
        "scale": cfg.scale,
        "trace_id": (
            f"seed{cfg.seed}-{cfg.arrival}-{cfg.rate_rps:g}rps-"
            f"{cfg.duration_s:g}s-{len(cfg.tenants)}t"
        ),
        "requests": len(submitted),
        "latency_ms": _percentiles(latency_us),
        "queue_ms": _percentiles(queue_us),
        "compute_ms": _percentiles(compute_us),
        "batching": {
            "factor": _delta("requests_served") / forwards,
            "forwards": int(_delta("forwards")),
            "window_timeouts": int(_delta("window_timeouts")),
            "early_closes": int(_delta("early_closes")),
        },
        "counts": {
            "submitted": len(submitted),
            "served": served,
            "shed_scheduled": shed_scheduled,
            "shed_deadline": shed_deadline,
            "failed": failed,
            "retries": int(_delta("retries")),
            "degraded_steps": int(_delta("degraded_steps")),
        },
        "goodput": served / feasible if feasible else 1.0,
        "unrecovered_fraction": failed / feasible if feasible else 0.0,
        "replay": {"time_scale": time_scale, "wall_s": wall_s},
    }
    return point


def main(argv: Optional[List[str]] = None) -> int:
    """CLI: replay a committed trace config, print the headline numbers,
    and (optionally) write the ``serve_trace/v1`` point for the gate.
    """
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace_config", help="serve_trace_config/v1 JSON (workload + policy)")
    ap.add_argument("out_json", nargs="?", help="where to write the serve_trace/v1 point")
    ap.add_argument(
        "--time-scale",
        type=float,
        default=1.0,
        help="virtual-clock compression (2.0 = replay twice as fast)",
    )
    args = ap.parse_args(argv)

    cfg, policy_kwargs = load_config(args.trace_config)
    policy = ServePolicy(**policy_kwargs)
    point = replay(cfg, policy, time_scale=args.time_scale)

    lat = point["latency_ms"] or {}
    counts = point["counts"]
    print("name,value,derived")
    print(f"serve_trace/requests,{point['requests']},trace {point['trace_id']}")
    for q in ("p50", "p95", "p99"):
        print(f"serve_trace/latency_{q}_ms,{lat.get(q, float('nan')):.3f},")
    print(f"serve_trace/batching_factor,{point['batching']['factor']:.3f},")
    print(
        f"serve_trace/goodput,{point['goodput']:.4f},"
        f"served={counts['served']} shed_sched={counts['shed_scheduled']} "
        f"shed_deadline={counts['shed_deadline']} failed={counts['failed']}"
    )
    if args.out_json:
        with open(args.out_json, "w") as f:
            json.dump(point, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.out_json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
