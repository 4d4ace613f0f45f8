#!/usr/bin/env python3
"""Bring-up check: the banded HGNN main path on a TPU, end to end.

    python chip_smoke.py             # one chip: forward parity, fit, serving
    python chip_smoke.py --chips 4   # four chips: the sharded forward only

It drives the public API only (``Session`` / ``CompiledHGNN`` /
``HGNNServeEngine``) on synthetic ACM at the paper's full size
(``scale=1.0``, features 1902-d) with the paper's §5.3 models — rgat and
rgcn with 3 layers, Simple-HGN with 2, hidden 64 — and random weights
drawn from ``--seed``.  Phases on one chip:

  forward  each model on the banded executor (compiled Pallas NA kernels,
           ``tpu_custom_call`` asserted in its HLO) against the f32 jnp
           executor;
  fit      three ``CompiledHGNN.fit`` epochs of rgat on the banded
           executor (custom VJPs on the chip): finite, non-rising loss;
  serve    ``HGNNServeEngine.run()`` answering full and dependency-subset
           requests, every row checked against the full forward.

With ``--chips 4`` it runs only the sharded forward (``shard="relation"``
and ``"edge_block"`` over four chips) against the single-chip banded
forward.  Every phase runs at ``"highest"`` matmul precision, so each
comparison is of f32 results and not of the MXU's default bf16 passes.
Any failed phase exits non-zero; with no TPU the script exits
non-zero before doing any work.  Lines that start with ``info`` are
informational (compile seconds are set-up time, not a measurement).  The
last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DATASET = "ACM"
TARGETS = ("PAP", "PSP", "PTP")
TARGET_TYPE = "P"
MODELS = (("rgat", 3), ("rgcn", 3), ("shgn", 2))  # paper §5.3, hidden 64
# max |out - ref| <= REL_TOL * max(1, max |ref|): f32 reassociation across
# three layers, far below any precision-loss fault (a bf16 rounding of
# the kernel's feature operand shows up at ~1e-3)
REL_TOL = 1e-4


def _cfg(model: str, layers: int):
    from repro.core.hgnn import HGNNConfig

    return HGNNConfig(model=model, hidden=64, num_layers=layers,
                      num_classes=3, target_type=TARGET_TYPE)


def _compare(name: str, got, want) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite output")
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    scale = max(1.0, float(np.max(np.abs(want))) if want.size else 0.0)
    print(f"{name}: max_abs_err {err:.3e}  rel_err {err / scale:.3e}  "
          f"(tolerance rel {REL_TOL:.0e})")
    if err > REL_TOL * scale:
        raise AssertionError(f"{name}: rel_err {err / scale:.3e} > {REL_TOL:.0e}")


def _timed_forward(name: str, compiled, params, feats):
    """AOT-compile ``compiled.forward``; returns (executable, output)."""
    import jax

    t0 = time.perf_counter()
    exe = jax.jit(compiled.forward).lower(params, feats).compile()
    compile_s = time.perf_counter() - t0
    out = exe(params, feats).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        exe(params, feats).block_until_ready()
    warm_ms = (time.perf_counter() - t0) / 5 * 1e3
    print(f"info {name}: compile {compile_s:.2f} s, warm forward {warm_ms:.3f} ms")
    return exe, out


def phase_forward(graph, feats, banded, ref, seed: int) -> None:
    for model, layers in MODELS:
        cfg = _cfg(model, layers)
        cb = banded.compile(graph, TARGETS, cfg)
        cj = ref.compile(graph, TARGETS, cfg)
        params = cb.init(seed)
        exe, out = _timed_forward(f"forward {model}", cb, params, feats)
        want = cj.forward(params, feats)
        if "tpu_custom_call" not in exe.as_text():
            raise AssertionError(f"forward {model}: no tpu_custom_call in the HLO")
        _compare(f"forward {model} banded vs jnp", out, want)


def phase_fit(graph, feats, banded, seed: int) -> None:
    import numpy as np

    from repro.train import propagated_feature_labels, semi_supervised_masks

    cb = banded.compile(graph, TARGETS, _cfg("rgat", 3))
    n = cb.num_target
    labels = propagated_feature_labels(cb.semantic, list(TARGETS),
                                       graph.features, n, seed=seed)
    masks = semi_supervised_masks(n, seed=seed)
    t0 = time.perf_counter()
    out = cb.fit(feats, labels, masks, epochs=3, seed=seed)
    losses = out["losses"]
    print(f"info fit rgat: 3 epochs in {time.perf_counter() - t0:.2f} s "
          f"(compile included)")
    print(f"fit rgat: losses {[round(x, 6) for x in losses]}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"fit: non-finite loss {losses}")
    if losses[-1] > losses[0]:
        raise AssertionError(f"fit: loss rose {losses[0]} -> {losses[-1]}")


def phase_serve(graph, feats, banded, seed: int) -> None:
    import numpy as np

    from repro.api import ServePolicy
    from repro.serve import HGNNRequest, HGNNServeEngine

    cfg = _cfg("rgat", 3)
    cb = banded.compile(graph, TARGETS, cfg)
    params = cb.init(seed)
    full = np.asarray(cb.forward(params, feats))
    # dependency_threshold=1.0: serve subsets through the k-hop closure
    # even when it covers most of this dense graph
    engine = HGNNServeEngine(session=banded, policy=ServePolicy(
        subset_mode="dependency", dependency_threshold=1.0))
    tenant = engine.register("acm", graph, list(TARGETS), cfg,
                             params=params, features=feats)
    rng = np.random.default_rng(seed)
    engine.run()
    try:
        t0 = time.perf_counter()
        subset = [rng.choice(cb.num_target, size=8, replace=False)
                  for _ in range(4)]
        futs = [tenant.submit(HGNNRequest(rid=i, nodes=ids))
                for i, ids in enumerate(subset)]
        resps = [f.result(timeout=600) for f in futs]
        futs = [tenant.submit(HGNNRequest(rid=4 + i)) for i in range(4)]
        resps += [f.result(timeout=600) for f in futs]
        print(f"info serve: 8 requests in {time.perf_counter() - t0:.2f} s "
              f"(compile included)")
    finally:
        engine.stop()
    modes = [r.mode for r in resps]
    print(f"serve: modes {modes}")
    if "dependency" not in modes or "full" not in modes:
        raise AssertionError(f"serve: expected dependency and full forwards, got {modes}")
    for r in resps:
        want = full if r.rid >= 4 else full[subset[r.rid]]
        _compare(f"serve request {r.rid} ({r.mode})", r.logits, want)


def phase_sharded(graph, feats, cache, seed: int) -> None:
    from repro.api import ExecutorSpec, Session

    single = Session(ExecutorSpec(na_executor="banded"), cache=cache)
    for mode in ("relation", "edge_block"):
        sharded = Session(ExecutorSpec(na_executor="banded", shard=mode,
                                       mesh_shape=(4,)), cache=cache)
        for model, layers in MODELS:
            cfg = _cfg(model, layers)
            cs = sharded.compile(graph, TARGETS, cfg)
            c1 = single.compile(graph, TARGETS, cfg)
            params = c1.init(seed)
            _, out = _timed_forward(f"sharded {mode} {model}", cs, params, feats)
            want = c1.forward(params, feats)
            _compare(f"sharded {mode} {model} vs single-chip banded", out, want)
        print(f"info sharded {mode}: load balance "
              f"{sharded.stats()['shard']['load_balance']:.3f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded forward over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: platform {device['platform']}  kind {device['kind']}  "
          f"count {device['count']}")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX reports {device['count']}", file=sys.stderr)
        return 1

    from repro.api import ExecutorSpec, Session, device_features
    from repro.compile_cache import enable_compile_cache
    from repro.hetero import make_dataset
    from repro.pipeline import SemanticGraphCache

    # a config value, not a context manager: the serve engine's worker
    # thread must see it too
    jax.config.update("jax_default_matmul_precision", "highest")
    print(f"info compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    graph = make_dataset(DATASET, seed=args.seed, scale=1.0)
    feats = device_features(graph)
    cache = SemanticGraphCache()
    banded = Session(ExecutorSpec(na_executor="banded"), cache=cache)
    banded.frontend(graph, TARGETS)
    print(f"info {DATASET} scale 1.0 {graph.num_vertices}: frontend "
          f"{time.perf_counter() - t0:.2f} s")

    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(graph, feats, cache, args.seed))]
    else:
        ref = Session(ExecutorSpec(), cache=cache)
        phases = [
            ("forward", lambda: phase_forward(graph, feats, banded, ref, args.seed)),
            ("fit", lambda: phase_fit(graph, feats, banded, args.seed)),
            ("serve", lambda: phase_serve(graph, feats, banded, args.seed)),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # noqa: BLE001 — report every phase, then fail
            failed.append(name)
            traceback.print_exc()
            print(f"phase {name} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        print(f"info phase {name}: {time.perf_counter() - t0:.2f} s")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": device["platform"],
                                             "kind": device["kind"],
                                             "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
