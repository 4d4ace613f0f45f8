"""HGNN training benchmark: the banded executor on the full workload.

PR 2 measured inference; this measures what the ROADMAP called the
"banded training path": per-epoch latency and convergence of
``CompiledHGNN.fit`` (the jitted semi-supervised step of
train/hgnn_step.py) compiled through a jnp-spec vs a banded-spec
``repro.api.Session`` — forward on the Pallas NA kernels, backward
through their custom VJPs over the same cached ``PackedEdges``.

Per dataset fixture (ACM/rgat, IMDB/shgn, DBLP/rgcn — all three model
families across the committed point):
  * per-epoch wall latency (p50 over post-compile epochs) per executor;
  * convergence: final loss and train/val/test accuracy on
    ``propagated_feature_labels`` (planted inside the GFP computation, so
    the task is learnable, not just memorizable);
  * the parity claims the CI gate tracks — banded-vs-jnp epoch-latency
    ratio, and banded accuracy >= jnp accuracy (identical seeds).

Run:  PYTHONPATH=src:. python benchmarks/train_bench.py [scale] [out_json]
          [--epochs N] [--datasets ACM,IMDB,DBLP]

Emits a ``BENCH_train.json`` trajectory point.  CI smokes ACM at reduced
scale/epochs and gates the latency ratio against the committed baselines
via ``benchmarks/check_regression.py``; the committed point is a full
three-dataset run at the default scale.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmarks.common import row
from repro.api import ExecutorSpec, Session, device_features
from repro.compile_cache import enable_compile_cache
from repro.core.hgnn import HGNNConfig
from repro.pipeline import SemanticGraphCache
from repro.train import propagated_feature_labels, semi_supervised_masks

# dataset -> (targets, target type, model family)
WORKLOADS: Dict[str, Tuple[List[str], str, str]] = {
    "ACM": (["APA", "PAP", "PSP"], "P", "rgat"),
    "IMDB": (["AMA", "MAM", "MDM"], "M", "shgn"),
    "DBLP": (["APA"], "A", "rgcn"),
}
HIDDEN = 32
LAYERS = 2
ACC_TARGET = 0.9  # train-split accuracy both executors must converge to


def bench_train(scale: float, epochs: int, datasets: List[str]
                ) -> Tuple[List[str], Dict]:
    from repro.pipeline.frontend import _dataset

    lines: List[str] = []
    point: Dict = {"schema": "train_bench/v1", "scale": scale,
                   "epochs": epochs, "datasets": {}}
    # one shared cache: the banded session's compile reuses every frontend
    # product the jnp session built (and packs exactly once) — both
    # executors train over the same cached artifacts, the repro.api way
    cache = SemanticGraphCache()
    sessions = {
        "jnp": Session(ExecutorSpec(planner="ctt", sgb_backend="host"),
                       cache=cache),
        "banded": Session(ExecutorSpec(planner="ctt", sgb_backend="host",
                                       na_executor="banded"), cache=cache),
    }
    for ds in datasets:
        targets, target_type, model_name = WORKLOADS[ds]
        graph = _dataset(ds, 0, float(scale))
        feats = device_features(graph)
        cfg = HGNNConfig(model=model_name, hidden=HIDDEN, num_layers=LAYERS,
                         num_classes=3, target_type=target_type)
        compiled = {b: s.compile(graph, targets, cfg)
                    for b, s in sessions.items()}
        n = graph.num_vertices[target_type]
        labels = propagated_feature_labels(
            compiled["jnp"].semantic, targets, graph.features, n)
        masks = semi_supervised_masks(n, seed=0)

        entry: Dict = {"model": model_name, "targets": targets}
        for backend, c in compiled.items():
            marks: List[float] = [time.perf_counter()]

            def mark(epoch: int, loss: float) -> None:
                marks.append(time.perf_counter())

            t0 = time.perf_counter()
            out = c.fit(feats, labels, masks, epochs=epochs,
                        epoch_callback=mark)
            total_s = time.perf_counter() - t0
            # first epoch pays jit compilation; p50 over the rest is the
            # steady-state per-epoch cost
            steady = np.diff(marks)[1:] if len(marks) > 2 else np.diff(marks)
            epoch_us = float(np.median(steady)) * 1e6
            entry[backend] = {
                "epoch_us_p50": epoch_us,
                "compile_s": float(marks[1] - marks[0]),
                "total_s": total_s,
                "final_loss": out["losses"][-1],
                "train_acc": out["train_acc"],
                "val_acc": out["val_acc"],
                "test_acc": out["test_acc"],
            }
            lines.append(row(
                f"train/{ds}/{model_name}/{backend}", epoch_us,
                f"epochs={epochs};train_acc={out['train_acc']:.3f};"
                f"val_acc={out['val_acc']:.3f}"))
        entry["latency_ratio_banded_vs_jnp"] = (
            entry["banded"]["epoch_us_p50"] / entry["jnp"]["epoch_us_p50"])
        entry["acc_parity"] = bool(
            entry["banded"]["train_acc"] >= entry["jnp"]["train_acc"] - 0.01
            and entry["banded"]["val_acc"] >= entry["jnp"]["val_acc"] - 0.02)
        entry["converged_to_target"] = bool(
            entry["banded"]["train_acc"] >= ACC_TARGET
            and entry["jnp"]["train_acc"] >= ACC_TARGET)
        point["datasets"][ds] = entry
    return lines, point


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("scale", nargs="?", type=float, default=0.15)
    ap.add_argument("out_json", nargs="?", default="BENCH_train.json")
    ap.add_argument("--epochs", type=int, default=60)
    ap.add_argument("--datasets", default="ACM,IMDB,DBLP")
    ap.add_argument("--require-target-acc", action="store_true",
                    help="also fail unless BOTH executors reach "
                    f"train_acc >= {ACC_TARGET} (the committed trajectory "
                    "point is generated with this; the few-epoch CI smoke "
                    "is not, since it cannot converge)")
    args = ap.parse_args()
    datasets = [d for d in args.datasets.split(",") if d]
    print("name,us_per_call,derived")
    lines, point = bench_train(args.scale, args.epochs, datasets)
    for line in lines:
        print(line, flush=True)
    with open(args.out_json, "w") as f:
        json.dump(point, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {args.out_json}", flush=True)
    for ds, entry in point["datasets"].items():
        if not entry["acc_parity"]:
            raise SystemExit(
                f"{ds}: banded executor converged below the jnp executor "
                f"(banded {entry['banded']['train_acc']:.3f}/"
                f"{entry['banded']['val_acc']:.3f} vs jnp "
                f"{entry['jnp']['train_acc']:.3f}/"
                f"{entry['jnp']['val_acc']:.3f})")
        if args.require_target_acc and not entry["converged_to_target"]:
            raise SystemExit(
                f"{ds}: executors failed to converge to train_acc >= "
                f"{ACC_TARGET} (banded {entry['banded']['train_acc']:.3f}, "
                f"jnp {entry['jnp']['train_acc']:.3f})")


if __name__ == "__main__":
    main()
