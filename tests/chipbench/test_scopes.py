"""Tests of the per-scope join (``benchmarks/chip/chipbench/scopes.py``)
and the readers built on it, on the CPU.

The join and the stages are checked by hand; each reader on a run record
built by hand around a tiny compiled model of the program; the join on a
scoped trace recorded on a TPU v5e with its op -> scope map
(``data/scoped.*``, made by ``benchmarks/chip/scope_table.py --keep``).
"""
from __future__ import annotations

import json
import sys
import weakref
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import scopes, spec, tracing  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
READERS = ["na_glue_ms.forward", "fp_ms.forward", "sf_ms.forward",
           "na_block_fill_pct.forward", "frontend_s"]


def test_seconds_by_scope_by_hand():
    ops = {"fusion.1": 1.0, "na_seg_sum.2": 4.0, "dot.3": 0.5, "copy-done.4": 0.25}
    scope_of = {"fusion.1": "layer0/na/MAM", "na_seg_sum.2": "layer0/na/MAM",
                "dot.3": "head"}
    assert scopes.seconds_by_scope(ops, scope_of) == {
        "layer0/na/MAM": 5.0, "head": 0.5, scopes.UNSCOPED: 0.25}
    assert scopes.scoped_share(ops, scope_of) == pytest.approx(5.5 / 5.75)
    assert scopes.scoped_share({}, scope_of) == 0.0


def test_stage_seconds_by_hand():
    ops = {"na_seg_sum.9": 8.0, "na_softmax_stats.3": 2.0, "gather.1": 1.0,
           "fusion.2": 0.5, "fusion.3": 0.25, "sort.4": 0.125, "dot.5": 0.0625,
           "copy-done.6": 0.03125}
    scope_of = {"na_seg_sum.9": "layer1/na/MDM", "na_softmax_stats.3": "layer0/na/MAM",
                "gather.1": "layer0/na/MAM", "fusion.2": "layer0/fp/M",
                "fusion.3": "layer1/sf/M", "sort.4": "layer1/na/MKM", "dot.5": "head"}
    got = scopes.stage_seconds(ops, scope_of)
    assert got == {"fp": 0.5, "na_kernels": 10.0, "na_glue": 1.125, "sf": 0.25,
                   "head": 0.0625, "unscoped": 0.03125}
    assert sum(got.values()) == sum(ops.values())
    assert scopes.is_kernel("na_seg_sum.9") and not scopes.is_kernel("na_seg_sum_x.1")


# ------------------------------------------------------------ readers --
@pytest.fixture(scope="module")
def model():
    from repro.api import ExecutorSpec, Session, device_features
    from repro.core.hgnn import HGNNConfig
    from repro.hetero import make_dataset

    graph = make_dataset("IMDB", scale=0.05)
    cfg = HGNNConfig(model="shgn", hidden=8, num_layers=2, num_classes=3,
                     target_type="M", edge_emb_dim=4, sf_att_dim=8)
    c = Session(ExecutorSpec(na_executor="banded")).compile(graph, ["MAM", "MDM"], cfg)
    c.forward(c.init(0), device_features(graph)).block_until_ready()
    return c


@pytest.fixture
def only(model, monkeypatch):
    """The registry holding just ``model``, with its forward's map joined
    to a hand-made trace: one op per stage and an NA kernel."""
    from repro import obs

    live = weakref.WeakSet([model])
    monkeypatch.setattr(obs, "_LIVE", live)
    real = obs.forward_scopes(model)
    pick = {}
    for op, sc in sorted(real.items()):
        pick.setdefault(scopes.stage_of(op, sc), op)
    scope_of = dict(real, **{"na_seg_sum.999": "layer0/na/MAM"})
    monkeypatch.setattr(obs, "forward_scopes", lambda m: scope_of if m is model else None)
    ops = {pick["fp"]: 0.002, pick["na_glue"]: 0.003, "na_seg_sum.999": 0.010,
           pick["sf"]: 0.001, pick["head"]: 0.0005, "copy-done.1": 0.0001}
    red = tracing.Reduction(window_s=0.02, busy_s=sum(ops.values()), op_seconds=ops,
                            op_counts=dict.fromkeys(ops, 1), idle_gaps=[])
    run = {"cell": "imdb-shgn.forward", "kind": "forward", "trace": red,
           "window": {"window_s": 0.02, "forwards": 2, "attempted": 2, "failed": 0}}
    return live, run


def _want(model):
    counts = model.packing_counts().values()
    return {"na_glue_ms.forward": 1.5, "fp_ms.forward": 1.0, "sf_ms.forward": 0.75,
            "na_block_fill_pct.forward": 100.0 * sum(c["edges"] for c in counts)
            / sum(c["slots"] for c in counts),
            "frontend_s": model.frontend.timings["total"]}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_hand_built_run(name, model, only):
    _, run = only
    assert spec.load_metric(name).read(run) == pytest.approx(_want(model)[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_needs_one_forward_model(name, model, only):
    live, run = only
    reader = spec.load_metric(name)
    assert reader.read(dict(run, kind="train")) is None
    live.clear()
    assert reader.read(run) is None  # no model
    live.add(model)
    other = type("Other", (), {"forward_built": True})()
    live.add(other)
    assert reader.read(run) is None  # two models
    live.discard(other)
    assert reader.read(run) is not None


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_a_program_without_the_registry(name, only, monkeypatch):
    """A program that predates ``repro.obs`` gives these readers nothing
    to read, and no error."""
    import repro

    _, run = only
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert spec.load_metric(name).read(run) is None


# ------------------------------------------------------- recorded trace --
def test_recorded_scoped_trace_is_under_scopes():
    """Three forwards (a 5 ms window) of the cell's two-layer Simple-HGN on
    its IMDB graph at a tenth of its scale, traced on a TPU v5e, with the
    map the program gave for them: the join puts at least 98% of the busy
    device time under a scope, and every NA kernel call under an NA scope."""
    red = tracing.reduce(tracing.load(str(DATA / "scoped.xplane.pb")))
    scope_of = json.loads((DATA / "scoped.scopes.json").read_text())
    assert red.op_seconds and red.busy_s > 0
    assert scopes.scoped_share(red.op_seconds, scope_of) >= 0.98
    scoped = sum(s for op, s in red.op_seconds.items() if op in scope_of)
    assert scoped >= 0.98 * red.busy_s
    kernels = {op for op in red.op_seconds if scopes.is_kernel(op)}
    assert kernels and all(scope_of[op].split("/")[1] == "na" for op in kernels)
    stages = scopes.stage_seconds(red.op_seconds, scope_of)
    assert stages["na_kernels"] > 0 and stages["fp"] > 0 and stages["sf"] > 0
    assert sum(stages.values()) == pytest.approx(sum(red.op_seconds.values()))
