"""Tests of the ``dblp-rgcn.forward`` cell and the readers of
``forward_compile_s`` and ``na_seg_sum_us_per_block.forward``, on the
CPU at small sizes.

    python3 -m pytest -q tests/chipbench/test_dblp_rgcn.py

A whole run of the cell at a twentieth of its scale reads ``correct``,
and with each fault planted under its forward does not; the work counts
of R-GCN over a 4-hop metapath are counted by hand; each new reader reads
a run record built by hand around a tiny compiled model of the program,
and nothing where the program has no such model or span.
"""
from __future__ import annotations

import dataclasses
import sys
import time
import weakref
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import faults, graphs, runner, spec, tracing, work  # noqa: E402

CELL = "dblp-rgcn.forward"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


# ------------------------------------------------------------ the cell --
@pytest.mark.parametrize("fault", [None] + faults.FAULTS_BY_ENTRY["forward"])
def test_run_is_correct_only_when_sound(fault):
    c = spec.load_cell(CELL)
    c = dataclasses.replace(c, config=dict(c.config, scale=0.05))
    res, checks = runner.run_cell(c, 2**31 + 7, 0.5, None, t_start=time.perf_counter(),
                                  device=CPU, fault=faults.FAULTS.get(fault))
    assert res["attempted"] > 0 and {"forward_ms", "setup_s"} == set(res["metrics"])
    assert res["correct"] is (fault is None), checks


# ---------------------------------------------------------------- work --
# one author per paper pair: a0-p0, a1-p1, a2-p3; terms t0 over p0, p1 and
# t1 over p2, p3 (p2 has no author)
NV = {"A": 3, "P": 4, "T": 2}
AP = (np.array([0, 1, 2], np.int32), np.array([0, 1, 3], np.int32))
PT = (np.array([0, 1, 2, 3], np.int32), np.array([0, 0, 1, 1], np.int32))
TINY = {"model": "rgcn", "hidden": 2, "num_classes": 3, "sf_att_dim": 4, "num_layers": 2,
        "target_type": "A", "metapaths": ["APTPA"], "vertices": NV,
        "features": {"A": 5, "P": 6, "T": 0}}


def test_four_hop_metapath_by_hand():
    """a0 -> p0 -> t0 -> {p0, p1} -> {a0, a1}; a1 likewise; a2 -> p3 -> t1
    -> {p2, p3} -> a2 (p2 has no author)."""
    rels = {"AP": AP, "PA": (AP[1], AP[0]), "PT": PT, "TP": (PT[1], PT[0])}
    s, d = graphs.semantic_graph(NV, rels, "APTPA")
    assert list(zip(s.tolist(), d.tolist())) == [(0, 0), (0, 1), (1, 0), (1, 1), (2, 2)]


def test_rgcn_forward_flops_hand_counted():
    # two layers, only A is live in each.  Layer 0: FP 2*3*5*2 = 60; NA
    # over the 5 APTPA edges: projection 2*3*2*2 = 24, mean 5*2 = 10; SF
    # self path 2*3*2*2 = 24, scores 2*3*(2*2*4 + 2*4) = 144, weighted sum
    # 2*2*3*2 = 24.  Layer 1: FP 2*3*2*2 = 24, NA and SF as layer 0 (226).
    # Head 2*3*2*3 = 36.
    assert work.live_types(TINY) == [{"A"}, {"A"}]
    assert work.forward_flops(TINY, NV, {"APTPA": 5}) == 60 + 226 + 24 + 226 + 36


def test_rgcn_na_kernel_work_hand_counted():
    # per layer: 2*5*2 flops; 4*2*(3+3) feature bytes + (4+4)*5 index bytes,
    # no weights and no stats kernel
    calls = work.na_kernel_work(TINY, NV, {"APTPA": (5, 3, 3)})
    assert calls == {"na_seg_sum": [(20, 48 + 40), (20, 48 + 40)]}


# ------------------------------------------------------------- readers --
READERS = ["forward_compile_s", "na_seg_sum_us_per_block.forward"]


@pytest.fixture(scope="module")
def model():
    from repro.api import ExecutorSpec, Session, device_features
    from repro.core.hgnn import HGNNConfig
    from repro.hetero import make_dataset

    graph = make_dataset("DBLP", scale=0.05)
    cfg = HGNNConfig(model="rgcn", hidden=8, num_layers=2, num_classes=4, target_type="A")
    c = Session(ExecutorSpec(na_executor="banded")).compile(graph, ["APA", "APTPA"], cfg)
    c.forward(c.init(0), device_features(graph)).block_until_ready()
    return c


@pytest.fixture
def only(model, monkeypatch):
    """The registry holding just ``model``, and a hand-made trace of two
    forwards with 6 ms of ``na_seg_sum``."""
    from repro import obs

    live = weakref.WeakSet([model])
    monkeypatch.setattr(obs, "_LIVE", live)
    ops = {"na_seg_sum.3": 0.004, "na_seg_sum.4": 0.002, "fusion.1": 0.001}
    red = tracing.Reduction(window_s=0.01, busy_s=sum(ops.values()), op_seconds=ops,
                            op_counts=dict.fromkeys(ops, 1), idle_gaps=[])
    run = {"cell": CELL, "kind": "forward", "trace": red,
           "window": {"window_s": 0.01, "forwards": 2, "attempted": 2, "failed": 0}}
    return live, run


def test_readers_on_a_hand_built_run(model, only):
    _, run = only
    blocks = sum(c["blocks"] for c in model.packing_counts().values())
    assert spec.load_metric("forward_compile_s").read(run) == model.timings["forward_compile"]
    assert spec.load_metric("na_seg_sum_us_per_block.forward").read(run) == pytest.approx(
        1e6 * 0.006 / 2 / (blocks * 2))


@pytest.mark.parametrize("name", READERS)
def test_reader_needs_the_program_model(name, model, only):
    """Nothing to read in a training run, with no model or two, or in a
    program whose model keeps no timings (one older than the span)."""
    live, run = only
    reader = spec.load_metric(name)
    assert reader.read(dict(run, kind="train")) is None
    live.clear()
    assert reader.read(run) is None
    if name == "forward_compile_s":
        old = type("Old", (), {"forward_built": True})()
        live.add(old)
        assert reader.read(run) is None
        live.discard(old)
    live.add(model)
    assert reader.read(run) is not None
