"""Tests of the reader of ``na_dense_edge_pct.forward``, on the CPU at
small sizes.

    python3 -m pytest -q tests/chipbench/test_dense_share.py

The reader reads a run record built by hand around a tiny compiled model
of the program: 0 on an IMDB-shaped Simple-HGN (attention weights are
traced, so every packing stays on edge blocks), the dense share on a
DBLP-shaped R-GCN (APTPA joins enough author pairs to go dense, APA does
not), and nothing where the program has no such model or counter.
"""
from __future__ import annotations

import sys
import weakref
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import spec, tracing  # noqa: E402

NAME = "na_dense_edge_pct.forward"
SHAPES = {
    "IMDB": ("imdb-shgn.forward", ["MAM", "MDM"],
             dict(model="shgn", hidden=8, num_layers=2, num_classes=3, target_type="M",
                  edge_emb_dim=4, sf_att_dim=8)),
    "DBLP": ("dblp-rgcn.forward", ["APA", "APTPA"],
             dict(model="rgcn", hidden=8, num_layers=2, num_classes=4, target_type="A")),
}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def model(request):
    from repro.api import ExecutorSpec, Session, device_features
    from repro.core.hgnn import HGNNConfig
    from repro.hetero import make_dataset

    _, metapaths, cfg = SHAPES[request.param]
    graph = make_dataset(request.param, scale=0.1)
    c = Session(ExecutorSpec(na_executor="banded")).compile(graph, metapaths,
                                                            HGNNConfig(**cfg))
    c.forward(c.init(0), device_features(graph)).block_until_ready()
    c.shape = request.param
    return c


@pytest.fixture
def only(model, monkeypatch):
    """The registry holding just ``model``, and a hand-made trace of two
    forwards."""
    from repro import obs

    live = weakref.WeakSet([model])
    monkeypatch.setattr(obs, "_LIVE", live)
    ops = {"na_seg_sum.3": 0.004, "fusion.1": 0.001}
    red = tracing.Reduction(window_s=0.01, busy_s=sum(ops.values()), op_seconds=ops,
                            op_counts=dict.fromkeys(ops, 1), idle_gaps=[])
    run = {"cell": SHAPES[model.shape][0], "kind": "forward", "trace": red,
           "window": {"window_s": 0.01, "forwards": 2, "attempted": 2, "failed": 0}}
    return live, run


def test_reader_on_a_hand_built_run(model, only):
    _, run = only
    counts = model.packing_counts()
    edges = sum(c["edges"] for c in counts.values())
    if model.shape == "IMDB":
        assert all(c["dense_tiles"] == c["dense_edges"] == 0 for c in counts.values())
        want = 0.0
    else:
        assert counts["APTPA"]["dense_edges"] == counts["APTPA"]["edges"] > 0
        assert counts["APA"]["dense_edges"] == 0
        want = 100.0 * counts["APTPA"]["edges"] / edges
    assert spec.load_metric(NAME).read(run) == pytest.approx(want)


def test_reader_needs_one_forward_model(model, only):
    """Nothing to read in a training run, with no model or with two."""
    live, run = only
    reader = spec.load_metric(NAME)
    assert reader.read(dict(run, kind="train")) is None
    live.clear()
    assert reader.read(run) is None
    live.add(model)
    other = type("Other", (), {"forward_built": True})()
    live.add(other)
    assert reader.read(run) is None
    live.discard(other)
    assert reader.read(run) is not None


def test_reader_finds_nothing_without_dense_counts(model, only):
    """A program whose packing counts hold no ``dense_edges`` (one older
    than the dense format) gives the reader nothing to read, and no
    error."""
    live, run = only
    old = type("Old", (), {"forward_built": True, "packing_counts": lambda self: {
        mp: {k: v for k, v in c.items() if not k.startswith("dense")}
        for mp, c in model.packing_counts().items()}})()
    live.clear()
    live.add(old)
    assert spec.load_metric(NAME).read(run) is None


def test_reader_finds_nothing_in_a_program_without_the_registry(only, monkeypatch):
    """A program that predates ``repro.obs`` gives the reader nothing to
    read, and no error."""
    import repro

    _, run = only
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert spec.load_metric(NAME).read(run) is None
