"""Tests of the ``dblp-han.forward`` cell, HAN's model file
(``benchmarks/chip/models/han.py``) and the reader of
``na_softmax_stats_us_per_block.forward``, on the CPU at small sizes.

    python3 -m pytest -q tests/chipbench/test_dblp_han.py

A whole run of the cell at a tenth of its scale reads ``correct``, and
with each fault planted under its forward does not (at a twentieth, some
seeds' reorder noise is exactly zero: few authors there have enough
neighbours for another edge order to round differently).  HAN's work
counts are counted by hand; its reference NA agrees with the program's
own jnp HAN on random inputs; its parameters have the program's layout
under the cell's configuration; and the new reader reads a run record
built by hand around a tiny compiled HAN of the program, and nothing where
the program has no such model or kernel.
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

from chipbench import faults, params, reference, runner, spec, tracing, work  # noqa: E402

CELL = "dblp-han.forward"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
HAN = spec.load_model("han")


# ------------------------------------------------------------ the cell --
@pytest.mark.parametrize("fault", [None] + faults.FAULTS_BY_ENTRY["forward"])
def test_run_is_correct_only_when_sound(fault):
    c = spec.load_cell(CELL)
    c = dataclasses.replace(c, config=dict(c.config, scale=0.1))
    res, checks = runner.run_cell(c, 2**31 + 7, 0.5, None, t_start=time.perf_counter(),
                                  device=CPU, fault=faults.FAULTS.get(fault))
    assert res["attempted"] > 0 and {"forward_ms", "setup_s"} == set(res["metrics"])
    assert res["correct"] is (fault is None), checks


def test_parameters_have_the_program_layout():
    """Under the cell's own configuration: eight heads of eight columns,
    one layer, the 128-wide semantic attention."""
    import jax

    from repro.core.hgnn import HGNN, HGNNConfig

    cfg = spec.load_json("configs", "dblp-han")
    hcfg = HGNNConfig(model="han", hidden=int(cfg["hidden"]), num_layers=int(cfg["num_layers"]),
                      num_classes=int(cfg["num_classes"]), target_type=cfg["target_type"],
                      sf_att_dim=int(cfg["sf_att_dim"]), **HAN.program_config(cfg))
    program = HGNN(hcfg, {t: int(d) for t, d in cfg["features"].items()}, cfg["vertices"],
                   cfg["metapaths"])
    key = jax.random.key(0)
    want = jax.eval_shape(program.init, key)
    got = jax.eval_shape(lambda k: params.init_params(cfg, k), key)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    assert [x.shape for x in jax.tree_util.tree_leaves(got)] == \
        [x.shape for x in jax.tree_util.tree_leaves(want)]
    assert got["layers"][0]["na"]["APA"]["a_src"].shape == (8, 8)


# ------------------------------------------------------------ reference --
def test_reference_na_matches_the_program_jnp_han():
    """``han.na`` (straight per-head dots, chunked sums) and the program's
    jnp HAN NA (``na_attention`` over an edge list, then the ELU), on the
    same random rows, vectors and edges: two independent writings of one
    equation."""
    import jax
    import jax.numpy as jnp

    from repro.core.hgnn.layers import na_attention

    rng = np.random.default_rng(0)
    n_src, n_dst, e, h = 40, 30, 500, 16
    src = jnp.asarray(rng.integers(0, n_src, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n_dst - 3, e), jnp.int32)  # 3 rows without edges
    w_rel = jnp.asarray(rng.standard_normal((h, h)) * 0.5, jnp.float32)
    hp = jnp.asarray(rng.standard_normal((n_src, h)), jnp.float32)
    a_src, a_dst = (jnp.asarray(rng.standard_normal((8, 2)), jnp.float32) for _ in range(2))
    lp = {"na": {"APA": {"w_rel": w_rel, "a_src": a_src, "a_dst": a_dst}}}
    dot = reference.make_dot("highest")
    hs = dot(hp, w_rel)
    got = HAN.na(dot, lp, 0, "APA", hs, hp[:n_dst], src, dst, n_dst)
    with jax.default_matmul_precision("highest"):
        want = jax.nn.elu(na_attention(hs, hs[:n_dst], src, dst, n_dst, a_src, a_dst))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    assert not np.asarray(got)[-3:].any()


# ---------------------------------------------------------------- work --
NV = {"A": 3, "P": 4, "T": 2}
TINY = {"model": "han", "hidden": 8, "num_classes": 3, "sf_att_dim": 4, "num_layers": 1,
        "target_type": "A", "metapaths": ["APTPA"], "vertices": NV,
        "features": {"A": 5, "P": 6, "T": 0}}


def test_han_forward_flops_hand_counted():
    # one layer, only A is live.  FP 2*3*5*8 = 240.  NA over 5 APTPA edges:
    # projection 2*3*8*8 = 384, score dots 2*3*8 + 2*3*8 = 96, per edge and
    # head 8*5*8 = 320, aggregation 2*5*8 = 80.  SF self path 2*3*8*8 = 384,
    # scores 2*3*(2*8*4 + 2*4) = 432, weighted sum 2*2*3*8 = 96.  Head
    # 2*3*8*3 = 144.
    assert work.live_types(TINY) == [{"A"}]
    assert work.forward_flops(TINY, NV, {"APTPA": 5}) == (
        240 + 384 + 96 + 320 + 80 + 384 + 432 + 96 + 144)


def test_han_na_kernel_work_hand_counted():
    # per edge two 4-byte indices and 8 logits, (8 + 32) * 5 = 200 bytes;
    # (m, s) of the 3 destinations, 2*4*8*3 = 192.  Aggregation: 2*5*8
    # flops; rows 4*8*(3 + 3) = 192.  Stats: 8*5*8 flops; score columns
    # 4*8*(3 + 3) = 192.
    calls = work.na_kernel_work(TINY, NV, {"APTPA": (5, 3, 3)})
    assert calls == {"na_seg_sum": [(80, 192 + 200 + 192)],
                     "na_softmax_stats": [(320, 200 + 192 + 192)]}


# ------------------------------------------------------------- readers --
NAME = "na_softmax_stats_us_per_block.forward"


@pytest.fixture(scope="module")
def model():
    from repro.api import ExecutorSpec, Session, device_features
    from repro.core.hgnn import HGNNConfig
    from repro.hetero import make_dataset

    graph = make_dataset("DBLP", scale=0.05)
    cfg = HGNNConfig(model="han", hidden=16, num_layers=1, num_classes=4, target_type="A",
                     num_heads=8, sf_att_dim=8)
    c = Session(ExecutorSpec(na_executor="banded")).compile(graph, ["APA", "APTPA"], cfg)
    c.forward(c.init(0), device_features(graph)).block_until_ready()
    return c


@pytest.fixture
def only(model, monkeypatch):
    """The registry holding just ``model``, and a hand-made trace of two
    forwards with 3 ms of ``na_softmax_stats``."""
    from repro import obs

    live = weakref.WeakSet([model])
    monkeypatch.setattr(obs, "_LIVE", live)
    ops = {"na_softmax_stats.3": 0.002, "na_softmax_stats.4": 0.001, "na_seg_sum.5": 0.004}
    red = tracing.Reduction(window_s=0.01, busy_s=sum(ops.values()), op_seconds=ops,
                            op_counts=dict.fromkeys(ops, 1), idle_gaps=[])
    run = {"cell": CELL, "kind": "forward", "trace": red,
           "window": {"window_s": 0.01, "forwards": 2, "attempted": 2, "failed": 0}}
    return live, run


def test_reader_on_a_hand_built_run(model, only):
    _, run = only
    blocks = sum(c["blocks"] for c in model.packing_counts().values())
    assert blocks > 0
    assert spec.load_metric(NAME).read(run) == pytest.approx(1e6 * 0.003 / 2 / blocks)


def test_reader_needs_the_program_model_and_the_kernel(model, only):
    """Nothing to read in a training run, with no model, or in a trace
    without the stats kernel (the mean model's)."""
    live, run = only
    reader = spec.load_metric(NAME)
    assert reader.read(dict(run, kind="train")) is None
    ops = {"na_seg_sum.5": 0.004}
    mean_only = tracing.Reduction(window_s=0.01, busy_s=0.004, op_seconds=ops,
                                  op_counts=dict.fromkeys(ops, 1), idle_gaps=[])
    assert reader.read(dict(run, trace=mean_only)) is None
    live.clear()
    assert reader.read(run) is None
