"""Tests of the chip benchmark's own code (``benchmarks/chip``), on the
CPU at small sizes.

    python3 -m pytest -q tests/chipbench

They cover the trace reduction (on a small trace recorded on a TPU v5e
and committed under ``data/``), the work counts against a hand-counted
graph, the graph generator's edge counts and determinism, loading every
file by name, the allowed characters of names and units, and that the
check turns ``correct`` false when the timed path is broken underneath
(one run per fault a cell can have) or replaced by the control.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import check, drivers, faults, graphs, runner, spec, tracing, work  # noqa: E402

BENCH = spec.load_benchmark()
SMALL_TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


# ---------------------------------------------------------------- names --
def _all_names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[key]:
            yield entry["name"]
    for w in BENCH["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in BENCH["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(_all_names())))
def test_name_characters(name):
    assert spec.NAME_RE.match(name), name


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert spec.UNIT_RE.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_unique_names():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))


# --------------------------------------------------------- files by name --
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(cell):
    c = spec.load_cell(cell, BENCH)
    assert c.kind in drivers.DRIVERS
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    assert set(c.limits) and all("limit" in v for v in c.limits.values())


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_loads_by_name(metric):
    mod = spec.load_metric(metric["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (metric["unit"], metric["layer"],
                                                metric["moves"])
    assert callable(mod.read)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    with open(ROOT / entry["file"]) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"] and cfg["reduced"] == entry["reduced"]
    assert set(cfg["features"]) == set(cfg["vertices"])
    for mp in cfg["metapaths"]:
        assert all(a + b in _relation_names(cfg) for a, b in zip(mp, mp[1:]))


def _relation_names(cfg):
    out = set()
    for s, d, _ in cfg["relation_edges"]:
        out |= {s + d, d + s}
    return out


@pytest.mark.parametrize("name", sorted({w["traffic"] for w in BENCH["workloads"]}))
def test_traffic_loads_by_name(name):
    t = spec.load_json("traffic", name)
    assert t["entry"] in drivers.DRIVERS


def test_paths_hold_the_command_and_these_tests():
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert (ROOT / BENCH["command"][1]).is_file()
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    here = Path(__file__).resolve().parent.relative_to(ROOT).as_posix()
    assert here in BENCH["paths"]


# ---------------------------------------------------------------- trace --
def test_busy_union_and_gaps_by_hand():
    ops = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50), ("d", 45, 48), ("e", 95, 120)]
    assert tracing.busy_intervals(ops) == [(10, 30), (40, 50), (95, 120)]
    tr = tracing.Trace(device_ops={"/device:TPU:0": ops},
                       spans=[("bench.window", 0, 100), ("bench.forward", 0, 35),
                              ("bench.gather", 50, 100)])
    red = tracing.reduce(tr)
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(35e-9)  # 20 + 10 + 5, clipped at 100
    assert red.idle_share == pytest.approx(0.65)
    assert red.idle_gaps[0] == ("bench.gather", pytest.approx(45e-9))
    assert red.idle_gaps[1] == ("no bench span", pytest.approx(10e-9))
    assert red.op_seconds["e"] == pytest.approx(5e-9)
    assert red.kernel("a") == (pytest.approx(10e-9), 1)


def test_op_names_and_breakdown_by_kind():
    assert tracing.op_name("%na_seg_sum.9 = f32[3072,64]{1,0} custom-call(s32[7485]{0} "
                           "%copy-done.170)") == "na_seg_sum.9"
    assert tracing.base_name("na_seg_sum.9") == "na_seg_sum"
    assert tracing.base_name("copy-start") == "copy-start"
    ops = [("na_seg_sum.1", 0, 30), ("na_seg_sum.2", 30, 50), ("fusion.3", 50, 60)]
    red = tracing.reduce(tracing.Trace({"/device:TPU:0": ops}, [("bench.window", 0, 100)]))
    out = tracing.breakdown(red)
    assert out["device_ops"] == [["na_seg_sum", pytest.approx(50e-9)],
                                 ["fusion", pytest.approx(10e-9)]]
    assert out["idle_gaps"] == [["no bench span", pytest.approx(40e-9)]]


def test_recorded_trace_reduction():
    """Two forwards of a one-layer RGAT on a small ACM graph, traced on a
    TPU v5e.  The device clock runs about a millisecond apart from the
    host's, so the first forward's first kernels fall before the window
    span and are clipped."""
    red = tracing.reduce(tracing.load(str(SMALL_TRACE)))
    assert 0 < red.busy_s <= red.window_s
    secs, count = red.kernel("na_seg_sum")
    assert count > 0 and 0 < secs < red.busy_s
    secs, count = red.kernel("na_softmax_stats")
    assert count > 0 and 0 < secs < red.busy_s
    assert {name for name, _ in red.idle_gaps} <= {"bench.forward", "bench.idle",
                                                   "no bench span"}
    out = tracing.breakdown(red)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


# ----------------------------------------------------------------- work --
TINY = {"model": "rgat", "hidden": 2, "num_classes": 3, "sf_att_dim": 4, "num_layers": 1,
        "target_type": "P", "metapaths": ["PAP"], "vertices": {"P": 3, "A": 2},
        "features": {"P": 5, "A": 0}}


def test_forward_flops_hand_counted():
    # one rgat layer, only P is live: FP 2*3*5*2 = 60; NA over E=4 edges:
    # projection 2*3*2*2 = 24, logits 2*3*2 + 2*3*2 = 24, per edge 8*4 = 32,
    # aggregation 2*4*2 = 16; SF self 2*3*2*2 = 24, scores 2*3*(2*2*4 + 2*4)
    # = 144, weighted sum 2*2*3*2 = 24; head 2*3*2*3 = 36
    assert work.live_types(TINY) == [{"P"}]
    assert work.forward_flops(TINY, {"P": 3, "A": 2}, {"PAP": 4}) == 384


def test_na_kernel_work_hand_counted():
    calls = work.na_kernel_work(TINY, {"P": 3, "A": 2}, {"PAP": (4, 3, 2)})
    # seg-sum: 2*4*2 flops; 4*2*(3+2) feature bytes + (4+4+4)*4 edge bytes
    assert calls["na_seg_sum"] == [(16, 40 + 48)]
    # stats: 4*4 flops; (4+4)*4 edge bytes + 2*4*2 per destination
    assert calls["na_softmax_stats"] == [(16, 32 + 16)]
    assert "na_softmax_stats" not in work.na_kernel_work(dict(TINY, model="rgcn"),
                                                         {"P": 3, "A": 2},
                                                         {"PAP": (4, 3, 2)})


def test_least_seconds_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(100, 5, peak) == 1.0
    assert work.least_seconds(10, 50, peak) == 5.0


# --------------------------------------------------------------- graphs --
@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
@pytest.mark.parametrize("scale", [0.05, 0.2])
def test_graph_has_the_stated_edge_counts(name, scale):
    """Every relation holds the stated number of distinct edges (its
    density kept under a cut in scale), and the graph seed fixes it."""
    cfg = dict(spec.load_json("configs", name), scale=scale)
    nv, rels = graphs.topology(cfg)
    again = graphs.topology(cfg)[1]
    for s, d, count in cfg["relation_edges"]:
        src, dst = rels[s + d]
        keys = src.astype(np.int64) * nv[d] + dst
        assert np.all(np.diff(keys) > 0)  # canonical: sorted, no duplicates
        if s != d:
            assert src.size == max(1, round(count * scale * scale))
            r_src, r_dst = rels[d + s]
            assert sorted(zip(r_dst.tolist(), r_src.tolist())) == list(zip(src.tolist(),
                                                                         dst.tolist()))
        assert np.array_equal(src, again[s + d][0]) and np.array_equal(dst, again[s + d][1])


def test_generator_draws_distinct_edges_up_to_the_degree_cap():
    rng = np.random.default_rng(3)
    src, dst = graphs._bipartite_edges(rng, 40, 300, 4000)
    assert src.size == 4000
    assert np.unique(src.astype(np.int64) * 300 + dst).size == 4000
    assert np.bincount(src).max() <= 10 * 4000 // 40
    with pytest.raises(ValueError):
        graphs._bipartite_edges(rng, 2, 10, 200)


def test_semantic_graph_by_hand():
    nv = {"P": 3, "A": 2}
    rels = {"PA": (np.array([0, 1, 2], np.int32), np.array([0, 0, 1], np.int32)),
            "AP": (np.array([0, 0, 1], np.int32), np.array([0, 1, 2], np.int32))}
    s, d = graphs.semantic_graph(nv, rels, "PAP")
    assert list(zip(s.tolist(), d.tolist())) == [(0, 0), (0, 1), (1, 0), (1, 1), (2, 2)]


# ------------------------------------------------------- check + faults --
def _tiny_cell(name: str):
    c = spec.load_cell(name, BENCH)
    cfg = dict(c.config, scale=0.05)
    return dataclasses.replace(c, config=cfg)


KIND_CELLS = {}
for _w in BENCH["workloads"]:
    KIND_CELLS.setdefault(spec.load_json("traffic", _w["traffic"])["entry"], _w["name"])
FAULT_CASES = [(kind, f) for kind in sorted(KIND_CELLS)
               for f in [None] + faults.FAULTS_BY_ENTRY[kind]]


def test_gap_over_noise_by_hand():
    want = np.array([[3.0, 4.0]])  # norm 5: a gap of 1 is 0.2, one of 0.5 is 0.1
    noise = want + [[0.5, 0.0]]
    assert check.gap_over_noise(want + [[0.0, 1.0]], want, noise) == pytest.approx(2.0)
    assert check.gap_over_noise(want + [[0.0, 1.0]], want, want) == float("inf")
    assert check.verdict({"g": float("nan")}, {"g": {"limit": 4}})[0] is False


@pytest.mark.parametrize("kind,fault", FAULT_CASES,
                         ids=[f"{k}-{f or 'sound'}" for k, f in FAULT_CASES])
def test_broken_timed_path_is_not_correct(kind, fault):
    """A whole run but the look for a chip, on the CPU at a small size:
    sound, it is correct; with each fault planted under it, it is not."""
    c = _tiny_cell(KIND_CELLS[kind])
    res, checks = runner.run_cell(c, 2**31 + 5, 1.0, None, t_start=time.perf_counter(),
                                  device=CPU, fault=faults.FAULTS.get(fault))
    assert res["attempted"] > 0 and list(res)[-1] == "checks"
    assert res["correct"] is (fault is None), checks


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_control_is_not_correct(cell):
    """The reference in three-pass bf16, put in the program's place, fails
    the cell's limits on three seeds, at a tenth of the cell's scale (the
    chip reads it at the cell's own size: PERF.md)."""
    c = spec.load_cell(cell, BENCH)
    drv = drivers.make_driver(drivers.Context(dict(c.config, scale=0.1)), c.traffic)
    for seed in (3, 4, 5):
        drv.prepare(seed)
        ok, checks = check.verdict(drv.check(drv.control()), c.limits)
        assert not ok, (seed, checks)


def test_result_line_has_every_key():
    res, _ = runner.run_cell(_tiny_cell(KIND_CELLS["forward"]), 1, 0.5, None,
                             t_start=time.perf_counter(), device=CPU)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert {"forward_ms", "setup_s"} == set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_no_tpu_no_result(capsys):
    assert runner.accelerator(1) is None
    assert "no TPU" in capsys.readouterr().err


def test_entry_point_exits_without_a_chip():
    import subprocess

    out = subprocess.run([sys.executable, str(ROOT / BENCH["command"][1]), "--workload",
                          BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
                          "--trace", "0"], capture_output=True, text=True, timeout=300,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.parametrize("path", sorted((HERE / "metrics").glob("*.py")), ids=lambda p: p.stem)
def test_every_metric_file_is_a_reader(path):
    mod = spec.load_metric(path.stem)
    assert spec.UNIT_RE.match(mod.UNIT) and mod.LAYER and mod.MOVES
    assert mod.read({"kind": "none", "window": {}, "trace": None}) is None
