"""Sharded multi-device execution: plan invariants, mesh sizing, parity
of the shard_map forward against the single-device executors, compile
caching / no-retrace guards, and pinned-device-group serving.

Parity and serving tests shard for real only when jax reports multiple
devices — CI runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; on a single
device the same code paths execute over a one-device mesh.
"""
import jax
import numpy as np
import pytest

from repro.api import ExecutorSpec, Session, device_features
from repro.core.hgnn import HGNNConfig
from repro.distributed import (SHARD_MODES, ShardedHGNNExecutor,
                               build_shard_plan)
from repro.launch.mesh import _balanced_shape, make_mesh_for
from repro.pipeline import SemanticGraphCache
from repro.serve import HGNNRequest, HGNNServeEngine

NDEV = len(jax.devices())
WORKLOADS = {
    "acm_small": (["APA", "PAP", "PSP"], "P"),
    "imdb_small": (["AMA", "MAM", "MDM"], "M"),
}
MODELS = ("rgcn", "rgat", "shgn", "han")  # han with one head: the sharded form


def _cfg(model, target_type, **kw):
    kw.setdefault("hidden", 16)
    kw.setdefault("num_layers", 2)
    return HGNNConfig(model=model, num_classes=3, target_type=target_type,
                      **kw)


@pytest.fixture(scope="module")
def sessions(acm_small, imdb_small):
    """Reference (jnp + banded) and sharded sessions over ONE shared
    cache, so every executor consumes the same frontend products."""
    cache = SemanticGraphCache()
    return {
        "jnp": Session(ExecutorSpec(), cache=cache),
        "banded": Session(ExecutorSpec(na_executor="banded"), cache=cache),
        "relation": Session(
            ExecutorSpec(na_executor="banded", shard="relation"),
            cache=cache),
        "edge_block": Session(
            ExecutorSpec(na_executor="banded", shard="edge_block"),
            cache=cache),
        "graphs": {"acm_small": acm_small, "imdb_small": imdb_small},
    }


def _banded_graphs(sessions, name):
    targets, tt = WORKLOADS[name]
    graph = sessions["graphs"][name]
    return sessions["banded"].compile(graph, targets, _cfg("rgcn", tt)).graphs


# ------------------------------------------------------- plan invariants --
@pytest.mark.parametrize("dataset", ["acm_small", "imdb_small"])
@pytest.mark.parametrize("mode", SHARD_MODES)
@pytest.mark.parametrize("ndev", [1, 2, 3, 4, 7])
def test_plan_invariants(sessions, dataset, mode, ndev):
    """Every block assigned exactly once; dst tiles never split across
    devices; edge totals conserved; the summary is self-consistent."""
    graphs = _banded_graphs(sessions, dataset)
    plan = build_shard_plan(graphs, ndev, mode, feature_dim=16)
    assert plan.num_devices == ndev and plan.mode == mode
    by_mp = {g.metapath: g.packed for g in graphs}
    for mp, packed in by_mp.items():
        ids = [s.block_ids for s in plan.slices if s.metapath == mp]
        merged = np.concatenate(ids) if ids else np.zeros(0, np.int64)
        # exactly once: the union over devices is the full stream
        assert np.array_equal(np.sort(merged),
                              np.arange(packed.num_blocks))
        # per-slice streams stay ascending (within-tile accumulation order)
        for a in ids:
            assert np.all(np.diff(a) > 0) or a.size <= 1
        # a dst tile's blocks live on exactly one device
        owner = {}
        for s in plan.slices:
            if s.metapath != mp:
                continue
            for t in np.unique(packed.dst_tile[s.block_ids]):
                assert owner.setdefault(int(t), s.device) == s.device
    if mode == "relation":
        # relations stay whole: one slice per metapath
        mps = [s.metapath for s in plan.slices]
        assert len(mps) == len(set(mps))
    total = sum(int(g.packed.count.sum()) for g in graphs)
    summ = plan.summary()
    assert sum(summ["per_device_edges"]) == total
    assert sum(summ["per_device_macs"]) == total * 16
    assert summ["load_balance"] >= 1.0
    assert plan.device_block_counts().sum() == sum(
        g.packed.num_blocks for g in graphs)


def test_edge_block_mode_balances_at_least_as_well(sessions):
    """Splitting oversized relations can only reduce the max/mean skew."""
    graphs = _banded_graphs(sessions, "acm_small")
    rel = build_shard_plan(graphs, 4, "relation")
    eb = build_shard_plan(graphs, 4, "edge_block")
    assert eb.load_balance() <= rel.load_balance() + 1e-9


# ------------------------------------------------------------ mesh sizing --
def test_balanced_shape_and_mesh_for():
    assert _balanced_shape(256, 2) == (16, 16)
    assert _balanced_shape(512, 3) == (8, 8, 8)
    assert _balanced_shape(6, 2) == (3, 2)
    assert _balanced_shape(1, 2) == (1, 1)
    mesh = make_mesh_for()
    assert mesh.devices.size == NDEV
    sub = make_mesh_for(jax.devices()[:1], ("dev",))
    assert sub.axis_names == ("dev",) and sub.devices.size == 1
    with pytest.raises(ValueError, match="does not cover"):
        make_mesh_for(jax.devices(), ("a", "b"), shape=(NDEV + 1, 1))


# ---------------------------------------------------------------- parity --
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("mode", ["relation", "edge_block"])
def test_forward_parity_acm(sessions, model, mode):
    """Sharded forward == single-device banded forward (<= 1e-4)."""
    targets, tt = WORKLOADS["acm_small"]
    graph = sessions["graphs"]["acm_small"]
    cfg = _cfg(model, tt)
    ref = sessions["banded"].compile(graph, targets, cfg)
    params = ref.init(0)
    feats = device_features(graph)
    want = ref.forward(params, feats)
    got = sessions[mode].compile(graph, targets, cfg).forward(params, feats)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_forward_parity_imdb_both_executors(sessions):
    """IMDB parity against BOTH single-device executors: tight against
    banded (same kernels, same order), float-tolerance against the jnp
    oracle (different reassociation)."""
    targets, tt = WORKLOADS["imdb_small"]
    graph = sessions["graphs"]["imdb_small"]
    cfg = _cfg("rgat", tt)
    params = sessions["banded"].compile(graph, targets, cfg).init(0)
    feats = device_features(graph)
    banded = sessions["banded"].compile(graph, targets, cfg).forward(
        params, feats)
    oracle = sessions["jnp"].compile(graph, targets, cfg).forward(
        params, feats)
    got = sessions["edge_block"].compile(graph, targets, cfg).forward(
        params, feats)
    np.testing.assert_allclose(np.asarray(got), np.asarray(banded),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               atol=2e-3)


def test_direct_executor_multi_device_plan(sessions):
    """ShardedHGNNExecutor over an explicit plan: a 2-device plan runs on
    a 2-device mesh (truncating jax.devices()) even on one host."""
    graphs = _banded_graphs(sessions, "acm_small")
    ndev = min(2, NDEV)
    plan = build_shard_plan(graphs, ndev, "edge_block")
    targets, tt = WORKLOADS["acm_small"]
    cfg = _cfg("rgcn", tt)
    ref = sessions["banded"].compile(
        sessions["graphs"]["acm_small"], targets, cfg)
    ex = ShardedHGNNExecutor(ref.model, graphs, plan)
    params = ref.init(1)
    feats = device_features(sessions["graphs"]["acm_small"])
    np.testing.assert_allclose(
        np.asarray(ex.forward(params, feats)),
        np.asarray(ref.forward(params, feats)), atol=1e-4)


# ------------------------------------------------- compile / trace guards --
def test_no_retrace_and_compile_cache(sessions):
    """Repeated shard forwards reuse one jit trace; an identical compile
    returns the identical object; stats()["shard"] reports the plans."""
    targets, tt = WORKLOADS["acm_small"]
    graph = sessions["graphs"]["acm_small"]
    sess = sessions["relation"]
    cfg = _cfg("rgcn", tt)
    c = sess.compile(graph, targets, cfg)
    params = c.init(0)
    feats = device_features(graph)
    c.forward(params, feats)
    before = c.shard_traces
    assert before == 1
    c.forward(params, feats)
    c.forward(params, feats)
    assert c.shard_traces == before  # the serving hot path never retraces
    cached = sess.stats().compiles_cached
    assert sess.compile(graph, targets, cfg) is c
    assert sess.stats().compiles_cached == cached + 1
    shard = sess.stats()["shard"]
    assert shard["mode"] == "relation" and shard["plans"] >= 1
    assert len(shard["per_device_edges"]) == NDEV
    assert shard["load_balance"] >= 1.0
    assert sum(shard["per_device_macs"]) > 0


def test_spec_validation():
    with pytest.raises(ValueError, match="requires na_executor='banded'"):
        ExecutorSpec(shard="relation")
    with pytest.raises(ValueError, match="mesh_shape without sharding"):
        ExecutorSpec(mesh_shape=(2,))
    with pytest.raises(ValueError, match="not in"):
        ExecutorSpec(na_executor="banded", shard="rows")
    spec = ExecutorSpec(na_executor="banded", shard="edge_block",
                        mesh_shape=[2, 1])
    assert spec.mesh_shape == (2, 1)


def test_unsharded_compile_rejects_devices(sessions):
    targets, tt = WORKLOADS["acm_small"]
    with pytest.raises(ValueError, match="requires a sharded spec"):
        sessions["banded"].compile(sessions["graphs"]["acm_small"],
                                   targets, _cfg("rgcn", tt), devices=[0])


@pytest.mark.parametrize("mode", ["relation", "edge_block"])
def test_sharded_compile_refuses_heads(sessions, mode):
    """The sharded executor's merged NA runs one attention head: a model
    with more is refused at compile, before any frontend work, with a
    message that says so; the unsharded banded executor takes it."""
    targets, tt = WORKLOADS["acm_small"]
    graph = sessions["graphs"]["acm_small"]
    cfg = _cfg("han", tt, num_heads=8)
    with pytest.raises(ValueError, match="runs one attention head.*num_heads=8"):
        sessions[mode].compile(graph, targets, cfg)
    assert sessions["banded"].compile(graph, targets, cfg).shard_plan is None


def test_old_lm_exports_raise_with_pointer():
    with pytest.raises(ImportError, match="repro.train._lm_pspecs"):
        from repro.distributed import param_pspecs  # noqa: F401


# ------------------------------------------------- pinned-group serving --
@pytest.mark.skipif(NDEV < 4, reason="needs 4 devices (CI shard leg)")
def test_serve_pinned_disjoint_device_groups(sessions):
    """Two tenants pinned to disjoint halves of a 4-device mesh serve
    responses identical to the unsharded session's forwards."""
    targets, tt = WORKLOADS["acm_small"]
    graph = sessions["graphs"]["acm_small"]
    eng = HGNNServeEngine(session=sessions["edge_block"])
    eng.register("lo", graph, targets, _cfg("rgcn", tt), seed=3,
                 device_group=[0, 1])
    eng.register("hi", graph, targets, _cfg("rgat", tt), seed=4,
                 device_group=[2, 3])
    eng.submit([HGNNRequest(0, "lo"), HGNNRequest(1, "hi"),
                HGNNRequest(2, "lo", nodes=np.arange(5))])
    by_rid = {r.rid: r for r in eng.step()}
    assert set(by_rid) == {0, 1, 2}
    for name, rid in (("lo", 0), ("hi", 1)):
        reg = eng._registered[name]
        assert reg.compiled.shard_plan.num_devices == 2
        ref = sessions["banded"].compile(graph, targets, reg.compiled.cfg)
        want = np.asarray(ref.forward(reg.params, reg.features))
        np.testing.assert_allclose(by_rid[rid].logits, want, atol=1e-4)
    np.testing.assert_allclose(by_rid[2].logits, by_rid[0].logits[:5],
                               atol=1e-4)
