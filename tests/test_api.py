"""repro.api execution sessions: spec validation, compile-once reuse,
jnp/banded parity through one Session, and the multi-tenant
HGNNServeEngine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutorSpec, Session, device_features
from repro.core.hgnn import BandedBatch, HGNNConfig, SemanticGraphBatch
from repro.pipeline import SemanticGraphCache
from repro.serve import HGNNRequest, HGNNServeEngine

# IMDB uses MDM over the keyword-hub MKM: same coverage, ~4x fewer edge
# blocks (interpret-mode kernels unroll one jaxpr step per block)
WORKLOADS = {
    "acm_small": (["APA", "PAP", "PSP"], "P"),
    "imdb_small": (["AMA", "MAM", "MDM"], "M"),
}
MODELS = ("rgcn", "rgat", "shgn")


def _cfg(model, target_type, **kw):
    kw.setdefault("hidden", 32)
    kw.setdefault("num_layers", 2)
    return HGNNConfig(model=model, num_classes=3, target_type=target_type,
                      **kw)


@pytest.fixture(scope="module")
def sessions(acm_small, imdb_small):
    """One jnp + one banded session over ONE shared cache (the
    two-executor scenario), with the fixture graphs attached."""
    cache = SemanticGraphCache()
    return {
        "jnp": Session(ExecutorSpec(), cache=cache),
        "banded": Session(ExecutorSpec(na_executor="banded"), cache=cache),
        "graphs": {"acm_small": acm_small, "imdb_small": imdb_small},
    }


# ------------------------------------------------------- spec validation --
def test_spec_banded_implies_packing():
    assert ExecutorSpec().pack is False
    assert ExecutorSpec(na_executor="banded").pack is True
    assert ExecutorSpec(pack=True).pack is True  # jnp may pre-pack
    with pytest.raises(ValueError, match="implies packing"):
        ExecutorSpec(na_executor="banded", pack=False)


def test_spec_banded_needs_restructure_and_kernels():
    with pytest.raises(ValueError, match="restructure"):
        ExecutorSpec(na_executor="banded", restructure=False)
    # packing needs the restructured schedule on the jnp executor too —
    # caught at spec construction, not later at Session()
    with pytest.raises(ValueError, match="restructure"):
        ExecutorSpec(pack=True, restructure=False)
    with pytest.raises(ValueError, match="kernels only"):
        ExecutorSpec(na_executor="banded", kernel_backend="jnp")
    # legal for the SGB device composer, though
    ExecutorSpec(sgb_backend="device", kernel_backend="jnp")


@pytest.mark.parametrize("field,value", [
    ("planner", "astar"), ("sgb_backend", "fpga"),
    ("na_executor", "sparse"), ("kernel_backend", "cuda"),
])
def test_spec_rejects_unknown_enums(field, value):
    with pytest.raises(ValueError, match=field):
        ExecutorSpec(**{field: value})


def test_spec_kernel_backend_follows_platform():
    """The default kernel backend is the platform's (interpret mode on
    this CPU host); naming 'pallas' where JAX has no TPU is an error at
    construction, never a silent fallback to the interpreter."""
    from repro.kernels.backend import platform_backend, resolve

    assert ExecutorSpec().kernel_backend is None
    assert resolve(None) == platform_backend()
    if jax.default_backend() != "tpu":
        assert platform_backend() == "interpret"
        with pytest.raises(RuntimeError, match="needs a TPU"):
            ExecutorSpec(na_executor="banded", kernel_backend="pallas")
        with pytest.raises(RuntimeError, match="needs a TPU"):
            resolve("pallas")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve("jnp")


_CACHE_CHILD = """
import sys
import jax
import jax.numpy as jnp
from repro import compile_cache
if sys.argv[1] != "-":  # stand-in for <checkout>/.jax_cache
    compile_cache.CHECKOUT_CACHE_DIR = compile_cache.Path(sys.argv[1])
print(compile_cache.enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1).lower(jnp.ones(3)).compile()
"""


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_is_env_or_checkout(tmp_path, env_dir):
    """$JAX_COMPILATION_CACHE_DIR wins and the helper sets no other path;
    without it the cache goes to the fixed in-checkout directory.  A
    child process compiles, because JAX reads the variable at import."""
    import os
    import subprocess
    import sys

    from repro import compile_cache

    checkout = compile_cache.CHECKOUT_CACHE_DIR
    assert checkout.name == ".jax_cache"
    assert (checkout.parent / "src" / "repro" / "compile_cache.py").is_file()
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(checkout.parent / "src"), env.get("PYTHONPATH", "")])
    if env_dir is None:
        want, arg = tmp_path / "checkout-cache", str(tmp_path / "checkout-cache")
    else:
        want, arg = tmp_path / env_dir, "-"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", _CACHE_CHILD, arg], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(want), str(want)]
    assert any(p.name.startswith("jit__lambda") for p in want.iterdir())
    assert sorted(p.name for p in tmp_path.iterdir()) == [want.name]


def test_spec_lowers_to_pipeline_config():
    pc = ExecutorSpec(na_executor="banded").pipeline_config()
    assert pc.pack and pc.restructure and pc.renumbered
    assert pc.backend == "host"


def test_device_sgb_jnp_compose_spec_runs_end_to_end(sessions):
    """kernel_backend='jnp' is legal for the SGB device composer; the jnp
    NA executor never consults the kernel backend, so the spec reaches
    the model unchanged (no remap to another kernel backend) and a
    compiled model from it runs, matching the host-spec result."""
    spec = ExecutorSpec(sgb_backend="device", kernel_backend="jnp")
    assert not hasattr(spec, "na_kernel_backend")
    graph = sessions["graphs"]["acm_small"]
    targets, target_type = WORKLOADS["acm_small"]
    cfg = _cfg("rgcn", target_type, num_layers=1)
    c_dev = Session(spec).compile(graph, targets, cfg)
    c_host = sessions["jnp"].compile(graph, targets, cfg)
    feats = device_features(graph)
    np.testing.assert_allclose(
        np.asarray(c_dev.forward(c_dev.init(0), feats)),
        np.asarray(c_host.forward(c_host.init(0), feats)), atol=1e-6)


def test_session_memo_bounded_lru(sessions):
    """max_memo bounds the session's own pins; an evicted compile is
    rebuilt on the next request while handed-out objects keep working."""
    graph = sessions["graphs"]["acm_small"]
    targets, target_type = WORKLOADS["acm_small"]
    sess = Session(ExecutorSpec(), cache=sessions["jnp"].cache, max_memo=1)
    a = sess.compile(graph, targets, _cfg("rgcn", target_type, hidden=8))
    b = sess.compile(graph, targets, _cfg("rgat", target_type, hidden=8))
    assert len(sess._compiled) == 1  # rgcn's pin evicted
    a2 = sess.compile(graph, targets, _cfg("rgcn", target_type, hidden=8))
    assert a2 is not a  # rebuilt, not served from the memo
    assert b.forward(b.init(0), device_features(graph)).shape[0] > 0


# ------------------------------------------- compile: parity and binding --
@pytest.mark.parametrize("ds", sorted(WORKLOADS))
@pytest.mark.parametrize("model", MODELS)
def test_session_compile_parity(sessions, ds, model):
    """One Session per executor, compiled once, serves every model family
    on ACM and IMDB: the banded forward matches jnp to fp tolerance, and
    each compiled model carries the right batch flavor with no backend
    kwargs anywhere."""
    graph = sessions["graphs"][ds]
    targets, target_type = WORKLOADS[ds]
    cfg = _cfg(model, target_type)
    c_jnp = sessions["jnp"].compile(graph, targets, cfg)
    c_banded = sessions["banded"].compile(graph, targets, cfg)
    assert all(isinstance(g, SemanticGraphBatch) for g in c_jnp.graphs)
    assert all(isinstance(g, BandedBatch) for g in c_banded.graphs)
    params = c_jnp.init(0)
    feats = device_features(graph)
    out_j = c_jnp.forward(params, feats)
    out_b = c_banded.forward(params, feats)
    assert out_j.shape == (c_jnp.num_target, 3)
    assert not jnp.isnan(out_b).any()
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_b),
                               atol=1e-4)


def test_zero_host_repacking_across_models(sessions):
    """The cache-stats guard: after the first banded compile, compiling
    and running every other model family must touch neither the packer
    nor the pipeline again (one PackedEdges set serves the session)."""
    import repro.kernels.ops as ops_mod
    import repro.kernels.seg_sum as seg_sum_mod

    sess = sessions["banded"]
    graph = sessions["graphs"]["acm_small"]
    targets, target_type = WORKLOADS["acm_small"]
    # hidden=24 keeps these compiles distinct from every other test's, so
    # each one really exercises the compile path (not the compile memo)
    first = sess.compile(graph, targets, _cfg(MODELS[0], target_type,
                                              hidden=24))
    feats = device_features(graph)
    before = sess.stats()
    orig = seg_sum_mod.pack_edge_blocks

    def _boom(*a, **k):
        raise AssertionError("host re-packing after the first compile")

    # patch BOTH bindings: ops.py imported the packer at module load, so
    # its packed=None fallback path calls its own module-local name
    seg_sum_mod.pack_edge_blocks = _boom
    ops_mod.pack_edge_blocks = _boom
    try:
        for model in MODELS[1:]:
            c = sess.compile(graph, targets, _cfg(model, target_type,
                                                  hidden=24))
            c.forward(c.init(1), feats).block_until_ready()
            assert c.frontend is first.frontend  # session-served products
            for g_new, g_first in zip(c.graphs, first.graphs):
                assert g_new.packed is g_first.packed
    finally:
        seg_sum_mod.pack_edge_blocks = orig
        ops_mod.pack_edge_blocks = orig
    after = sess.stats()
    assert after.frontend_runs == before.frontend_runs
    assert after.cache_misses == before.cache_misses  # zero new cache work
    assert after.frontend_served > before.frontend_served


def test_compile_memoizes_identical_requests(sessions):
    sess = sessions["jnp"]
    graph = sessions["graphs"]["acm_small"]
    targets, target_type = WORKLOADS["acm_small"]
    cfg = _cfg("rgcn", target_type)
    a = sess.compile(graph, targets, cfg)
    before = sess.stats().compiles_cached
    b = sess.compile(graph, list(reversed(targets)), cfg)
    assert a is b  # target order is not identity
    assert sess.stats().compiles_cached == before + 1


# ------------------------------------------------------- model lifecycle --
def test_compiled_loss_fit_evaluate(sessions):
    from repro.train import propagated_feature_labels, semi_supervised_masks

    sess = sessions["jnp"]
    graph = sessions["graphs"]["acm_small"]
    targets, target_type = WORKLOADS["acm_small"]
    c = sess.compile(graph, targets, _cfg("rgat", target_type))
    feats = device_features(graph)
    labels = propagated_feature_labels(c.semantic, targets, graph.features,
                                       c.num_target)
    masks = semi_supervised_masks(c.num_target, seed=0)
    out = c.fit(feats, labels, masks, epochs=8)
    assert out["losses"][-1] < out["losses"][0]  # it trains
    params = out["state"].params
    acc = float(c.evaluate(params, feats, labels, masks["train"]))
    assert 0.0 <= acc <= 1.0
    # loss with mask=None equals an all-ones mask (shape-static trace)
    full = float(c.loss(params, feats, labels))
    ones = float(c.loss(params, feats, labels,
                        jnp.ones((c.num_target,), jnp.float32)))
    np.testing.assert_allclose(full, ones, rtol=1e-6)


# --------------------------------------------------------- serve engine --
@pytest.fixture()
def engine(sessions):
    eng = HGNNServeEngine(session=sessions["jnp"])
    acm = sessions["graphs"]["acm_small"]
    imdb = sessions["graphs"]["imdb_small"]
    eng.register("acm", acm, WORKLOADS["acm_small"][0],
                 _cfg("rgcn", "P"), seed=3)
    eng.register("imdb", imdb, WORKLOADS["imdb_small"][0],
                 _cfg("rgat", "M"), seed=4)
    return eng


def test_serve_batches_by_fingerprint(engine):
    """Requests against two registered graphs: grouped per graph, one
    compiled forward per group, responses match direct forwards and carry
    latency."""
    rng = np.random.default_rng(0)
    reqs = [
        HGNNRequest(0, "acm", nodes=rng.integers(0, 50, size=6)),
        HGNNRequest(1, "imdb"),
        HGNNRequest(2, "acm"),
        HGNNRequest(3, "imdb", nodes=np.array([0, 1])),
        HGNNRequest(4, "acm", nodes=np.array([7])),
    ]
    engine.submit(reqs)
    responses = engine.step()
    assert [r.rid for r in responses] in ([0, 2, 4, 1, 3], [1, 3, 0, 2, 4])
    by_rid = {r.rid: r for r in responses}
    assert by_rid[0].batched_with == 3 and by_rid[1].batched_with == 2

    # responses equal the compiled forward, sliced per request
    reg = engine._registered["acm"]
    direct = np.asarray(reg.compiled.forward(reg.params, reg.features))
    np.testing.assert_array_equal(by_rid[2].logits, direct)
    np.testing.assert_array_equal(by_rid[4].logits, direct[[7]])
    np.testing.assert_array_equal(by_rid[4].predictions,
                                  direct[[7]].argmax(-1))
    assert all(r.latency_us > 0 for r in responses)
    assert engine.step() == []  # queue drained

    st = engine.stats()
    assert st["requests_served"] == 5 and st["forwards"] == 2
    assert st["batching_factor"] == 2.5
    assert st["latency_us_p50"] > 0
    assert st["session"].hit_rate >= 0.0


def test_serve_rejects_unknown_graph_and_double_register(sessions, engine):
    with pytest.raises(KeyError, match="not registered"):
        engine.submit(HGNNRequest(9, "dblp"))
    with pytest.raises(ValueError, match="already registered"):
        engine.register("acm", sessions["graphs"]["acm_small"],
                        WORKLOADS["acm_small"][0], _cfg("rgcn", "P"))
    with pytest.raises(ValueError, match="not both"):
        HGNNServeEngine(session=sessions["jnp"], spec=ExecutorSpec())


def test_serve_shares_session_frontend(sessions):
    """Registering a second model over an already-compiled graph is pure
    session reuse — no pipeline run, no cache misses."""
    sess = sessions["jnp"]
    before = sess.stats()
    eng = HGNNServeEngine(session=sess)
    eng.register("acm2", sessions["graphs"]["acm_small"],
                 WORKLOADS["acm_small"][0], _cfg("shgn", "P"), warm=False)
    after = sess.stats()
    assert after.frontend_runs == before.frontend_runs
    assert after.cache_misses == before.cache_misses
