"""K-hop dependency extraction and the dependency-mode subset forward.

Covers the extractor invariants (frontier monotonicity, memo reuse, the
bucket-signature no-retrace guard), exact parity of
``forward_subset(mode="dependency")`` against full-forward rows on both
executors, the serving engine's dependency mode with its
closure-coverage fallback, the empty-submit/drained-step no-ops, and the
``_hash_tokens`` overflow-warning regression.
"""
import warnings

import numpy as np
import pytest

from proptest import seeded_property
from repro.api import ExecutorSpec, ServePolicy, Session, device_features
from repro.core.hgnn import HGNNConfig
from repro.pipeline import SemanticGraphCache
from repro.serve import HGNNRequest, HGNNServeEngine

WORKLOADS = {
    "acm_small": (["APA", "PAP", "PSP"], "P"),
    "imdb_small": (["AMA", "MAM", "MDM"], "M"),
}


def _cfg(model, target_type, **kw):
    kw.setdefault("hidden", 16)
    kw.setdefault("num_layers", 2)
    return HGNNConfig(model=model, num_classes=3, target_type=target_type,
                      **kw)


@pytest.fixture(scope="module")
def sessions(acm_small, imdb_small):
    """One jnp and one banded session over a shared cache, plus graphs."""
    cache = SemanticGraphCache()
    return {
        "jnp": Session(ExecutorSpec(na_executor="jnp"), cache=cache),
        "banded": Session(ExecutorSpec(na_executor="banded"), cache=cache),
        "graphs": {"acm_small": acm_small, "imdb_small": imdb_small},
    }


def _compiled(sessions, executor, ds, model):
    graph = sessions["graphs"][ds]
    targets, target_type = WORKLOADS[ds]
    return graph, sessions[executor].compile(graph, targets,
                                             _cfg(model, target_type))


# ---------------------------------------------------- extractor invariants --
@seeded_property(max_examples=15)
def test_frontier_monotone(sessions, seed):
    """F_{k+1}[t] ⊇ F_k[t] for every hop and vertex type, and hop 0 is
    exactly the requested ids on the target type."""
    _, c = _compiled(sessions, "jnp", "acm_small", "rgcn")
    rng = np.random.default_rng(seed)
    ids = np.unique(rng.integers(0, c.num_target,
                                 size=int(rng.integers(1, 12))))
    sub = c.dependency_subset(ids)
    assert np.array_equal(sub.hops[0][c.cfg.target_type], ids)
    assert len(sub.hops) == c.cfg.num_layers + 1
    for k in range(len(sub.hops) - 1):
        for t, prev in sub.hops[k].items():
            nxt = sub.hops[k + 1][t]
            assert np.isin(prev, nxt).all(), (k, t)
    # the closure IS the last frontier, and coverage is its size ratio
    for t, v in sub.closure.items():
        assert np.array_equal(v, sub.hops[-1][t])
    assert 0.0 <= sub.coverage <= 1.0


def test_extract_memoized_and_order_insensitive(sessions):
    """Resubmission — any order, duplicates allowed — returns the
    identical DependencySubset object (device arrays included)."""
    _, c = _compiled(sessions, "jnp", "acm_small", "rgcn")
    a = c.dependency_subset(np.array([9, 3, 7]))
    b = c.dependency_subset(np.array([3, 7, 9, 9, 3]))
    assert a is b
    assert np.array_equal(a.node_ids, [3, 7, 9])


def test_extract_rejects_out_of_bounds(sessions):
    _, c = _compiled(sessions, "jnp", "acm_small", "rgcn")
    with pytest.raises(ValueError, match="out of bounds"):
        c.dependency_subset(np.array([0, c.num_target]), validate=False)


# ---------------------------------------------------------------- parity --
@pytest.mark.parametrize("executor", ["jnp", "banded"])
@pytest.mark.parametrize("ds", sorted(WORKLOADS))
@pytest.mark.parametrize("model", ["rgcn", "shgn"])
def test_dependency_forward_matches_full_rows(sessions, executor, ds, model):
    """forward_subset(mode="dependency") rows == the full forward's rows
    for random id sets, on both executors (mean and attention NA)."""
    graph, c = _compiled(sessions, executor, ds, model)
    params = c.init(0)
    feats = device_features(graph)
    full = np.asarray(c.forward(params, feats))
    rng = np.random.default_rng(7)
    for size in (1, 13):
        ids = np.unique(rng.integers(0, c.num_target, size=size))
        dep = np.asarray(c.forward_subset(params, feats, ids,
                                          mode="dependency"))
        np.testing.assert_allclose(dep, full[ids], atol=1e-4)


@pytest.mark.parametrize("executor", ["jnp", "banded"])
def test_dependency_forward_takes_heads(sessions, executor):
    """HAN with eight heads: the dependency executor's per-head softmax and
    one kernel call per head give the full forward's rows, as does the
    head-gathering subset forward."""
    graph = sessions["graphs"]["acm_small"]
    targets, target_type = WORKLOADS["acm_small"]
    c = sessions[executor].compile(graph, targets, _cfg(
        "han", target_type, num_heads=8, num_layers=1))
    params = c.init(0)
    assert params["layers"][0]["na"]["PAP"]["a_src"].shape == (8, 2)
    feats = device_features(graph)
    full = np.asarray(c.forward(params, feats))
    ids = np.unique(np.random.default_rng(8).integers(0, c.num_target, size=13))
    dep = np.asarray(c.forward_subset(params, feats, ids, mode="dependency"))
    np.testing.assert_allclose(dep, full[ids], atol=1e-4)
    head = np.asarray(c.forward_subset(params, feats, ids))
    np.testing.assert_array_equal(head, full[ids])


def test_dependency_forward_restores_caller_order(sessions):
    """Unsorted / duplicated ids come back in the caller's order."""
    graph, c = _compiled(sessions, "jnp", "acm_small", "rgcn")
    params = c.init(0)
    feats = device_features(graph)
    full = np.asarray(c.forward(params, feats))
    ids = np.array([11, 2, 11, 5])
    dep = np.asarray(c.forward_subset(params, feats, ids,
                                      mode="dependency"))
    np.testing.assert_allclose(dep, full[ids], atol=1e-4)


# ------------------------------------------------------- no-retrace guard --
def test_dependency_no_retrace_within_bucket_signature(sessions):
    """Two extractions with equal bucket signatures share one trace: the
    dependency_traces counter must not move on the second call."""
    graph, c = _compiled(sessions, "jnp", "acm_small", "rgat")
    params = c.init(0)
    feats = device_features(graph)
    # probe host-side (extraction is pure numpy) until two distinct id
    # sets land in the same bucket signature
    rng = np.random.default_rng(0)
    sig_to_ids = {}
    pair = None
    for _ in range(64):
        ids = np.unique(rng.integers(0, c.num_target, size=9))
        sub = c.dependency_subset(ids)
        prev = sig_to_ids.get(sub.signature)
        if prev is not None and not np.array_equal(prev, sub.node_ids):
            pair = (prev, sub.node_ids)
            break
        sig_to_ids[sub.signature] = sub.node_ids
    assert pair is not None, "no signature collision in 64 probes"
    c.forward_subset(params, feats, pair[0], mode="dependency")
    traces = c.dependency_traces
    assert traces >= 1
    c.forward_subset(params, feats, pair[1], mode="dependency")
    assert c.dependency_traces == traces  # same signature, same trace


# ----------------------------------------------------------- serve engine --
def test_serve_dependency_mode(sessions):
    """A group of explicit-id requests under subset_mode="dependency" is
    served by the k-hop executor: responses say so and match the full
    forward row-for-row."""
    eng = HGNNServeEngine(
        session=sessions["jnp"],
        policy=ServePolicy(subset_threshold=0.5, subset_mode="dependency",
                           dependency_threshold=1.0))
    graph = sessions["graphs"]["acm_small"]
    eng.register("acm", graph, WORKLOADS["acm_small"][0], _cfg("rgcn", "P"),
                 seed=3)
    reqs = [HGNNRequest(0, "acm", nodes=np.array([4, 7])),
            HGNNRequest(1, "acm", nodes=np.array([7, 19]))]
    eng.submit(reqs)
    responses = {r.rid: r for r in eng.step()}
    assert all(r.mode == "dependency" for r in responses.values())
    reg = eng._registered["acm"]
    direct = np.asarray(reg.compiled.forward(reg.params, reg.features))
    np.testing.assert_allclose(responses[0].logits, direct[[4, 7]],
                               atol=1e-4)
    np.testing.assert_allclose(responses[1].logits, direct[[7, 19]],
                               atol=1e-4)
    st = eng.stats()
    assert st["forwards_dependency"] == 1 and st["forwards_full"] == 0


def test_serve_dependency_falls_back_when_closure_covers_graph(sessions):
    """dependency_threshold=0.0 makes every closure "too big": the group
    falls back to the plain full forward."""
    eng = HGNNServeEngine(
        session=sessions["jnp"],
        policy=ServePolicy(subset_threshold=1.0, subset_mode="dependency",
                           dependency_threshold=0.0))
    graph = sessions["graphs"]["acm_small"]
    eng.register("acm", graph, WORKLOADS["acm_small"][0], _cfg("rgcn", "P"),
                 seed=3)
    eng.submit(HGNNRequest(0, "acm", nodes=np.array([4, 7])))
    (resp,) = eng.step()
    assert resp.mode == "full"
    assert eng.stats()["forwards_dependency"] == 0


def test_serve_empty_submit_and_drained_step_are_noops(sessions):
    """submit([]) and step() on a drained queue return [] without
    touching admission state."""
    eng = HGNNServeEngine(session=sessions["jnp"])
    assert eng.submit([]) == []
    assert eng.step() == []
    st = eng.stats()
    assert st["requests_served"] == 0 and st["forwards"] == 0
    assert st["queued"] == 0


def test_serve_policy_validates_dependency_knobs():
    with pytest.raises(ValueError, match="subset_mode"):
        ServePolicy(subset_mode="spam")
    with pytest.raises(ValueError, match="dependency_threshold"):
        ServePolicy(dependency_threshold=1.5)


# ---------------------------------------------------- train-data warnings --
def test_hash_tokens_no_overflow_warning():
    """uint64 wraparound in the splitmix mixer is intended — the token
    generator must stay silent under error::RuntimeWarning (the tier-1
    filterwarnings policy) and keep its output in range."""
    from repro.train.data import _hash_tokens

    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        toks = _hash_tokens(3, np.arange(8), 16, 1000, seed=7)
        again = _hash_tokens(3, np.arange(8), 16, 1000, seed=7)
    assert toks.shape == (8, 16)
    assert toks.min() >= 0 and toks.max() < 1000
    np.testing.assert_array_equal(toks, again)  # counter-based: pure
