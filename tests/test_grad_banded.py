"""Gradient parity of the banded executor: ``jax.grad`` through the
Pallas NA kernels' custom VJPs must match the jnp segment-sum path for
every model family, plus finite-difference spot checks on the VJPs
themselves.

Seed-based (no hypothesis dependency): this file is part of the
no-hypothesis CI leg, so the fallback seed grid covers the VJP cases.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hgnn import HGNN, HGNNConfig
from repro.kernels import ops, ref
from repro.kernels.seg_sum import pack_edge_blocks, seg_sum_na
from repro.pipeline import (FrontendPipeline, PipelineConfig,
                            SemanticGraphCache)
from repro.train import (degree_bucket_labels, fit, make_train_step,
                         init_train_state, propagated_feature_labels,
                         semi_supervised_masks)

RNG = np.random.default_rng(7)

# same reduced workloads as tests/test_gfp_banded.py (MDM over MKM keeps
# interpret-mode block counts small)
WORKLOADS = {
    "acm_small": (["APA", "PAP", "PSP"], "P"),
    "imdb_small": (["AMA", "MAM", "MDM"], "M"),
}


@pytest.fixture(scope="module")
def frontends(request, acm_small, imdb_small):
    graphs = {"acm_small": acm_small, "imdb_small": imdb_small}
    out = {}
    for name, (targets, target_type) in WORKLOADS.items():
        pipe = FrontendPipeline(
            PipelineConfig(planner="ctt", backend="host", pack=True),
            cache=SemanticGraphCache())
        out[name] = (graphs[name], pipe.run(graphs[name], targets),
                     target_type)
    return out


def _random_stream(ns, nd, ne, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, ns, ne)
    dst = rng.integers(0, nd, ne)
    o = np.lexsort((src, dst))
    return src[o], dst[o]


# ------------------------------------------------- model-level parity --
@pytest.mark.parametrize("ds", sorted(WORKLOADS))
@pytest.mark.parametrize("model", ["rgcn", "rgat", "shgn"])
def test_loss_grads_match_jnp(frontends, ds, model):
    """jax.grad of execute_loss on the banded executor == the jnp executor to
    1e-4 for every parameter (including the attention vectors a_src /
    a_dst and the Simple-HGN edge-type embedding) AND the input
    features."""
    graph, res, target_type = frontends[ds]
    targets = WORKLOADS[ds][0]
    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    n = graph.num_vertices[target_type]
    labels = jnp.asarray(RNG.integers(0, 3, n).astype(np.int32))
    mask = jnp.asarray((np.arange(n) % 3 == 0).astype(np.float32))
    cfg = HGNNConfig(model=model, hidden=16, num_layers=2, num_classes=3,
                     target_type=target_type)
    m = HGNN(cfg, graph.feature_dims, graph.num_vertices, sorted(targets))
    params = m.init(jax.random.key(2))

    def loss_fn(graphs):
        return lambda p, f: m.execute_loss(p, f, graphs, labels, mask=mask)

    g_jnp = jax.grad(loss_fn(res.batches()), argnums=(0, 1))(
        params, feats)
    g_banded = jax.grad(loss_fn(res.banded_batches()),
                        argnums=(0, 1))(params, feats)
    flat_j, tree_j = jax.tree.flatten(g_jnp)
    flat_b, tree_b = jax.tree.flatten(g_banded)
    assert tree_j == tree_b
    for a, b in zip(flat_j, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    # the gradients must carry signal, not vacuous zeros
    assert max(float(jnp.abs(g).max()) for g in flat_j) > 0


def test_attention_param_grads_nonzero(frontends):
    """No stop_gradient holes: the attention parameters of the banded
    path receive nonzero gradients (they only get them through the fused
    kernel's logits cotangent)."""
    graph, res, target_type = frontends["acm_small"]
    targets = WORKLOADS["acm_small"][0]
    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    n = graph.num_vertices[target_type]
    labels = jnp.asarray(RNG.integers(0, 3, n).astype(np.int32))
    cfg = HGNNConfig(model="shgn", hidden=16, num_layers=2, num_classes=3,
                     target_type=target_type)
    m = HGNN(cfg, graph.feature_dims, graph.num_vertices, sorted(targets))
    params = m.init(jax.random.key(3))
    grads = jax.grad(
        lambda p: m.execute_loss(p, feats, res.banded_batches(), labels))(params)
    # only PAP/PSP can influence the P-type head in this workload (APA is
    # A -> A, and nothing live consumes h[A]); their attention params must
    # get gradients in EVERY layer — a stop_gradient hole anywhere in the
    # fused kernel path would zero them
    for li, lp in enumerate(grads["layers"]):
        for mp in ("PAP", "PSP"):
            assert float(jnp.abs(lp["na"][mp]["a_src"]).max()) > 0, (li, mp)
            assert float(jnp.abs(lp["na"][mp]["a_dst"]).max()) > 0, (li, mp)
        assert float(jnp.abs(lp["a_edge"]).max()) > 0, li
        assert float(jnp.abs(lp["edge_emb"]).max()) > 0, li


@pytest.mark.parametrize("ds", sorted(WORKLOADS))
def test_shgn_attention_param_grads_match_jnp(frontends, ds):
    """Simple-HGN's attention parameters reach the banded loss only through
    the per-vertex score columns the kernels read (``a_src``, ``a_dst``)
    and the bias folded into the destination's (``edge_emb``, ``a_edge``):
    their banded gradients match the jnp executor's and are not zero."""
    graph, res, target_type = frontends[ds]
    targets = WORKLOADS[ds][0]
    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    n = graph.num_vertices[target_type]
    labels = jnp.asarray(RNG.integers(0, 3, n).astype(np.int32))
    cfg = HGNNConfig(model="shgn", hidden=16, num_layers=2, num_classes=3,
                     target_type=target_type)
    m = HGNN(cfg, graph.feature_dims, graph.num_vertices, sorted(targets))
    params = m.init(jax.random.key(4))

    def grads(graphs):
        return jax.grad(lambda p: m.execute_loss(p, feats, graphs, labels))(params)

    g_j, g_b = grads(res.batches()), grads(res.banded_batches())
    for li, (lj, lb) in enumerate(zip(g_j["layers"], g_b["layers"])):
        pairs = [(lj[k], lb[k]) for k in ("edge_emb", "a_edge")]
        pairs += [(lj["na"][mp][k], lb["na"][mp][k])
                  for mp in targets for k in ("a_src", "a_dst")]
        for want, got in pairs:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-4)
        assert float(jnp.abs(lb["a_edge"]).max()) > 0, li
        assert float(jnp.abs(lb["edge_emb"]).max()) > 0, li


# ------------------------------------------------------ op-level VJPs --
def test_seg_sum_na_grad_matches_ref():
    """Banded matvec VJP == jnp oracle gradient wrt features and blocked
    weights on random streams (incl. multi-band, tile-revisit shapes)."""
    for seed, (ns, nd, ne) in enumerate([(300, 150, 1200), (1100, 400, 3000)]):
        src, dst = _random_stream(ns, nd, ne, seed)
        packed = pack_edge_blocks(src, dst, ns, nd)
        h = jnp.asarray(RNG.standard_normal((ns, 8)), jnp.float32)
        r = jnp.asarray(RNG.standard_normal((nd, 8)), jnp.float32)

        g_b = jax.grad(
            lambda x: jnp.sum(seg_sum_na(packed, x, interpret=True) * r))(h)
        g_r = jax.grad(
            lambda x: jnp.sum(ref.seg_sum_na_ref(src, dst, x, nd) * r))(h)
        np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_r),
                                   atol=1e-5)

        w_flat = jnp.asarray(RNG.random(ne), jnp.float32)
        wb = packed.scatter_blocks(w_flat)
        gw = jax.grad(lambda w: jnp.sum(
            seg_sum_na(packed, h, interpret=True, weights=w) * r))(wb)
        gw_ref = jax.grad(lambda w: jnp.sum(
            ref.seg_sum_na_ref(src, dst, h, nd, weight=w) * r))(w_flat)
        blk, slot = packed.edge_map()
        np.testing.assert_allclose(np.asarray(gw)[blk, slot],
                                   np.asarray(gw_ref), atol=1e-5)


def test_seg_sum_na_vjp_finite_difference():
    """Central finite differences confirm the custom VJP analytically —
    the parity tests alone would pass if *both* executors shared a wrong
    gradient."""
    ns, nd, ne = 96, 48, 300
    src, dst = _random_stream(ns, nd, ne, 5)
    packed = pack_edge_blocks(src, dst, ns, nd)
    h0 = RNG.standard_normal((ns, 4)).astype(np.float32)
    r = jnp.asarray(RNG.standard_normal((nd, 4)), jnp.float32)

    def f(x):
        return float(jnp.sum(seg_sum_na(packed, jnp.asarray(x),
                                        interpret=True) * r))

    grad = np.asarray(jax.grad(
        lambda x: jnp.sum(seg_sum_na(packed, x, interpret=True) * r)
    )(jnp.asarray(h0)))
    eps = 1e-2  # fp32 central differences: sqrt-ish step
    for i, j in [(0, 0), (7, 3), (31, 2), (95, 1), (50, 0)]:
        hp, hm = h0.copy(), h0.copy()
        hp[i, j] += eps
        hm[i, j] -= eps
        fd = (f(hp) - f(hm)) / (2 * eps)
        np.testing.assert_allclose(grad[i, j], fd, atol=5e-2, rtol=5e-2)


def test_na_attention_packed_grads_match_ref():
    """Attention VJP (per-vertex scores + features) == differentiating the
    jnp oracle composite; the scores give logits of both signs, so both
    branches of the leaky ReLU's derivative are taken."""
    ns, nd, ne = 250, 120, 900
    src, dst = _random_stream(ns, nd, ne, 9)
    packed = pack_edge_blocks(src, dst, ns, nd)
    h = jnp.asarray(RNG.standard_normal((ns, 8)), jnp.float32)
    r = jnp.asarray(RNG.standard_normal((nd, 8)), jnp.float32)
    e_s = jnp.asarray(RNG.standard_normal(ns), jnp.float32)
    e_d = jnp.asarray(RNG.standard_normal(nd), jnp.float32)
    pre = np.asarray(e_s)[src] + np.asarray(e_d)[dst]
    assert (pre < 0).any() and (pre > 0).any()

    def f_banded(a, b, x):
        return jnp.sum(ops.na_attention_packed(packed, a, b, x,
                                               backend="interpret") * r)

    def f_ref(a, b, x):
        return jnp.sum(ops.na_attention_aggregate(src, dst, a, b, x, nd,
                                                  backend="jnp") * r)

    g_b = jax.grad(f_banded, argnums=(0, 1, 2))(e_s, e_d, h)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(e_s, e_d, h)
    for b, want in zip(g_b, g_r):
        np.testing.assert_allclose(np.asarray(b), np.asarray(want), atol=1e-5)


# -------------------------------------------------- train-step plumbing --
def test_train_step_banded_reuses_packing(frontends):
    """A jitted banded train step runs multiple steps on one cached
    BandedBatch list without re-packing (grad-safe reuse) and decreases
    the loss."""
    import repro.kernels.ops as ops_mod
    import repro.kernels.seg_sum as seg_sum_mod

    graph, res, target_type = frontends["acm_small"]
    targets = WORKLOADS["acm_small"][0]
    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    n = graph.num_vertices[target_type]
    labels = degree_bucket_labels(res.semantic, targets, n)
    masks = semi_supervised_masks(n, seed=1)
    cfg = HGNNConfig(model="rgcn", hidden=16, num_layers=2, num_classes=3,
                     target_type=target_type)
    m = HGNN(cfg, graph.feature_dims, graph.num_vertices, sorted(targets))
    banded = res.banded_batches()
    state = init_train_state(m, jax.random.key(0))
    step = make_train_step(m, banded, total=8)

    def _boom(*a, **k):
        raise AssertionError("pack_edge_blocks called inside the train step")

    # patch BOTH namespaces: ops.py binds the packer by name at import
    # time, so patching only the defining module would miss its callers
    orig_seg, orig_ops = seg_sum_mod.pack_edge_blocks, ops_mod.pack_edge_blocks
    seg_sum_mod.pack_edge_blocks = _boom
    ops_mod.pack_edge_blocks = _boom
    try:
        losses = []
        for _ in range(8):
            state, loss = step(state, feats, labels, masks["train"])
            losses.append(float(loss))
    finally:
        seg_sum_mod.pack_edge_blocks = orig_seg
        ops_mod.pack_edge_blocks = orig_ops
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_fit_banded_converges_like_jnp(frontends):
    """Short full training runs on both executors reach the same
    accuracy (identical seeds -> near-identical trajectories)."""
    graph, res, target_type = frontends["acm_small"]
    targets = WORKLOADS["acm_small"][0]
    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    n = graph.num_vertices[target_type]
    labels = propagated_feature_labels(res.semantic, targets,
                                       graph.features, n)
    masks = semi_supervised_masks(n, seed=0)
    cfg = HGNNConfig(model="rgat", hidden=32, num_layers=2, num_classes=3,
                     target_type=target_type)
    m = HGNN(cfg, graph.feature_dims, graph.num_vertices, sorted(targets))
    out_j = fit(m, res.batches(), feats, labels, masks, epochs=40)
    out_b = fit(m, res.banded_batches(), feats, labels, masks, epochs=40)
    assert out_j["train_acc"] >= 0.9
    assert out_b["train_acc"] >= out_j["train_acc"] - 0.01
    assert out_b["val_acc"] >= out_j["val_acc"] - 0.02
