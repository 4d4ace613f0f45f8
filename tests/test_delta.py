"""Incremental semantic graphs: GraphDelta -> incremental SGB -> splice
repack -> session delta compile.

The load-bearing invariant, tested at every layer: the delta path's
products — semantic relations, restructure permutations, packed edge
blocks, and forward logits on both executors — are **bitwise-equal** to a
from-scratch rebuild of the mutated graph on a cold cache.  Incremental
is an optimization, never an approximation.
"""
import numpy as np
import pytest

from proptest import seeded_property
from repro.api import ExecutorSpec, Session, device_features
from repro.core.hgnn import HGNNConfig
from repro.hetero import GraphDelta, make_dataset
from repro.hetero.graph import HetGraph, Relation
from repro.kernels.seg_sum import pack_edge_blocks, splice_pack_edge_blocks
from repro.pipeline import FrontendPipeline, PipelineConfig, SemanticGraphCache

TARGETS = ["APA", "PAP", "PSP"]


def _pipe(cache=None):
    return FrontendPipeline(
        PipelineConfig(planner="ctt", backend="host", pack=True),
        cache=cache if cache is not None else SemanticGraphCache())


def _random_delta(graph, rng, *, allow_remove=True, allow_grow=True):
    """A mixed random delta over the base relations of ``graph``."""
    add_edges, remove_edges, add_vertices = {}, {}, {}
    names = sorted(graph.relations)
    for rname in rng.choice(names, size=rng.integers(1, 3), replace=False):
        r = graph.relations[rname]
        k = int(rng.integers(1, 9))
        if allow_remove and r.src.size > k and rng.random() < 0.3:
            take = rng.choice(r.src.size, size=k, replace=False)
            remove_edges[rname] = (r.src[take], r.dst[take])
        else:
            add_edges[rname] = (rng.integers(0, r.num_src, k),
                                rng.integers(0, r.num_dst, k))
    if allow_grow and rng.random() < 0.25:
        t = str(rng.choice(sorted(graph.num_vertices)))
        add_vertices[t] = int(rng.integers(1, 4))
    return GraphDelta(add_edges=add_edges, remove_edges=remove_edges,
                      add_vertices=add_vertices)


def _assert_frontend_equal(a, b, targets):
    """Bitwise equality of every frontend product for ``targets``."""
    for mp in targets:
        ra, rb = a.semantic[mp], b.semantic[mp]
        assert (ra.num_src, ra.num_dst) == (rb.num_src, rb.num_dst)
        np.testing.assert_array_equal(ra.src, rb.src)
        np.testing.assert_array_equal(ra.dst, rb.dst)
        ga, gb = a.restructured[mp], b.restructured[mp]
        for pa, pb in zip(ga.permutations(), gb.permutations()):
            np.testing.assert_array_equal(pa, pb)
        ka, kb = a.packed[mp], b.packed[mp]
        assert ka.num_blocks == kb.num_blocks
        # with the edge map, this set fully determines the packing
        for f in ("src_local", "dst_local", "band", "dst_tile",
                  "first_in_tile", "count", "edge_block_id", "edge_slot"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ka, f)), np.asarray(getattr(kb, f)),
                err_msg=f"{mp}.{f}")


# ------------------------------------------------------------ delta value --
def test_apply_delta_validates(acm_small):
    g = acm_small
    with pytest.raises(ValueError, match="unknown relation"):
        g.apply_delta(GraphDelta.insert("XX", [0], [0]))
    with pytest.raises(ValueError, match="unknown vertex type"):
        g.apply_delta(GraphDelta(add_vertices={"X": 1}))
    with pytest.raises(ValueError, match="out of range"):
        g.apply_delta(GraphDelta.insert(
            "PS", [g.relations["PS"].num_src], [0]))
    with pytest.raises(ValueError, match="not in the graph"):
        # (0, 0) twice: even if present once, an absent partner raises;
        # pick an edge guaranteed absent by removing it twice
        src, dst = g.relations["PS"].src[:1], g.relations["PS"].dst[:1]
        g2 = g.apply_delta(GraphDelta.remove("PS", src, dst))
        g2.apply_delta(GraphDelta.remove("PS", src, dst))


def test_apply_delta_roundtrip_and_vertex_growth(acm_small):
    g = acm_small
    r = g.relations["PS"]
    d = GraphDelta(add_edges={"PS": ([r.num_src - 1], [r.num_dst - 1])},
                   add_vertices={"P": 3})
    g2 = g.apply_delta(d)
    assert g2.num_vertices["P"] == g.num_vertices["P"] + 3
    assert g2.features["P"].shape[0] == g.features["P"].shape[0] + 3
    assert np.all(g2.features["P"][-3:] == 0)
    assert g2.relations["PS"].num_src == r.num_src + 3
    # removing the inserted edge restores the edge set
    g3 = g2.apply_delta(GraphDelta.remove(
        "PS", [r.num_src - 1], [r.num_dst - 1]))
    np.testing.assert_array_equal(g3.relations["PS"].src, r.src)
    np.testing.assert_array_equal(g3.relations["PS"].dst, r.dst)


def test_fingerprint_insertion_order_invariant(acm_small):
    """A delta-applied graph and an identically-rebuilt graph hash equal:
    the fingerprint covers the edge *set*, not the stored edge order."""
    g = acm_small
    rng = np.random.default_rng(0)
    r = g.relations["PS"]
    d = GraphDelta.insert("PS", rng.integers(0, r.num_src, 8),
                          rng.integers(0, r.num_dst, 8))
    g2 = g.apply_delta(d)
    # rebuild from scratch with every relation's edges in shuffled order
    relations = {}
    for rname, rel in g2.relations.items():
        perm = rng.permutation(rel.src.size)
        relations[rname] = Relation(
            rel.src_type, rel.dst_type, rel.num_src, rel.num_dst,
            rel.src[perm], rel.dst[perm])
    rebuilt = HetGraph(name=g2.name, num_vertices=dict(g2.num_vertices),
                       feature_dims=dict(g2.feature_dims),
                       relations=relations, features=dict(g2.features))
    assert rebuilt.fingerprint() == g2.fingerprint()
    assert g2.fingerprint() != g.fingerprint()


# ---------------------------------------------------------- cache lineage --
def test_cache_migrate_moves_untouched_and_returns_stale(acm_small):
    cache = SemanticGraphCache()
    pipe = _pipe(cache)
    res = pipe.run(acm_small, TARGETS)
    fp_old = acm_small.fingerprint()
    d = GraphDelta.insert("PS", [0], [0])
    dres = pipe.apply_delta(acm_small, d, TARGETS)
    fp_new = dres.graph.fingerprint()
    assert dres.touched == ["PSP"]
    assert cache.lineage[fp_new] == fp_old
    assert cache.stats.migrations == dres.migrated > 0
    # untouched products moved in place: the very objects survive
    assert cache.get_relation(fp_new, "APA") is res.semantic["APA"]
    # nothing rots under the old fingerprint
    assert not any(k[1] == fp_old for k in cache._store)
    # a second run over the new graph is pure cache
    res2 = pipe.run(dres.graph, TARGETS)
    assert res2.sgb is None


# -------------------------------------------------------- splice equality --
@seeded_property(max_examples=20)
def test_splice_pack_matches_full_pack(seed):
    """Splicing an edited scheduled stream into a cached packing is
    bitwise-equal to packing the edited stream from scratch."""
    rng = np.random.default_rng(seed)
    n_src, n_dst = int(rng.integers(40, 900)), int(rng.integers(40, 900))
    e = int(rng.integers(1, 4000))
    src = rng.integers(0, n_src, e).astype(np.int32)
    dst = rng.integers(0, n_dst, e).astype(np.int32)
    old = pack_edge_blocks(src, dst, n_src, n_dst)
    # random edit window: replace [i:j) with a fresh random run
    i = int(rng.integers(0, e + 1))
    j = int(rng.integers(i, e + 1))
    k = int(rng.integers(0, 64))
    ns = np.concatenate([src[:i], rng.integers(0, n_src, k).astype(np.int32),
                         src[j:]])
    nd = np.concatenate([dst[:i], rng.integers(0, n_dst, k).astype(np.int32),
                         dst[j:]])
    if ns.size == 0:
        return
    out = splice_pack_edge_blocks(ns, nd, src, dst, old, n_src, n_dst)
    if out is None:
        return  # legal fallback (empty stream / geometry mismatch)
    spliced, reused, total = out
    full = pack_edge_blocks(ns, nd, n_src, n_dst)
    assert 0 <= reused <= total == full.num_blocks
    for f in ("src_local", "dst_local", "band", "dst_tile",
              "first_in_tile", "count", "edge_block_id", "edge_slot"):
        np.testing.assert_array_equal(
            np.asarray(getattr(spliced, f)), np.asarray(getattr(full, f)),
            err_msg=f)


@seeded_property(max_examples=20)
def test_splice_pack_inside_multiband_tile_run(seed):
    """An edit inside one destination tile's run whose edges span several
    source bands (so the packer groups the run by band, and the run's
    blocks are not contiguous in the stream) splices bitwise-equal to a
    full pack of the edited stream."""
    rng = np.random.default_rng(seed)
    n_src, n_dst = int(rng.integers(1100, 3000)), int(rng.integers(130, 520))
    e = int(rng.integers(200, 4000))
    src = rng.integers(0, n_src, e).astype(np.int32)
    dst = rng.integers(0, n_dst, e).astype(np.int32)
    o = np.lexsort((src, dst))  # one tile run per tile, many bands each
    src, dst = src[o], dst[o]
    old = pack_edge_blocks(src, dst, n_src, n_dst)
    i = int(rng.integers(0, e))
    tile = dst[i] // 128
    run = np.flatnonzero(dst // 128 == tile)
    j = int(rng.integers(i, run[-1] + 2))
    k = int(rng.integers(2, 64))
    ins_dst = (tile * 128 + rng.integers(0, 128, k)).clip(max=n_dst - 1)
    ins_src = rng.integers(0, n_src, k)
    ins_src[[0, -1]] = rng.integers(0, 512), 512 + rng.integers(0, 512)
    ns = np.concatenate([src[:i], ins_src.astype(np.int32), src[j:]])
    nd = np.concatenate([dst[:i], ins_dst.astype(np.int32), dst[j:]])
    nrun = np.flatnonzero(nd // 128 == tile)
    assert np.unique(ns[nrun] // 512).size >= 2  # a multi-band tile run
    out = splice_pack_edge_blocks(ns, nd, src, dst, old, n_src, n_dst)
    assert out is not None
    spliced, reused, total = out
    full = pack_edge_blocks(ns, nd, n_src, n_dst)
    assert 0 <= reused <= total == full.num_blocks
    for f in ("src_local", "dst_local", "band", "dst_tile",
              "first_in_tile", "count", "edge_block_id", "edge_slot"):
        np.testing.assert_array_equal(
            np.asarray(getattr(spliced, f)), np.asarray(getattr(full, f)),
            err_msg=f)


# --------------------------------------------- pipeline delta == rebuild --
@seeded_property(max_examples=6)
def test_delta_pipeline_bitwise_equals_rebuild(seed):
    """The acceptance property: ``FrontendPipeline.apply_delta`` products
    are bitwise-equal to a cold rebuild of the mutated graph — for mixed
    insert/remove/vertex-growth deltas (removals fall back to full
    recompose of touched products; equality must hold regardless)."""
    g = make_dataset("ACM", scale=0.15)
    rng = np.random.default_rng(seed)
    pipe = _pipe()
    pipe.run(g, TARGETS)
    d = _random_delta(g, rng)
    dres = pipe.apply_delta(g, d, TARGETS)
    cold = _pipe().run(g.apply_delta(d), TARGETS)
    assert dres.graph.fingerprint() == g.apply_delta(d).fingerprint()
    _assert_frontend_equal(dres.result, cold, TARGETS)


def test_delta_forward_logits_bitwise_both_executors(acm_small):
    """Forward logits after a session delta compile are bitwise-equal to
    a cold compile of the mutated graph, on the jnp and banded executors
    (same products -> same jitted program -> same floats)."""
    g = acm_small
    rng = np.random.default_rng(3)
    r = g.relations["PS"]
    d = GraphDelta.insert("PS", rng.integers(0, r.num_src, 6),
                          rng.integers(0, r.num_dst, 6))
    cfg = HGNNConfig(model="rgcn", hidden=16, num_layers=2, num_classes=3,
                     target_type="P")
    for na in ("jnp", "banded"):
        sess = Session(ExecutorSpec(na_executor=na))
        c1 = sess.compile(g, TARGETS, cfg)
        params = c1.init(0)
        c2, g2, _ = sess.compile_delta(c1, g, d)
        cold = Session(ExecutorSpec(na_executor=na)).compile(g2, TARGETS, cfg)
        feats = device_features(g2)
        np.testing.assert_array_equal(
            np.asarray(c2.forward(params, feats)),
            np.asarray(cold.forward(params, feats)), err_msg=na)


def test_chained_deltas_keep_lineage_and_equality(acm_small):
    """Two deltas in sequence: migration chains fingerprints and the end
    state still bitwise-matches a cold rebuild."""
    cache = SemanticGraphCache()
    pipe = _pipe(cache)
    pipe.run(acm_small, TARGETS)
    d1 = GraphDelta.insert("TP", [0, 1], [2, 3])
    r1 = pipe.apply_delta(acm_small, d1, TARGETS)
    assert r1.touched == []  # TP is outside every target metapath
    d2 = GraphDelta.insert("PS", [5], [1])
    r2 = pipe.apply_delta(r1.graph, d2, TARGETS)
    fp0, fp1, fp2 = (acm_small.fingerprint(), r1.graph.fingerprint(),
                     r2.graph.fingerprint())
    assert cache.lineage == {fp1: fp0, fp2: fp1}
    cold = _pipe().run(acm_small.apply_delta(d1).apply_delta(d2), TARGETS)
    _assert_frontend_equal(r2.result, cold, TARGETS)
