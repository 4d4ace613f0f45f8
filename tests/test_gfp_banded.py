"""Banded GFP executor: model-level parity with the jnp path, packer
vectorization equivalence, first-touch-ever tile semantics, and the
cached-packing attention op."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hgnn import HGNN, HGNNConfig
from repro.hetero import make_dataset
from repro.kernels import ops, ref
from repro.kernels.seg_sum import (pack_edge_blocks,
                                   pack_edge_blocks_reference, seg_sum_na,
                                   stream_tile_loads)
from repro.pipeline import (FrontendPipeline, PipelineConfig,
                            SemanticGraphCache)

RNG = np.random.default_rng(11)

# IMDB uses MDM over the keyword-hub MKM: same coverage (three semantic
# graphs, both dst types), ~4x fewer edge blocks — interpret-mode kernels
# unroll one jaxpr step per block, so block count is compile time here.
WORKLOADS = {
    "acm_small": (["APA", "PAP", "PSP"], "P"),
    "imdb_small": (["AMA", "MAM", "MDM"], "M"),
}

_PACKED_FIELDS = ("src_local", "dst_local", "band", "dst_tile",
                  "first_in_tile", "count")


@pytest.fixture(scope="module")
def frontends(request, acm_small, imdb_small):
    """One pack=True frontend pass per fixture graph, shared by the module
    (the multi-model scenario: every test below reuses these packings)."""
    graphs = {"acm_small": acm_small, "imdb_small": imdb_small}
    out = {}
    for name, (targets, target_type) in WORKLOADS.items():
        pipe = FrontendPipeline(
            PipelineConfig(planner="ctt", backend="host", pack=True),
            cache=SemanticGraphCache())
        out[name] = (graphs[name], pipe.run(graphs[name], targets),
                     target_type)
    return out


# --------------------------------------------------- model-level parity --
@pytest.mark.parametrize("ds", sorted(WORKLOADS))
@pytest.mark.parametrize("model", ["rgcn", "rgat", "shgn"])
def test_banded_matches_jnp(frontends, ds, model):
    """HGNN.execute on the banded Pallas path reproduces the segment-sum
    path to fp tolerance for every model on ACM and IMDB."""
    graph, res, target_type = frontends[ds]
    targets = WORKLOADS[ds][0]
    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    cfg = HGNNConfig(model=model, hidden=32, num_layers=2, num_classes=3,
                     target_type=target_type)
    m = HGNN(cfg, graph.feature_dims, graph.num_vertices, sorted(targets))
    params = m.init(jax.random.key(0))
    logits_jnp = m.execute(params, feats, res.batches())
    logits_banded = m.execute(params, feats, res.banded_batches(),
                              na_executor="banded")
    assert not jnp.isnan(logits_banded).any()
    np.testing.assert_allclose(np.asarray(logits_jnp),
                               np.asarray(logits_banded), atol=1e-4)


def test_packed_built_once_and_shared(frontends):
    """One PackedEdges per semantic graph, shared across models and
    layers: after the banded batches exist, running all three models must
    never call pack_edge_blocks again."""
    import repro.kernels.seg_sum as seg_sum_mod

    graph, res, target_type = frontends["acm_small"]
    targets = WORKLOADS["acm_small"][0]
    banded = res.banded_batches()
    assert res.banded_batches() is banded  # built once per result
    for b in banded:
        assert b.packed is res.packed[b.metapath]  # the pipeline's packing

    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    orig = seg_sum_mod.pack_edge_blocks

    def _boom(*a, **k):
        raise AssertionError("pack_edge_blocks called inside the model")

    seg_sum_mod.pack_edge_blocks = _boom
    try:
        for model in ("rgcn", "rgat", "shgn"):
            cfg = HGNNConfig(model=model, hidden=16, num_layers=2,
                             num_classes=3, target_type=target_type)
            m = HGNN(cfg, graph.feature_dims, graph.num_vertices,
                     sorted(targets))
            m.execute(m.init(jax.random.key(1)), feats, banded,
                      na_executor="banded").block_until_ready()
    finally:
        seg_sum_mod.pack_edge_blocks = orig


def test_execute_rejects_mismatched_batches(frontends):
    graph, res, target_type = frontends["acm_small"]
    targets = WORKLOADS["acm_small"][0]
    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    cfg = HGNNConfig(model="rgcn", hidden=16, num_layers=1, num_classes=3,
                     target_type=target_type)
    m = HGNN(cfg, graph.feature_dims, graph.num_vertices, sorted(targets))
    params = m.init(jax.random.key(0))
    with pytest.raises(TypeError):
        m.execute(params, feats, res.batches(), na_executor="banded")
    with pytest.raises(TypeError):
        m.execute(params, feats, res.banded_batches())
    with pytest.raises(ValueError):
        m.execute(params, feats, res.batches(), na_executor="spam")


def test_banded_batches_need_restructure(acm_small):
    pipe = FrontendPipeline(
        PipelineConfig(planner="ctt", restructure=False),
        cache=SemanticGraphCache())
    res = pipe.run(acm_small, ["APA"])
    with pytest.raises(ValueError):
        res.banded_batches()


def test_banded_batches_pack_on_demand(acm_small):
    """A model requesting banded batches triggers the packing even when
    the pipeline config didn't pre-pack (pack=False default)."""
    pipe = FrontendPipeline(
        PipelineConfig(planner="ctt", backend="host"),
        cache=SemanticGraphCache())
    res = pipe.run(acm_small, ["APA", "PAP"])
    assert not res.packed
    banded = res.banded_batches()
    assert {b.metapath for b in banded} == {"APA", "PAP"}
    for b in banded:
        assert b.packed is res.packed[b.metapath]  # kept for later models


# ------------------------------------------------------ packer semantics --
def test_packer_vectorized_equals_reference(frontends):
    """The vectorized packer is field-identical to the Python-loop packer,
    edge map included, on random streams and the restructured schedule."""
    streams = []
    for _ in range(8):
        ns, nd = int(RNG.integers(2, 1200)), int(RNG.integers(2, 900))
        ne = int(RNG.integers(1, 5000))
        src = RNG.integers(0, ns, ne)
        dst = RNG.integers(0, nd, ne)
        o = np.lexsort((src, dst))
        streams.append((src[o], dst[o], ns, nd, RNG.random(ne).astype(np.float32)))
    _, res, _ = frontends["acm_small"]
    for mp, rg in res.restructured.items():
        s, d = rg.scheduled_edges(renumbered=True)
        rel = rg.original
        streams.append((s, d, rel.num_src, rel.num_dst, None))
    for src, dst, ns, nd, w in streams:
        vec = pack_edge_blocks(src, dst, ns, nd, weight=w)
        loop = pack_edge_blocks_reference(src, dst, ns, nd, weight=w)
        for f in _PACKED_FIELDS:
            assert np.array_equal(getattr(vec, f), getattr(loop, f)), f
        # weights: eager when given, lazily-materialized ones-mask when not
        assert np.array_equal(vec.valid_weight(), loop.valid_weight())
        # both packers set the edge map explicitly, and agree on it
        assert np.array_equal(vec.edge_block_id, loop.edge_block_id)
        assert np.array_equal(vec.edge_slot, loop.edge_slot)


def _revisit_stream():
    """Tile 0 alternating between bands 0 and 1, tile 1, then tile 0
    again alternating: three tile runs, the last a non-consecutive
    revisit of tile 0 (dst 0 and 5 receive in both visits)."""
    src = np.array([0, 600, 1, 601, 2, 602,  # tile 0: bands 0,1,0,1,0,1
                    700, 3,                  # tile 1: bands 1,0
                    900, 4, 901, 5])         # tile 0: bands 1,0,1,0
    dst = np.array([0, 0, 5, 5, 7, 9,
                    130, 131,
                    0, 5, 5, 9])
    return src, dst, 1024, 256


def test_alternating_bands_in_one_tile_pack_into_two_blocks():
    """One tile run whose edges alternate between two bands: one block
    per band (the stream-order cut made one block per flip, ten here),
    each holding its band's edges in stream order."""
    src = np.array([0, 600, 1, 601, 2, 602, 3, 603, 4, 604])
    dst = np.array([0, 0, 1, 1, 2, 2, 3, 3, 4, 4])
    packed = pack_edge_blocks(src, dst, 1024, 128)
    assert packed.num_blocks == 2
    np.testing.assert_array_equal(packed.band, [0, 1])
    np.testing.assert_array_equal(packed.dst_tile, [0, 0])
    np.testing.assert_array_equal(packed.first_in_tile, [1, 0])
    np.testing.assert_array_equal(packed.count, [5, 5])
    np.testing.assert_array_equal(packed.edge_block_id,
                                  [0, 1, 0, 1, 0, 1, 0, 1, 0, 1])
    np.testing.assert_array_equal(packed.edge_slot,
                                  [0, 0, 1, 1, 2, 2, 3, 3, 4, 4])
    np.testing.assert_array_equal(packed.src_local[:, :5],
                                  [[0, 1, 2, 3, 4], [88, 89, 90, 91, 92]])
    np.testing.assert_array_equal(packed.dst_local[:, :5],
                                  [[0, 1, 2, 3, 4], [0, 1, 2, 3, 4]])
    assert packed.fill == pytest.approx(10 / 512)


def test_stream_tile_loads_counts_runs_in_stream_order():
    """The schedule's locality meter walks the stream in its own order:
    one load per edge_block chunk of each (dst tile, band) run, so the
    alternating stream the packer fits in 2 blocks costs one load per
    flip, and a band-sorted tile run costs one per band."""
    src = np.array([0, 600, 1, 601, 2, 602, 3, 603, 4, 604])
    dst = np.array([0, 0, 1, 1, 2, 2, 3, 3, 4, 4])
    assert stream_tile_loads(src, dst) == 10
    assert stream_tile_loads(np.sort(src), dst) == 2
    assert stream_tile_loads(np.sort(src), dst, edge_block=4) == 4  # 5+5
    # a revisited tile is a new run: tile 0, tile 1, tile 0 again
    assert stream_tile_loads(np.array([0, 1, 2]), np.array([0, 130, 1])) == 3
    assert stream_tile_loads(np.zeros(0, int), np.zeros(0, int)) == 0
    # a stream in one band per tile run loads what the packer packs
    src = np.concatenate([np.arange(300) % 512, 1024 + np.arange(10)])
    dst = np.concatenate([np.arange(300) % 128, 128 + np.arange(10)])
    assert stream_tile_loads(src, dst) == pack_edge_blocks(
        src, dst, 1536, 256).num_blocks == 3


def test_one_band_per_tile_packs_as_contiguous_chunks():
    """A stream already in one band per tile run packs as before the
    grouping: blocks are consecutive edge_block chunks of the stream."""
    src = np.concatenate([np.arange(300) % 512, 1024 + np.arange(10)])
    dst = np.concatenate([np.arange(300) % 128, 128 + np.arange(10)])
    packed = pack_edge_blocks(src, dst, 1536, 256)
    np.testing.assert_array_equal(packed.count, [256, 44, 10])
    np.testing.assert_array_equal(packed.band, [0, 0, 2])
    np.testing.assert_array_equal(packed.dst_tile, [0, 0, 1])
    np.testing.assert_array_equal(packed.first_in_tile, [1, 0, 1])
    np.testing.assert_array_equal(
        packed.edge_block_id, np.repeat([0, 1, 2], [256, 44, 10]))
    np.testing.assert_array_equal(
        packed.edge_slot,
        np.concatenate([np.arange(256), np.arange(44), np.arange(10)]))


def test_flat_global_edges_of_grouped_packing_is_the_stream():
    """The edge map is a permutation, so going back from blocks to the
    stream recovers the input stream element for element."""
    src, dst, ns, nd = _revisit_stream()
    packed = pack_edge_blocks(src, dst, ns, nd)
    # tile 0: band 0 (3), band 1 (3); tile 1: band 0, band 1; tile 0
    # again: band 0 (2), band 1 (2)
    np.testing.assert_array_equal(packed.count, [3, 3, 1, 1, 2, 2])
    np.testing.assert_array_equal(packed.band, [0, 1, 0, 1, 0, 1])
    np.testing.assert_array_equal(packed.dst_tile, [0, 0, 1, 1, 0, 0])
    np.testing.assert_array_equal(packed.first_in_tile, [1, 0, 1, 0, 0, 0])
    fs, fd = packed.flat_global_edges()
    np.testing.assert_array_equal(fs, src)
    np.testing.assert_array_equal(fd, dst)


def test_grouped_packing_with_revisit_matches_reference():
    """seg_sum_na and the attention op over a grouped packing whose tile
    0 is revisited non-consecutively match the jnp references."""
    src, dst, ns, nd = _revisit_stream()
    packed = pack_edge_blocks(src, dst, ns, nd)
    h = jnp.asarray(RNG.standard_normal((ns, 16)), jnp.float32)
    out = seg_sum_na(packed, h, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.seg_sum_na_ref(src, dst, h, nd)),
        atol=1e-5)
    logits = (RNG.standard_normal(src.size) * 2).astype(np.float32)
    out_a, alpha = ops.na_attention_packed(packed, logits, h, dst,
                                           backend="interpret")
    want_a, alpha_ref = ops.na_attention_aggregate(src, dst, logits, h, nd,
                                                   backend="jnp")
    np.testing.assert_allclose(np.asarray(alpha), np.asarray(alpha_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_a)[:nd], np.asarray(want_a),
                               atol=1e-4)


def test_restructured_imdb_blocks_count_tile_run_band_groups():
    """On the restructured IMDB graph (scale 0.3) the packer emits
    ceil(n / edge_block) blocks for each (tile run, band) group of n
    edges, counted here with a plain loop over the stream."""
    graph = make_dataset("IMDB", scale=0.3)
    res = FrontendPipeline(
        PipelineConfig(planner="ctt", backend="host", pack=True),
        cache=SemanticGraphCache()).run(graph, ["MAM", "MDM", "MKM"])
    for mp, rg in res.restructured.items():
        s, d = rg.scheduled_edges(renumbered=True)
        packed = res.packed[mp]
        groups, run, prev = {}, -1, None
        for a, b in zip((d // packed.dst_tile_rows).tolist(),
                        (s // packed.src_band).tolist()):
            if a != prev:
                run, prev = run + 1, a
            groups[(run, b)] = groups.get((run, b), 0) + 1
        want = sum(-(-n // packed.edge_block) for n in groups.values())
        assert packed.num_blocks == want, mp
        assert packed.num_edges == s.size


def test_first_in_tile_survives_nonconsecutive_revisit():
    """A dst tile revisited non-consecutively (the scheduled stream
    crossing subgraph boundaries: backbone destinations appear in both
    in_in and out_in) must NOT be re-zeroed — first_in_tile means first
    touch ever.  The seed packer re-marked the revisit block as first,
    discarding the earlier subgraph's accumulation."""
    # tile 0 -> tile 1 -> tile 0 again (dst 0 receives from both visits)
    src = np.array([0, 1, 700, 2])
    dst = np.array([0, 3, 130, 0])
    ns, nd = 1024, 256
    packed = pack_edge_blocks(src, dst, ns, nd)
    assert packed.num_blocks == 3  # the tile change splits the stream
    np.testing.assert_array_equal(packed.dst_tile, [0, 1, 0])
    np.testing.assert_array_equal(packed.first_in_tile, [1, 1, 0])

    h = jnp.asarray(RNG.standard_normal((ns, 16)), jnp.float32)
    out = seg_sum_na(packed, h, interpret=True)
    want = ref.seg_sum_na_ref(src, dst, h, nd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)

    # the attention stats accumulate across the revisit too
    logits = (RNG.standard_normal(src.size) * 2).astype(np.float32)
    out_a, alpha = ops.na_attention_packed(packed, logits, h, dst,
                                           backend="interpret")
    want_a, alpha_ref = ops.na_attention_aggregate(src, dst, logits, h, nd,
                                                   backend="jnp")
    np.testing.assert_allclose(np.asarray(alpha), np.asarray(alpha_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_a)[:nd], np.asarray(want_a),
                               atol=1e-4)

    # zeroing a revisited tile is exactly what the seed semantics did:
    # simulate it and confirm it would corrupt the result (guards against
    # the regression sneaking back behind a passing happy path)
    bad = dataclasses.replace(packed, first_in_tile=np.array([1, 1, 1],
                                                             np.int32))
    out_bad = seg_sum_na(bad, h, interpret=True)
    assert not np.allclose(np.asarray(out_bad), np.asarray(want), atol=1e-3)


def test_out_of_order_revisits_on_platform_backend():
    """Many non-consecutive dst-tile revisits through the platform's kernel
    backend: interpret mode here, compiled Mosaic kernels on a TPU, where
    an output tile left the accumulator when its block index changed and
    must still hold the earlier visits' sums when it comes back."""
    ns, nd, ne, run = 1536, 1024, 2400, 40
    src = RNG.integers(0, ns, ne)
    dst = RNG.integers(0, nd, ne)
    o = np.lexsort((src, dst))
    runs = RNG.permutation(-(-ne // run))  # shuffle dst-sorted runs of edges
    o = np.concatenate([o[r * run:(r + 1) * run] for r in runs])
    src, dst = src[o], dst[o]
    packed = pack_edge_blocks(src, dst, ns, nd)
    tiles = packed.dst_tile
    moved = tiles[1:] != tiles[:-1]
    seen_before = [t in set(tiles[:i]) for i, t in enumerate(tiles)]
    assert int(np.sum(moved & np.array(seen_before[1:]))) > 20

    h = jnp.asarray(RNG.standard_normal((ns, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out = seg_sum_na(packed, h)
        logits = (RNG.standard_normal(ne) * 2).astype(np.float32)
        out_a, alpha = ops.na_attention_packed(packed, logits, h, dst)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.seg_sum_na_ref(src, dst, h, nd)),
                               atol=1e-4)
    want_a, alpha_ref = ops.na_attention_aggregate(src, dst, logits, h, nd,
                                                   backend="jnp")
    np.testing.assert_allclose(np.asarray(alpha), np.asarray(alpha_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_a)[:nd], np.asarray(want_a),
                               atol=1e-4)


# ------------------------------------------------------- ops-level paths --
def test_na_attention_aggregate_accepts_cached_packed():
    ns, nd, ne = 300, 150, 1200
    src = RNG.integers(0, ns, ne)
    dst = RNG.integers(0, nd, ne)
    o = np.lexsort((src, dst))
    src, dst = src[o], dst[o]
    logits = RNG.standard_normal(ne).astype(np.float32)
    h = jnp.asarray(RNG.standard_normal((ns, 32)), jnp.float32)
    packed = pack_edge_blocks(src, dst, ns, nd)
    out_cached, a_cached = ops.na_attention_aggregate(
        src, dst, logits, h, nd, backend="interpret", packed=packed)
    out_fresh, a_fresh = ops.na_attention_aggregate(
        src, dst, logits, h, nd, backend="interpret")
    out_ref, a_ref = ops.na_attention_aggregate(
        src, dst, logits, h, nd, backend="jnp")
    np.testing.assert_allclose(np.asarray(out_cached), np.asarray(out_fresh),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(a_cached), np.asarray(a_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_cached), np.asarray(out_ref),
                               atol=1e-4)


def test_weighted_packing_keeps_zero_weight_edges_in_softmax():
    """Validity must come from count, not the weights: a cached packing
    carrying zero edge weights (masked edges) still contributes ALL its
    edges to the per-destination softmax denominator."""
    ns, nd, ne = 200, 100, 600
    src = RNG.integers(0, ns, ne)
    dst = RNG.integers(0, nd, ne)
    o = np.lexsort((src, dst))
    src, dst = src[o], dst[o]
    w = RNG.random(ne).astype(np.float32)
    w[::3] = 0.0  # masked edges on valid slots
    logits = RNG.standard_normal(ne).astype(np.float32)
    h = jnp.asarray(RNG.standard_normal((ns, 16)), jnp.float32)
    packed_w = pack_edge_blocks(src, dst, ns, nd, weight=w)
    out_w, alpha_w = ops.na_attention_aggregate(
        src, dst, logits, h, nd, backend="interpret", packed=packed_w)
    out_ref, alpha_ref = ops.na_attention_aggregate(
        src, dst, logits, h, nd, backend="jnp")
    np.testing.assert_allclose(np.asarray(alpha_w), np.asarray(alpha_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(out_ref),
                               atol=1e-4)
    # and valid_mask is count-derived even when weights are zero
    np.testing.assert_array_equal(
        packed_w.valid_mask(),
        pack_edge_blocks(src, dst, ns, nd).valid_weight())


def test_execute_rejects_unknown_kernel_backend(frontends):
    graph, res, target_type = frontends["acm_small"]
    targets = WORKLOADS["acm_small"][0]
    feats = {t: jnp.asarray(x) for t, x in graph.features.items()}
    cfg = HGNNConfig(model="rgcn", hidden=16, num_layers=1, num_classes=3,
                     target_type=target_type)
    m = HGNN(cfg, graph.feature_dims, graph.num_vertices, sorted(targets))
    params = m.init(jax.random.key(0))
    with pytest.raises(ValueError):
        m.execute(params, feats, res.banded_batches(),
                  na_executor="banded", kernel_backend="jnp")


def test_hbm_feature_bytes_fp32_default():
    src = np.arange(10)
    dst = np.zeros(10, np.int64)
    packed = pack_edge_blocks(src, dst, 16, 4)
    d = 64
    assert packed.hbm_feature_bytes(d) == packed.num_blocks * packed.src_band * d * 4
    assert packed.hbm_feature_bytes(d, elem_bytes=2) == packed.hbm_feature_bytes(d) // 2


def test_scatter_blocks_matches_host_blocking():
    """Device-side scatter == host with_weights/block_logits layouts."""
    from repro.kernels.edge_softmax import block_logits

    ns, nd, ne = 400, 90, 900
    src = RNG.integers(0, ns, ne)
    dst = RNG.integers(0, nd, ne)
    o = np.lexsort((src, dst))
    src, dst = src[o], dst[o]
    packed = pack_edge_blocks(src, dst, ns, nd)
    vals = RNG.standard_normal(ne).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(packed.scatter_blocks(vals, fill=0.0)),
        packed.with_weights(vals).weight)
    lb = np.asarray(packed.scatter_blocks(vals, fill=-1e30))
    np.testing.assert_array_equal(lb, block_logits(packed, vals))
