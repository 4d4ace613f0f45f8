"""The dense-tile format of the NA aggregation, in interpret mode (CPU).

A packing whose (dst tile, band) pairs are few against its edge blocks is
aggregated with static weights as dense (TD, BAND) adjacency tiles, one
grid step per tile, under the kernel name ``na_seg_sum``.  It must give
the edge-block path's sums and the plain reference's, on tiles the
schedule revisits, dst tiles no edge reaches and duplicate (src, dst)
pairs, with host weights and with the unweighted mask; the count rule
picks it, traced weights never take it, and gradients agree between the
two formats.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, seg_sum
from repro.kernels.seg_sum import (DENSE_BLOCKS_PER_TILE, DST_TILE, EDGE_BLOCK,
                                   SRC_BAND, pack_edge_blocks, seg_sum_na)

RNG = np.random.default_rng(16)
NS, ND, D = 1100, 640, 32  # 3 bands, 5 dst tiles


def _dense_graph(revisit=False, untouched=False, duplicates=False):
    """Edges over 15% of the (src, dst) pairs, in (dst, src) order.  With
    ``revisit`` the stream is two such halves one after the other, so
    every dst tile's run comes back; with ``untouched`` no edge reaches
    dst tiles 1 and 3; with ``duplicates`` 500 edges are repeated."""
    dst, src = np.nonzero(RNG.random((ND, NS)) < 0.15)
    if untouched:
        keep = ~np.isin(dst // DST_TILE, [1, 3])
        src, dst = src[keep], dst[keep]
    if duplicates:
        again = RNG.integers(0, src.size, 500)
        src, dst = np.append(src, src[again]), np.append(dst, dst[again])
    o = np.lexsort((src, dst))
    if revisit:
        half = RNG.random(src.size) < 0.5
        o = np.concatenate([o[half[o]], o[~half[o]]])
    return src[o], dst[o]


@pytest.fixture
def kernel_calls(monkeypatch):
    """Which kernel each aggregation ran: "dense" or "blocks" per call."""
    calls = []
    for name, fmt in [("_dense_call", "dense"), ("_seg_sum_call", "blocks")]:
        real = getattr(seg_sum, name)

        def spy(*a, _real=real, _fmt=fmt, **kw):
            calls.append(_fmt)
            return _real(*a, **kw)

        monkeypatch.setattr(seg_sum, name, spy)
    return calls


@pytest.mark.parametrize("weighted", [False, True], ids=["mask", "host_weights"])
@pytest.mark.parametrize("case", ["plain", "revisit", "untouched", "duplicates"])
def test_dense_equals_blocks_and_reference(case, weighted, kernel_calls):
    src, dst = _dense_graph(**({case: True} if case != "plain" else {}))
    w = RNG.random(src.size).astype(np.float32) if weighted else None
    packed = pack_edge_blocks(src, dst, NS, ND, weight=w)
    assert packed.dense_format
    if case == "revisit":  # more tile runs than tiles, one tile per pair
        assert np.count_nonzero(np.diff(packed.dst_tile)) >= len(set(packed.dst_tile))
        assert packed.num_dense_tiles == len(set(zip(packed.dst_tile, packed.band)))
    h = jnp.asarray(RNG.standard_normal((NS, D)), jnp.float32)
    dense = np.asarray(seg_sum_na(packed, h, interpret=True))
    blocks = np.asarray(seg_sum_na(packed, h, interpret=True,
                                   weights=packed.device_weight()))
    assert kernel_calls == ["dense", "blocks"]
    want = np.asarray(ref.seg_sum_na_ref(src, dst, h, ND, weight=w))
    np.testing.assert_allclose(dense, want, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(dense, blocks, atol=1e-4, rtol=1e-5)
    if case == "untouched":
        assert not dense[DST_TILE:2 * DST_TILE].any()
        assert not dense[3 * DST_TILE:4 * DST_TILE].any()


def test_dense_tiles_key_each_pair_once():
    """One tile per (dst tile, band) pair of the whole packing, in dst-tile
    order, zeroed on the first of each dst tile; each holds the summed
    weights of its edges (the count, for the mask)."""
    src, dst = _dense_graph(revisit=True, duplicates=True)
    packed = pack_edge_blocks(src, dst, NS, ND)
    band, tile, first, tiles = packed.dense_tiles()
    pairs = sorted(set(zip(packed.dst_tile.tolist(), packed.band.tolist())))
    assert list(zip(tile.tolist(), band.tolist())) == pairs
    assert first.tolist() == [int(i == 0 or tile[i] != tile[i - 1])
                              for i in range(len(tile))]
    assert tiles.shape == (len(pairs), DST_TILE, SRC_BAND) and tiles.dtype == np.float32
    want = np.zeros((ND, NS))
    np.add.at(want, (dst, src), 1.0)
    i = pairs.index((0, 1))
    np.testing.assert_array_equal(tiles[i], want[:DST_TILE, SRC_BAND:2 * SRC_BAND])
    assert tiles.sum() == src.size


@pytest.mark.parametrize("edges,dense", [(DENSE_BLOCKS_PER_TILE * EDGE_BLOCK, True),
                                         ((DENSE_BLOCKS_PER_TILE - 1) * EDGE_BLOCK, False)])
def test_count_rule_picks_the_format(edges, dense, kernel_calls):
    """One (dst tile, band) pair: dense from ``DENSE_BLOCKS_PER_TILE``
    blocks of it, edge blocks below; traced weights take edge blocks."""
    src = RNG.integers(0, SRC_BAND, edges)
    dst = RNG.integers(0, DST_TILE, edges)
    packed = pack_edge_blocks(src, dst, SRC_BAND, DST_TILE)
    assert packed.num_dense_tiles == 1
    assert packed.num_blocks == edges // EDGE_BLOCK
    assert packed.dense_format is dense
    assert ("dense" in packed.device_arrays()) is dense
    h = jnp.asarray(RNG.standard_normal((SRC_BAND, D)), jnp.float32)
    out = seg_sum_na(packed, h, interpret=True)
    seg_sum_na(packed, h, interpret=True, weights=packed.device_weight())
    assert kernel_calls == ["dense" if dense else "blocks", "blocks"]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.seg_sum_na_ref(src, dst, h, DST_TILE)),
                               atol=1e-4)


def test_sparse_and_empty_packings_stay_on_blocks():
    """300 edges in (dst, src) order fill about one block per pair."""
    src, dst = RNG.integers(0, NS, 300), RNG.integers(0, ND, 300)
    o = np.lexsort((src, dst))
    sparse = pack_edge_blocks(src[o], dst[o], NS, ND)
    assert sparse.num_blocks < sparse.num_dense_tiles * DENSE_BLOCKS_PER_TILE
    assert not sparse.dense_format and "dense" not in sparse.device_arrays()
    empty = pack_edge_blocks(np.zeros(0), np.zeros(0), NS, ND)
    assert empty.num_dense_tiles == 0 and not empty.dense_format


def test_host_weights_through_ops_take_the_dense_format(kernel_calls):
    """``ops.na_aggregate`` with host weights may go dense, with the
    reference's result."""
    src, dst = _dense_graph()
    w = RNG.random(src.size).astype(np.float32)
    h = jnp.asarray(RNG.standard_normal((NS, D)), jnp.float32)
    out = ops.na_aggregate(src, dst, h, ND, weight=w, backend="interpret")
    assert kernel_calls == ["dense"]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.seg_sum_na_ref(src, dst, h, ND, weight=w)),
        atol=1e-4, rtol=1e-5)


def test_grad_agrees_between_formats(kernel_calls):
    """``jax.grad`` in ``h`` through the dense forward equals the edge-block
    forward's and the reference's: the backward is the same gather."""
    src, dst = _dense_graph(revisit=True)
    packed = pack_edge_blocks(src, dst, NS, ND)
    h = jnp.asarray(RNG.standard_normal((NS, D)), jnp.float32)
    r = jnp.asarray(RNG.standard_normal((ND, D)), jnp.float32)
    wb = packed.device_weight()
    g_dense = jax.grad(lambda x: jnp.sum(seg_sum_na(packed, x, interpret=True) * r))(h)
    g_blocks = jax.grad(lambda x: jnp.sum(
        seg_sum_na(packed, x, interpret=True, weights=wb) * r))(h)
    g_ref = jax.grad(lambda x: jnp.sum(ref.seg_sum_na_ref(src, dst, x, ND) * r))(h)
    assert kernel_calls == ["dense", "blocks"]
    np.testing.assert_allclose(np.asarray(g_dense), np.asarray(g_blocks), atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_dense), np.asarray(g_ref), atol=1e-4)
