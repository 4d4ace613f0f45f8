"""The attention path's two kernels against the path they replace, in
interpret mode on seeded random packings.

The model's attention NA passes per-vertex score columns to a stats kernel
that builds each slot's logit and to an aggregation kernel that forms alpha
per slot (``kernels/ops.py::na_attention_packed``).  The path they replace
builds the logits per edge in stream order, scatters them into the blocked
layout (``PackedEdges.scatter_blocks``), runs the flat-logit stats kernel
(``edge_softmax_stats``), gathers ``m[dst]``/``s[dst]`` per edge and
scatters alpha back into the blocks for ``seg_sum_na(weights=...)``.  Both
must give the same blocked logits, (m, s), alpha and outputs.  With H
heads (``(rows, H)`` score columns, HAN's eight) each head's logits, (m,
s) and columns are checked against the jnp oracle over the edge list.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hgnn.layers import head_scores, na_attention, na_attention_banded
from repro.kernels import ops, ref
from repro.kernels.edge_softmax import attention_stats_call, edge_softmax_stats
from repro.kernels.seg_sum import (attention_seg_sum_call, dst_rows,
                                   pack_edge_blocks, seg_sum_na, src_rows)

SLOPE = 0.2


def _stream(case, seed):
    """(src, dst, num_src, num_dst, weight) of a seeded stream of one kind."""
    rng = np.random.default_rng(seed)
    ns, nd, ne = 1300, 700, 2600
    src = rng.integers(0, ns, ne)
    dst = rng.integers(0, nd, ne)
    weight = None
    if case == "untouched":  # tiles 1, 2 and 5 receive no edge
        keep = (dst < 128) | ((dst >= 384) & (dst < 640))
        src, dst = src[keep], dst[keep]
    o = np.lexsort((src, dst))
    src, dst = src[o], dst[o]
    if case == "revisits":  # dst-sorted runs of 40 edges in shuffled order
        runs = rng.permutation(-(-src.size // 40))
        o = np.concatenate([np.arange(r * 40, min((r + 1) * 40, src.size))
                            for r in runs])
        src, dst = src[o], dst[o]
    if case == "zero_weight":  # zero weights on valid slots stay in the softmax
        weight = rng.random(src.size).astype(np.float32)
        weight[::3] = 0.0
    return src, dst, ns, nd, weight


def _scores(ns, nd, seed):
    """Per-vertex scores whose sums take both signs (both leaky branches)."""
    rng = np.random.default_rng(seed + 100)
    return (jnp.asarray(rng.standard_normal(ns) * 2, jnp.float32),
            jnp.asarray(rng.standard_normal(nd) * 2, jnp.float32))


def _columns(packed, e_s, e_d):
    """The score columns as the entry pads them for the kernels."""
    n_dst_pad = packed.num_dst_tiles * packed.dst_tile_rows
    e_d = jnp.pad(e_d[:, None], ((0, n_dst_pad - e_d.shape[0]), (0, 0)))
    return src_rows(packed, e_s[:, None]), e_d


def _replaced_path(packed, e_s, e_d, h):
    """Stream-order logits -> blocked scatter -> flat-logit stats ->
    per-edge alpha -> blocked scatter -> weighted aggregation."""
    src_g, dst_g = (jnp.asarray(a) for a in packed.flat_global_edges())
    logits = jax.nn.leaky_relu(e_s[src_g] + e_d[dst_g], SLOPE)
    blocked = packed.scatter_blocks(logits, fill=-1e30)
    m, s = edge_softmax_stats(packed, blocked, interpret=True)
    alpha = jnp.exp(logits - m[dst_g]) / jnp.maximum(s[dst_g], 1e-9)
    out = seg_sum_na(packed, h, interpret=True,
                     weights=packed.scatter_blocks(alpha, fill=0.0))
    return blocked, m, s, alpha, out


CASES = ["random", "revisits", "untouched", "zero_weight"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("seed", [0, 1])
def test_kernels_match_the_replaced_path(case, seed):
    src, dst, ns, nd, weight = _stream(case, seed)
    packed = pack_edge_blocks(src, dst, ns, nd, weight=weight)
    e_s, e_d = _scores(ns, nd, seed)
    h = jnp.asarray(np.random.default_rng(seed).standard_normal((ns, 16)),
                    jnp.float32)
    pre = np.asarray(e_s)[src] + np.asarray(e_d)[dst]
    assert (pre < 0).any() and (pre > 0).any()
    if case == "revisits":
        t = packed.dst_tile
        assert any(t[i] != t[i - 1] and t[i] in t[:i - 1] for i in range(1, t.size))
    if case == "untouched":
        assert not np.isin([1, 2, 5], packed.dst_tile).any()

    blocked, m_ref, s_ref, alpha_ref, out_ref = _replaced_path(packed, e_s, e_d, h)

    geometry = dict(num_dst_tiles=packed.num_dst_tiles, src_band=packed.src_band,
                    dst_tile_rows=packed.dst_tile_rows, interpret=True)
    blocks = (*packed.device_blocked(), packed.device_valid())
    m, s, logits = attention_stats_call(*blocks, *_columns(packed, e_s, e_d),
                                        slope=SLOPE, **geometry)
    # blocked logits, padding slots included (-1e30 on both paths)
    np.testing.assert_allclose(np.asarray(logits)[:, 0], np.asarray(blocked),
                               rtol=1e-6, atol=1e-6)
    # (m, s) on every destination with an in-edge
    has_edge = np.bincount(dst, minlength=nd) > 0
    np.testing.assert_allclose(np.asarray(m)[:nd, 0][has_edge],
                               np.asarray(m_ref)[has_edge], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s)[:nd, 0][has_edge],
                               np.asarray(s_ref)[has_edge], rtol=1e-5)
    # alpha from the kernel's logits and stats, per stream edge, against
    # both the replaced path and the segment-softmax oracle
    blk, slot = packed.edge_map()
    src_g, dst_g = packed.flat_global_edges()
    lg = np.asarray(logits)[blk, 0, slot]
    alpha = np.exp(lg - np.asarray(m)[dst_g, 0]) / np.asarray(s)[dst_g, 0]
    np.testing.assert_allclose(alpha, np.asarray(alpha_ref), atol=1e-6)
    np.testing.assert_allclose(
        alpha, np.asarray(ref.edge_softmax_ref(jnp.asarray(lg), jnp.asarray(dst_g), nd)),
        atol=1e-5)

    out = attention_seg_sum_call(*blocks, logits, m, s, src_rows(packed, h),
                                 **geometry)
    np.testing.assert_allclose(np.asarray(dst_rows(packed, out)),
                               np.asarray(out_ref), atol=1e-5)
    entry = ops.na_attention_packed(packed, e_s, e_d, h, SLOPE, backend="interpret")
    np.testing.assert_allclose(np.asarray(entry), np.asarray(out_ref), atol=1e-5)
    if case == "untouched":
        assert not np.asarray(entry)[128:384].any()


def test_empty_packing_aggregates_to_zeros():
    """A semantic graph with no edges: zero rows, zero gradients."""
    packed = pack_edge_blocks(np.zeros(0, int), np.zeros(0, int), 10, 5)
    e_s, e_d = _scores(10, 5, 0)
    h = jnp.ones((10, 4), jnp.float32)
    out = ops.na_attention_packed(packed, e_s, e_d, h, backend="interpret")
    assert out.shape == (5, 4) and not np.asarray(out).any()
    grads = jax.grad(lambda a, b, x: jnp.sum(
        ops.na_attention_packed(packed, a, b, x, backend="interpret")),
        argnums=(0, 1, 2))(e_s, e_d, h)
    assert all(not np.asarray(g).any() for g in grads)


@pytest.mark.parametrize("bias", [None, 0.7], ids=["rgat", "shgn"])
def test_banded_layer_matches_jnp_layer(bias):
    """``na_attention_banded`` (the bias folded into the destination
    scores) == ``na_attention`` (the bias added to each edge's logit)."""
    src, dst, ns, nd, _ = _stream("revisits", 3)
    packed = pack_edge_blocks(src, dst, ns, nd)
    rng = np.random.default_rng(3)
    h_src = jnp.asarray(rng.standard_normal((ns, 16)), jnp.float32)
    h_dst = jnp.asarray(rng.standard_normal((nd, 16)), jnp.float32)
    a_src = jnp.asarray(rng.standard_normal(16), jnp.float32)
    a_dst = jnp.asarray(rng.standard_normal(16), jnp.float32)
    edge_bias = None if bias is None else jnp.float32(bias)
    with jax.default_matmul_precision("highest"):
        got = na_attention_banded(packed, h_src, h_dst, a_src, a_dst,
                                  edge_bias=edge_bias, backend="interpret")
        want = na_attention(h_src, h_dst, jnp.asarray(src), jnp.asarray(dst), nd,
                            a_src, a_dst, edge_bias=edge_bias)
    pre = np.asarray(h_src @ a_src)[src] + np.asarray(h_dst @ a_dst)[dst]
    assert (pre < 0).any() and (pre > 0).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("heads", [1, 8])
@pytest.mark.parametrize("case", ["random", "untouched"])
def test_head_kernels_match_the_jnp_path(case, heads):
    """``(rows, H)`` score columns through both kernels: per head the
    blocked logits, (m, s) and the aggregation against the jnp oracle over
    the edge list (``na_attention_aggregate(backend="jnp")``), head k's
    alpha on columns ``2k`` to ``2k + 2``.  Tiles no block touches and
    destinations with no in-edge come out zero."""
    src, dst, ns, nd, _ = _stream(case, 5)
    packed = pack_edge_blocks(src, dst, ns, nd)
    rng = np.random.default_rng(11)
    e_s = jnp.asarray(rng.standard_normal((ns, heads)) * 2, jnp.float32)
    e_d = jnp.asarray(rng.standard_normal((nd, heads)) * 2, jnp.float32)
    h = jnp.asarray(rng.standard_normal((ns, 16)), jnp.float32)
    no_edge = np.bincount(dst, minlength=nd) == 0
    assert no_edge.any()
    src_g, dst_g = packed.flat_global_edges()
    pre = np.asarray(e_s)[src_g] + np.asarray(e_d)[dst_g]  # (E, H)
    assert (pre < 0).any() and (pre > 0).any()

    geometry = dict(num_dst_tiles=packed.num_dst_tiles, src_band=packed.src_band,
                    dst_tile_rows=packed.dst_tile_rows, interpret=True)
    blocks = (*packed.device_blocked(), packed.device_valid())
    n_dst_pad = packed.num_dst_tiles * packed.dst_tile_rows
    cols = (src_rows(packed, e_s), jnp.pad(e_d, ((0, n_dst_pad - nd), (0, 0))))
    m, s, logits = attention_stats_call(*blocks, *cols, slope=SLOPE, **geometry)
    assert m.shape == s.shape == (n_dst_pad, heads)
    assert logits.shape == (packed.num_blocks, heads, packed.edge_block)
    blk, slot = packed.edge_map()
    want = np.where(pre >= 0, pre, SLOPE * pre)
    np.testing.assert_allclose(np.asarray(logits)[blk, :, slot], want, rtol=1e-6, atol=1e-6)
    pad = np.ones(np.asarray(logits).shape, bool)
    pad[blk, :, slot] = False
    assert (np.asarray(logits)[pad] == -1e30).all()
    m_want = np.asarray(jax.ops.segment_max(jnp.asarray(want), jnp.asarray(dst_g),
                                            num_segments=nd))
    s_want = np.asarray(jax.ops.segment_sum(jnp.exp(jnp.asarray(want) - m_want[dst_g]),
                                            jnp.asarray(dst_g), num_segments=nd))
    has_edge = ~no_edge
    np.testing.assert_allclose(np.asarray(m)[:nd][has_edge], m_want[has_edge],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s)[:nd][has_edge], s_want[has_edge], rtol=1e-5)

    oracle = ops.na_attention_aggregate(src, dst, e_s, e_d, h, nd, SLOPE, backend="jnp")
    out = attention_seg_sum_call(*blocks, logits, m, s, src_rows(packed, h), **geometry)
    np.testing.assert_allclose(np.asarray(dst_rows(packed, out)), np.asarray(oracle),
                               atol=1e-5)
    entry = ops.na_attention_packed(packed, e_s, e_d, h, SLOPE, backend="interpret")
    np.testing.assert_allclose(np.asarray(entry), np.asarray(oracle), atol=1e-5)
    assert not np.asarray(entry)[no_edge].any()
    if case == "untouched":
        assert not np.asarray(entry)[128:384].any()


def test_head_outputs_are_the_heads_concatenated():
    """Eight heads at once == each head alone on its own columns."""
    src, dst, ns, nd, _ = _stream("revisits", 6)
    packed = pack_edge_blocks(src, dst, ns, nd)
    rng = np.random.default_rng(12)
    e_s = jnp.asarray(rng.standard_normal((ns, 8)), jnp.float32)
    e_d = jnp.asarray(rng.standard_normal((nd, 8)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((ns, 16)), jnp.float32)
    both = ops.na_attention_packed(packed, e_s, e_d, h, backend="interpret")
    each = [ops.na_attention_packed(packed, e_s[:, k], e_d[:, k], h[:, 2 * k:2 * k + 2],
                                    backend="interpret") for k in range(8)]
    np.testing.assert_allclose(np.asarray(both), np.asarray(jnp.concatenate(each, axis=1)),
                               atol=1e-6)


def test_banded_han_layer_matches_jnp_layer():
    """``na_attention_banded`` with ``(H, D/H)`` attention vectors ==
    ``na_attention``: per-head scores from ``head_scores`` on both."""
    src, dst, ns, nd, _ = _stream("revisits", 4)
    packed = pack_edge_blocks(src, dst, ns, nd)
    rng = np.random.default_rng(4)
    h_src = jnp.asarray(rng.standard_normal((ns, 16)), jnp.float32)
    h_dst = jnp.asarray(rng.standard_normal((nd, 16)), jnp.float32)
    a_src = jnp.asarray(rng.standard_normal((8, 2)), jnp.float32)
    a_dst = jnp.asarray(rng.standard_normal((8, 2)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = na_attention_banded(packed, h_src, h_dst, a_src, a_dst, backend="interpret")
        want = na_attention(h_src, h_dst, jnp.asarray(src), jnp.asarray(dst), nd,
                            a_src, a_dst)
        scores = head_scores(h_src, a_src)
    np.testing.assert_allclose(np.asarray(scores), np.einsum(
        "nkd,kd->nk", np.asarray(h_src).reshape(ns, 8, 2), np.asarray(a_src)), rtol=1e-5,
        atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
