"""Pallas kernel validation: shape/dtype sweeps against ref.py oracles,
all in interpret mode (CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest
from proptest import seeded_property

from repro.kernels import ops, ref
from repro.kernels.edge_softmax import block_logits, edge_softmax_stats
from repro.kernels.flash_attention import flash_attention
from repro.kernels.seg_sum import pack_edge_blocks, seg_sum_na
from repro.kernels.spgemm_bsr import compose_dense_blocked
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(7)


def _edges(ns, nd, ne, sort=True):
    src = RNG.integers(0, ns, ne)
    dst = RNG.integers(0, nd, ne)
    if sort:
        o = np.lexsort((src, dst))
        src, dst = src[o], dst[o]
    return src, dst


# ------------------------------------------------------------- seg_sum ----
@pytest.mark.parametrize("ns,nd,ne,d", [
    (64, 64, 200, 32), (300, 200, 1500, 64),
    pytest.param(1000, 700, 4000, 128, marks=pytest.mark.slow),
    (17, 5, 40, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seg_sum_sweep(ns, nd, ne, d, dtype):
    src, dst = _edges(ns, nd, ne)
    w = RNG.random(ne).astype(np.float32)
    h = jnp.asarray(RNG.standard_normal((ns, d)), dtype)
    packed = pack_edge_blocks(src, dst, ns, nd, weight=w)
    out = seg_sum_na(packed, h, interpret=True)
    want = ref.seg_sum_na_ref(src, dst, h, nd, weight=w)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@seeded_property(max_examples=15)
def test_seg_sum_property(seed):
    rng = np.random.default_rng(seed)
    ns, nd = int(rng.integers(2, 200)), int(rng.integers(2, 150))
    ne = int(rng.integers(1, 600))
    src = rng.integers(0, ns, ne)
    dst = rng.integers(0, nd, ne)
    o = np.lexsort((src, dst))
    src, dst = src[o], dst[o]
    h = jnp.asarray(rng.standard_normal((ns, 32)), jnp.float32)
    packed = pack_edge_blocks(src, dst, ns, nd)
    out = seg_sum_na(packed, h, interpret=True)
    # the exact sum of the same edges, in float64 on the host: a float32
    # reference rounds too, and its own error alone can pass the tolerance
    want = np.zeros((nd, h.shape[1]))
    np.add.at(want, dst, np.asarray(h, np.float64)[src])
    np.testing.assert_allclose(out, want, atol=1e-4)


# -------------------------------------------------------- edge softmax ----
@pytest.mark.parametrize("ns,nd,ne", [(300, 200, 1500), (50, 600, 900)])
def test_edge_softmax(ns, nd, ne):
    src, dst = _edges(ns, nd, ne)
    logits = (RNG.standard_normal(ne) * 3).astype(np.float32)
    packed = pack_edge_blocks(src, dst, ns, nd)
    m, s = edge_softmax_stats(packed, block_logits(packed, logits),
                              interpret=True)
    alpha = np.exp(logits - np.asarray(m)[dst]) / np.maximum(
        np.asarray(s)[dst], 1e-9)
    want = np.asarray(ref.edge_softmax_ref(
        jnp.asarray(logits), jnp.asarray(dst), nd))
    np.testing.assert_allclose(alpha, want, atol=1e-5)
    # weights sum to 1 per destination with in-edges
    sums = np.zeros(nd)
    np.add.at(sums, dst, alpha)
    nz = np.bincount(dst, minlength=nd) > 0
    np.testing.assert_allclose(sums[nz], 1.0, atol=1e-4)


# ---------------------------------------------------------- attention -----
@pytest.mark.parametrize("b,hq,hkv,s,t,dh,causal,window,cap", [
    (2, 4, 2, 128, 128, 64, True, None, None),
    (1, 8, 2, 100, 100, 64, True, None, 50.0),
    (1, 4, 4, 96, 224, 64, True, None, None),
    (2, 4, 2, 128, 128, 64, True, 64, None),
    (1, 2, 1, 64, 64, 128, False, None, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, s, t, dh, causal, window, cap, dtype):
    q = jnp.asarray(RNG.standard_normal((b, hq, s, dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hkv, t, dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hkv, t, dh)), dtype)
    o = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                        bq=64, bk=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_attention_chunked_matches_ref():
    q = jnp.asarray(RNG.standard_normal((1, 4, 192, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 320, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 320, 32)), jnp.float32)
    o = ref.attention_chunked(q, k, v, causal=True, bk=64)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(o, want, atol=1e-5)


# ----------------------------------------------------------------- ssd ----
@pytest.mark.parametrize("b,s,h,g,p,n,chunk", [
    pytest.param(2, 128, 4, 2, 32, 16, 32, marks=pytest.mark.slow),
    pytest.param(1, 256, 2, 1, 64, 64, 64, marks=pytest.mark.slow),
    (1, 64, 8, 8, 16, 16, 16),
])
def test_ssd_sweep(b, s, h, g, p, n, chunk):
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), jnp.float32)
    a = jnp.asarray(-np.abs(RNG.standard_normal((b, s, h))) * 0.1, jnp.float32)
    bc = jnp.asarray(RNG.standard_normal((b, s, g, n)) * 0.3, jnp.float32)
    cc = jnp.asarray(RNG.standard_normal((b, s, g, n)) * 0.3, jnp.float32)
    want = ref.ssd_ref(x, a, bc, cc)
    kern = ssd_scan(x, a, bc, cc, chunk=chunk, interpret=True)
    np.testing.assert_allclose(kern, want, atol=3e-4)
    chunked = ref.ssd_chunked(x, a, bc, cc, chunk=chunk)
    np.testing.assert_allclose(chunked, want, atol=3e-4)


# -------------------------------------------------------------- spgemm ----
def test_spgemm_vs_oracle(acm_small):
    a = acm_small.relation("AP").dense()
    b = acm_small.relation("PA").dense()
    out, stats = compose_dense_blocked(a, b)
    want = np.asarray(ref.spgemm_ref(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(out, want)
    assert stats["tile_pairs_live"] <= stats["tile_pairs_total"]


def test_spgemm_sparse_skips_tiles():
    # block-diagonal-ish matrix: most tile pairs dead
    n = 512
    a = np.zeros((n, n), np.float32)
    a[:128, :128] = (RNG.random((128, 128)) < 0.05)
    a[300:400, 300:400] = (RNG.random((100, 100)) < 0.05)
    out, stats = compose_dense_blocked(a, a)
    want = np.asarray(ref.spgemm_ref(jnp.asarray(a), jnp.asarray(a)))
    assert np.array_equal(out, want)
    assert stats["tile_pairs_live"] < stats["tile_pairs_total"] * 0.5


# ----------------------------------------------------------- ops layer ----
def test_ops_na_backends_agree():
    src, dst = _edges(200, 150, 800)
    h = jnp.asarray(RNG.standard_normal((200, 64)), jnp.float32)
    a = ops.na_aggregate(src, dst, h, 150, backend="jnp")
    b = ops.na_aggregate(src, dst, h, 150, backend="interpret")
    np.testing.assert_allclose(a, b, atol=1e-4)
    e_s = jnp.asarray(RNG.standard_normal(200), jnp.float32)
    e_d = jnp.asarray(RNG.standard_normal(150), jnp.float32)
    oa = ops.na_attention_aggregate(src, dst, e_s, e_d, h, 150, backend="jnp")
    ob = ops.na_attention_aggregate(src, dst, e_s, e_d, h, 150,
                                    backend="interpret")
    np.testing.assert_allclose(oa, ob, atol=1e-4)
