"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def seg_sum_na_ref(
    src: np.ndarray,
    dst: np.ndarray,
    h: jax.Array,
    num_dst: int,
    weight: Optional[np.ndarray] = None,
) -> jax.Array:
    """Weighted gather + segment-sum (the NA aggregation oracle).  A
    ``(E, H)`` ``weight`` holds one weight per head: head k's on columns
    ``k*D/H`` to ``(k+1)*D/H``."""
    w = jnp.ones((src.shape[0],), h.dtype) if weight is None else jnp.asarray(weight, h.dtype)
    w = w[:, None] if w.ndim == 1 else jnp.repeat(w, h.shape[1] // w.shape[1], axis=1)
    gathered = h[jnp.asarray(src)] * w
    return jax.ops.segment_sum(gathered, jnp.asarray(dst), num_segments=num_dst)


def edge_softmax_ref(logits: jax.Array, dst: jax.Array, num_dst: int) -> jax.Array:
    """Per-destination softmax over edges (oracle for edge_softmax)."""
    m = jax.ops.segment_max(logits, dst, num_segments=num_dst)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    ex = jnp.exp(logits - m[dst])
    s = jax.ops.segment_sum(ex, dst, num_segments=num_dst)
    return ex / jnp.maximum(s[dst], 1e-9)


def spgemm_ref(a_dense: jax.Array, b_dense: jax.Array) -> jax.Array:
    """Boolean matrix product oracle: (A @ B) > 0 as float 0/1."""
    return (a_dense @ b_dense > 0).astype(jnp.float32)


def spgemm_macs_ref(a_dense: np.ndarray, b_dense: np.ndarray) -> int:
    """Exact join-pair count of the boolean product A @ B.

    For every middle vertex k the join emits colsum_A[k] * rowsum_B[k]
    output pairs (before dedup) — identical to the MAC counter of the
    host sorted-merge join in ``hetero.graph.compose_relations``, so the
    device SGB backend's cost model stays bit-equal to the host one.
    """
    col_a = (np.asarray(a_dense) > 0).sum(axis=0).astype(np.int64)
    row_b = (np.asarray(b_dense) > 0).sum(axis=1).astype(np.int64)
    k = min(col_a.shape[0], row_b.shape[0])  # operands may be tile-padded
    return int(col_a[:k] @ row_b[:k])


def attention_chunked(
    q: jax.Array,  # (B, Hq, S, Dh)
    k: jax.Array,  # (B, Hkv, T, Dh)
    v: jax.Array,  # (B, Hkv, T, Dh)
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    bk: int = 1024,
    pos_offset: Optional[int] = None,
) -> jax.Array:
    """Flash-style attention in pure jnp with a *static* python loop over
    key/value chunks (online softmax).  Never materializes (S, T) logits —
    required for the 32k/500k shapes — and keeps every FLOP visible to
    XLA cost_analysis (a lax.scan body would be counted once).
    GQA is handled by a grouped einsum (no repeated K/V materialization).
    ``pos_offset``: position of query 0 relative to key 0 (default: queries
    end-aligned to keys).
    """
    b, hq, s, dh = q.shape
    hkv, t = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # value dim may differ (MLA expanded path)
    g = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    qg = q.reshape(b, hkv, g, s, dh)
    nk = -(-t // bk)
    off = pos_offset if pos_offset is not None else t - s
    m = jnp.full((b, hkv, g, s), -1e30, jnp.float32)
    l = jnp.zeros((b, hkv, g, s), jnp.float32)
    acc = jnp.zeros((b, hkv, g, s, dv), jnp.float32)
    for i in range(nk):
        lo = i * bk
        hi = min(t, lo + bk)
        if causal and lo > off + s - 1:
            continue  # block entirely in the future for every query
        if window is not None and hi - 1 <= off - window:
            continue  # block entirely outside every query's window
        kb = k[:, :, lo:hi].astype(jnp.float32)
        vb = v[:, :, lo:hi].astype(jnp.float32)
        logits = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(jnp.float32), kb) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        qpos = off + jnp.arange(s)[:, None]
        kpos = lo + jnp.arange(hi - lo)[None, :]
        mask = jnp.ones((s, hi - lo), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgst,bktd->bkgsd", p, vb)
        m = m_new
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(b, hq, s, dv).astype(q.dtype)


def attention_chunked_2d(
    q: jax.Array,  # (B, Hq, S, Dh)
    k: jax.Array,  # (B, Hkv, T, Dh)
    v: jax.Array,  # (B, Hkv, T, Dh)
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    bq: int = 4096,
    bk: int = 2048,
) -> jax.Array:
    """Query-AND-key blocked attention with *block-level masking skips*.

    Beyond-paper §Perf optimization: the single-loop chunked path computes
    every (q, kv) pair and masks — for causal attention that's 2x the
    needed FLOPs, and for sliding-window layers O(S/window)x.  Blocking the
    query dim too lets fully-masked blocks be skipped statically:
      causal:  skip kv blocks with k_lo > q_hi            (upper triangle)
      window:  skip kv blocks with k_hi <= q_lo - window  (stale past)
    Static python loops keep every remaining FLOP visible to cost_analysis.
    """
    b, hq, s, dh = q.shape
    t = k.shape[2]
    off = t - s
    nq = -(-s // bq)
    outs = []
    for i in range(nq):
        q_lo = i * bq
        q_hi = min(s, q_lo + bq)
        qblk = q[:, :, q_lo:q_hi]
        # restrict the kv range for this q block
        k_hi_allowed = t if not causal else min(t, off + q_hi)
        k_lo_allowed = 0 if window is None else max(0, off + q_lo + 1 - window)
        k_lo_blk = (k_lo_allowed // bk) * bk
        kv = slice(k_lo_blk, k_hi_allowed)
        o = attention_chunked(
            qblk, k[:, :, kv], v[:, :, kv], causal=causal, window=window,
            softcap=softcap, scale=scale, bk=bk,
            pos_offset=(off + q_lo) - k_lo_blk,
        )
        outs.append(o)
    return jnp.concatenate(outs, axis=2)


def attention_ref(
    q: jax.Array,  # (B, Hq, S, Dh)
    k: jax.Array,  # (B, Hkv, T, Dh)
    v: jax.Array,  # (B, Hkv, T, Dh)
    causal: bool = True,
    window: Optional[int] = None,  # sliding window size (None = full)
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference multi-head attention with GQA / sliding window / softcap."""
    b, hq, s, dh = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kf = jnp.repeat(k, g, axis=1)
    vf = jnp.repeat(v, g, axis=1)
    scale = scale if scale is not None else dh ** -0.5
    logits = jnp.einsum("bhsd,bhtd->bhst", q, kf) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    t = k.shape[2]
    qpos = jnp.arange(s)[:, None] + (t - s)  # queries end-aligned to keys
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, vf)


def ssd_ref(
    x: jax.Array,  # (B, S, H, P) input (already conv'd / gated outside)
    a_log: jax.Array,  # (B, S, H) negative log decay input (dt*A), a = exp(a_log)<1
    b_coef: jax.Array,  # (B, S, G, N) input->state coefficients
    c_coef: jax.Array,  # (B, S, G, N) state->output coefficients
) -> jax.Array:
    """Mamba2 SSD (state-space duality) oracle — sequential scan.

    State h[t] = a[t] * h[t-1] + B[t] ⊗ x[t];  y[t] = C[t] · h[t].
    Heads are grouped: H heads share G B/C groups (H % G == 0).
    Runs an explicit lax.scan over time (slow but unambiguous).
    """
    bsz, s, h, p = x.shape
    g, n = b_coef.shape[2], b_coef.shape[3]
    rep = h // g
    bexp = jnp.repeat(b_coef, rep, axis=2)  # (B, S, H, N)
    cexp = jnp.repeat(c_coef, rep, axis=2)

    def step(carry, t):
        hstate = carry  # (B, H, P, N)
        a_t = jnp.exp(a_log[:, t])[:, :, None, None]  # (B, H, 1, 1)
        upd = jnp.einsum("bhp,bhn->bhpn", x[:, t], bexp[:, t])
        hstate = a_t * hstate + upd
        y_t = jnp.einsum("bhpn,bhn->bhp", hstate, cexp[:, t])
        return hstate, y_t

    init = jnp.zeros((bsz, h, p, n), x.dtype)
    _, ys = jax.lax.scan(step, init, jnp.arange(s))
    return jnp.moveaxis(ys, 0, 1)  # (B, S, H, P)


def ssd_chunked(
    x: jax.Array,  # (B, S, H, P)
    a_log: jax.Array,  # (B, S, H)
    b_coef: jax.Array,  # (B, S, G, N)
    c_coef: jax.Array,  # (B, S, G, N)
    chunk: int = 128,
) -> jax.Array:
    """Vectorized chunked SSD — the production jnp path (same math as the
    Pallas kernel; inter-chunk recurrence via associative_scan so the HLO
    is static and XLA cost_analysis sees every FLOP)."""
    bsz, s, h, p = x.shape
    g, n = b_coef.shape[2], b_coef.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc, L = s // chunk, chunk
    rep = h // g
    bexp = jnp.repeat(b_coef, rep, axis=2)
    cexp = jnp.repeat(c_coef, rep, axis=2)

    xr = x.reshape(bsz, nc, L, h, p)
    ar = a_log.reshape(bsz, nc, L, h)
    br = bexp.reshape(bsz, nc, L, h, n)
    cr = cexp.reshape(bsz, nc, L, h, n)
    cum = jnp.cumsum(ar, axis=2)  # (B, nc, L, H) inclusive

    # --- intra-chunk (masked L x L matmuls) ---
    tri = jnp.tril(jnp.ones((L, L), bool))
    gate = jnp.where(
        tri[None, None, :, :, None],
        jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :]),
        0.0,
    )  # (B, nc, L(t), L(s), H)
    cb = jnp.einsum("bclhn,bcmhn->bclmh", cr, br)
    y = jnp.einsum("bclmh,bcmhp->bclhp", cb * gate, xr)

    # --- chunk boundary states ---
    w_end = jnp.exp(cum[:, :, L - 1 : L, :] - cum)  # (B, nc, L, H)
    states = jnp.einsum("bclhp,bclhn,bclh->bchpn", xr, br, w_end)
    decay = jnp.exp(cum[:, :, L - 1, :])  # (B, nc, H)

    # --- inter-chunk associative scan: h[c] = decay[c] * h[c-1] + states[c]
    def combine(left, right):
        (a1, s1), (a2, s2) = left, right
        return a1 * a2, s1 * a2[..., None, None] + s2

    a_sc, h_after = jax.lax.associative_scan(
        combine, (decay, states), axis=1)
    h_before = jnp.concatenate(
        [jnp.zeros_like(h_after[:, :1]), h_after[:, :-1]], axis=1)

    # --- inter-chunk contribution ---
    y = y + jnp.einsum(
        "bclhn,bchpn,bclh->bclhp", cr, h_before, jnp.exp(cum))
    return y.reshape(bsz, s, h, p)
