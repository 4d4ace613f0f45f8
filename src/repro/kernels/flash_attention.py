"""Block-wise (flash) attention forward kernel for the LM zoo.

Supports: causal masking, sliding-window (gemma2 local layers), logit
softcap (gemma2), GQA head grouping (kv head = q head // group), and
end-aligned query positions (prefill with history / decode).

Grid: (batch*q_heads, q_blocks, kv_blocks); the kv dimension is innermost
and carries (m, l, acc) scratch across steps — the canonical online-softmax
accumulation.  Fully-masked (q,kv) block pairs are skipped with pl.when so
causal/windowed attention does ~half / O(window) of the work, which is what
moves the compute roofline term for long sequences.

VMEM per step at (bq, bk, dh) = (128, 128, 128) fp32: q/k/v/acc tiles
~256 KB — far under budget; bq/bk can be raised to 256/512 for deeper
pipelines (hillclimb lever).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import use_interpret

_NEG = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref,  # (1, bq, dh), (1, bk, dh), (1, bk, dh)
    o_ref,  # (1, bq, dh)
    m_scr, l_scr, acc_scr,  # VMEM scratch: (bq, 128), (bq, 128), (bq, dh)
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    bq: int,
    bk: int,
    s_len: int,
    t_len: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # query positions are end-aligned to key positions (history = t - s)
    off = t_len - s_len
    q_lo = qi * bq + off
    q_hi = q_lo + bq - 1
    k_lo = ki * bk
    k_hi = k_lo + bk - 1

    # block-level skip: causal => need k_lo <= q_hi; window => k_hi > q_lo - w
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window is not None:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        logits = (q @ k.T) * scale  # (bq, bk)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        # also mask key padding (t_len may not divide bk)
        mask &= kpos < t_len
        logits = jnp.where(mask, logits, _NEG)

        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.max(logits, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        # rows with everything masked keep m == _NEG; guard the exp
        alpha = jnp.where(m_prev > _NEG / 2, jnp.exp(m_prev - m_new), 0.0)
        p = jnp.exp(logits - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Hq, S, Dh)
    k: jax.Array,  # (B, Hkv, T, Dh)
    v: jax.Array,  # (B, Hkv, T, Dh)
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    bq: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if interpret is None:
        interpret = use_interpret()
    b, hq, s, dh = q.shape
    _, hkv, t, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else dh ** -0.5

    s_pad = -(-s // bq) * bq
    t_pad = -(-t // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    qf = qp.reshape(b * hq, s_pad, dh)
    kf = kp.reshape(b * hkv, t_pad, dh)
    vf = vp.reshape(b * hkv, t_pad, dh)

    def kv_head(bh):  # fold (batch, q head) -> (batch, kv head)
        return (bh // hq) * hkv + (bh % hq) // group

    kern = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, s_len=s, t_len=t,
    )
    out = pl.pallas_call(
        kern,
        grid=(b * hq, s_pad // bq, t_pad // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, qi, ki: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, qi, ki: (kv_head(bh), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s_pad, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, s_pad, dh)[:, :, :s]
