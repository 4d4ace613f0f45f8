"""Public jit'd wrappers around the Pallas kernels.

Each op dispatches on ``backend``:
  * None         — the platform's kernel backend (the default): compiled
                   Pallas on a TPU, interpret mode anywhere else
                   (``repro.kernels.backend``);
  * "pallas"     — pl.pallas_call compiled for the TPU (an error off-TPU);
  * "interpret"  — the same kernel body in the Pallas interpreter;
  * "jnp"        — the pure-jnp oracle (``ref.py``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.backend import use_interpret
from repro.kernels.edge_softmax import attention_stats_call
from repro.kernels.flash_attention import flash_attention as _fa
from repro.kernels.seg_sum import (PackedEdges, attention_seg_sum_call,
                                   dst_rows, pack_edge_blocks, seg_sum_na,
                                   src_rows)
from repro.kernels.spgemm_bsr import compose_dense_blocked
from repro.kernels.ssd_scan import ssd_scan as _ssd

# Attention sharding hint, set by the launch layer under a mesh context:
#   None    — no constraints (single-device tests/benches)
#   "heads" — shard heads over the 'model' axis (requires divisibility)
#   "qseq"  — context parallelism: shard QUERY sequence over 'model'
#             (the general fallback when head counts don't divide the
#             model axis — GSPMD would otherwise replicate attention
#             per device, a 16x compute/memory blowup)
ATTN_SHARDING: Optional[str] = None

# Batch axes of the current launch (e.g. ('data',) or (('pod', 'data'),)).
# When set, constrain_batch() pins activations' leading dim to the data
# axes; with_sharding_constraint transposes to itself, so the BACKWARD
# cotangents inherit the same sharding — without this, GSPMD loses batch
# sharding inside rematerialized backward bodies and replicates the whole
# microbatch per device.
BATCH_AXES: Optional[tuple] = None

# Long-sequence attention implementation for the jnp path:
#   "chunked"    — kv-only blocking (baseline; computes masked halves)
#   "chunked2d"  — q+kv blocking with block-level causal/window skips
#                  (§Perf optimization: ~2x FLOPs for causal, O(S/window)x
#                  for sliding-window layers)
ATTN_IMPL: str = "chunked"


def _constrain(x: jax.Array, spec) -> jax.Array:
    from jax.sharding import PartitionSpec as P

    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x  # no mesh context (unit tests)


def constrain_batch(x: jax.Array) -> jax.Array:
    """Pin dim 0 (batch / token-group) to the data axes; rest unconstrained."""
    from jax.sharding import PartitionSpec as P

    if BATCH_AXES is None:
        return x
    spec = (BATCH_AXES[0], *([P.UNCONSTRAINED] * (x.ndim - 1)))
    return _constrain(x, spec)


def constrain_vocab(logits: jax.Array) -> jax.Array:
    """Pin the vocab (last) dim to 'model' — keeps the unembed matmul
    vocab-parallel instead of letting GSPMD replicate the (D, V) weight."""
    from jax.sharding import PartitionSpec as P

    if BATCH_AXES is None:
        return logits
    spec = (*([P.UNCONSTRAINED] * (logits.ndim - 1)), "model")
    return _constrain(logits, spec)


def _attn_shard(q, k, v):
    from jax.sharding import PartitionSpec as P

    U = P.UNCONSTRAINED
    if ATTN_SHARDING == "heads":
        q = _constrain(q, (U, "model", U, U))
        k = _constrain(k, (U, "model", U, U))
        v = _constrain(v, (U, "model", U, U))
    elif ATTN_SHARDING == "qseq":
        q = _constrain(q, (U, None, "model", U))
        k = _constrain(k, (U, None, None, U))
        v = _constrain(v, (U, None, None, U))
    return q, k, v


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    backend: Optional[str] = None,
    bq: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Multi-head attention (B, Hq, S, Dh) x (B, Hkv, T, Dh) -> (B, Hq, S, Dh)."""
    if backend == "jnp":
        q, k, v = _attn_shard(q, k, v)
        s, t = q.shape[2], k.shape[2]
        if s * t <= 2048 * 2048:
            o = _ref.attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
        elif ATTN_IMPL == "chunked2d":
            o = _ref.attention_chunked_2d(q, k, v, causal=causal,
                                          window=window, softcap=softcap,
                                          bq=4096, bk=2048)
        elif (ATTN_IMPL in ("cp_zigzag", "cp_zigzag_native")
              and causal and window is None
              and q.shape[2] == k.shape[2] and q.shape[2] % 32 == 0):
            # §Perf: shard_map zigzag context parallelism — statically
            # balanced causal work; the 'native' mode keeps the residual
            # stream in zigzag layout end-to-end (no data movement)
            from repro.kernels.cp_attention import cp_zigzag_attention

            return cp_zigzag_attention(
                q, k, v, softcap=softcap, p_shards=16,
                pre_permuted=(ATTN_IMPL == "cp_zigzag_native"))
        else:
            # long sequences: statically-chunked online softmax (never
            # builds (S, T) logits; FLOPs stay visible to cost_analysis)
            o = _ref.attention_chunked(q, k, v, causal=causal, window=window,
                                       softcap=softcap, bk=1024)
        if ATTN_SHARDING == "qseq":
            from jax.sharding import PartitionSpec as P

            o = _constrain(o, (P.UNCONSTRAINED, None, "model", P.UNCONSTRAINED))
        return o
    return _fa(q, k, v, causal=causal, window=window, softcap=softcap,
               bq=bq, bk=bk, interpret=use_interpret(backend))


def ssd(
    x: jax.Array, a_log: jax.Array, b_coef: jax.Array, c_coef: jax.Array,
    chunk: int = 64,
    backend: Optional[str] = None,
) -> jax.Array:
    """Mamba2 SSD scan (B, S, H, P)."""
    if backend == "jnp":
        # chunked-vectorized path: static HLO, full FLOP visibility
        c = chunk if x.shape[1] % chunk == 0 else 1
        return _ref.ssd_chunked(x, a_log, b_coef, c_coef, chunk=c)
    return _ssd(x, a_log, b_coef, c_coef, chunk=chunk, interpret=use_interpret(backend))


def na_aggregate(
    src: np.ndarray,
    dst: np.ndarray,
    h: jax.Array,
    num_dst: int,
    weight: Optional[np.ndarray] = None,
    backend: Optional[str] = None,
    packed: Optional[PackedEdges] = None,
) -> jax.Array:
    """Neighbor aggregation: out[d] = sum_{(s,d) in E} w * h[s]."""
    if backend == "jnp":
        return _ref.seg_sum_na_ref(src, dst, h, num_dst, weight=weight)
    if packed is None:
        packed = pack_edge_blocks(src, dst, int(h.shape[0]), num_dst, weight=weight)
    elif weight is not None:
        packed = packed.with_weights(np.asarray(weight, np.float32))
    return seg_sum_na(packed, h, interpret=use_interpret(backend))


def _build_attention_packed_vjp(packed: PackedEdges, interpret: bool,
                                slope: float):
    """``custom_vjp``-wrapped attention NA for one packing, over the
    per-vertex score columns ``e_s`` and ``e_d`` and the features ``h``,
    each padded to the rows the kernels index.

    Forward is the two kernels and nothing per edge between them: the
    stats kernel builds each slot's logit ``leaky_relu(e_s[src] +
    e_d[dst])`` from the columns, folds it into the online (m, s) and
    writes the blocked logits; the aggregation kernel forms alpha from
    those and the final (m, s) and weighs the scatter with it.  The
    backward pass reuses the packing's flat edge map (uploaded on first
    use; the forward never reads it) and the forward's (m, s) to
    recompute alpha, then scatters cotangents with jnp segment-adds:

        grad_alpha_e = h[src_e] . g_out[dst_e]
        grad_logit_e = alpha_e (grad_alpha_e - t[dst_e]),
                       t[d] = sum_{e: dst_e=d} alpha_e grad_alpha_e
        grad_pre_e   = grad_logit_e * leaky_relu'(pre_e)
        grad_e_s[s]  = sum_{e: src_e=s} grad_pre_e   (grad_e_d likewise)
        grad_h[s]    = sum_{e: src_e=s} alpha_e g_out[dst_e]
    """
    geometry = dict(num_dst_tiles=packed.num_dst_tiles, src_band=packed.src_band,
                    dst_tile_rows=packed.dst_tile_rows, interpret=interpret)

    def primal(e_s, e_d, h):
        blocks = (*packed.device_blocked(), packed.device_valid())
        m, s, logits = attention_stats_call(*blocks, e_s, e_d, slope=slope,
                                            **geometry)
        return attention_seg_sum_call(*blocks, logits, m, s, h, **geometry), (m, s)

    @jax.custom_vjp
    def attention(e_s, e_d, h):
        return primal(e_s, e_d, h)[0]

    def fwd(e_s, e_d, h):
        out, (m, s) = primal(e_s, e_d, h)
        return out, (e_s, e_d, m, s, h)

    def bwd(res, g_out):
        e_s, e_d, m, s, h = res
        src_g, dst_g = packed.device_flat_edges()
        pre = e_s[src_g, 0] + e_d[dst_g, 0]
        logit = jnp.where(pre >= 0, pre, slope * pre)
        alpha = jnp.exp(logit - m[dst_g, 0]) / jnp.maximum(s[dst_g, 0], 1e-9)
        g_e = g_out[dst_g]  # (E, D)
        grad_alpha = jnp.sum(h[src_g].astype(jnp.float32) * g_e, axis=1)
        t = jnp.zeros((g_out.shape[0],), jnp.float32).at[dst_g].add(
            alpha * grad_alpha)
        grad_pre = alpha * (grad_alpha - t[dst_g]) * jnp.where(pre >= 0, 1.0, slope)
        grad_e_s = jnp.zeros_like(e_s).at[src_g, 0].add(grad_pre)
        grad_e_d = jnp.zeros_like(e_d).at[dst_g, 0].add(grad_pre)
        grad_h = jnp.zeros_like(h).at[src_g].add(
            (alpha[:, None] * g_e).astype(h.dtype))
        return grad_e_s, grad_e_d, grad_h

    attention.defvjp(fwd, bwd)
    return attention


def attention_packed_vjp(packed: PackedEdges, interpret: bool, slope: float):
    """Memoized accessor — one custom-VJP function per (packing,
    interpret, slope), cached on the packing so jitted train steps
    retrace nothing across steps (grad-safe ``BandedBatch`` reuse)."""
    cache = getattr(packed, "_attn_vjp_fns", None)
    if cache is None:
        cache = {}
        packed._attn_vjp_fns = cache
    key = (interpret, slope)
    fn = cache.get(key)
    if fn is None:
        fn = _build_attention_packed_vjp(packed, interpret, slope)
        cache[key] = fn
    return fn


def na_attention_packed(
    packed: PackedEdges,
    e_s: jax.Array,  # (N_src,) source scores h_src @ a_src, src numbering
    e_d: jax.Array,  # (N_dst,) destination scores h_dst @ a_dst (+ bias)
    h: jax.Array,  # (N_src, D) features in the packing's src numbering
    leaky_slope: float = 0.2,
    backend: Optional[str] = None,
) -> jax.Array:
    """Edge-softmax attention NA over a cached packing; returns (N_dst, D).

    Edge e's logit is ``leaky_relu(e_s[src_e] + e_d[dst_e])`` (a
    per-metapath bias belongs in ``e_d``); alpha is its softmax over the
    destination's in-edges, and ``out[d] = sum_e alpha_e h[src_e]``.  The
    scores go to the kernels as ``(rows, 1)`` columns, and the logits,
    the (m, s) stats and alpha are all made inside the two Pallas
    kernels: nothing per edge is built around them.  Differentiable in
    ``e_s``, ``e_d`` and ``h`` (see ``_build_attention_packed_vjp``).
    Kernel backends only ("pallas" / "interpret"); the jnp oracle needs
    the flat edge list and lives in ``na_attention_aggregate``.
    """
    assert backend != "jnp", "na_attention_packed is the kernel path"
    if not packed.num_blocks:
        return jnp.zeros((packed.num_dst, h.shape[1]), h.dtype)
    fn = attention_packed_vjp(packed, use_interpret(backend), leaky_slope)
    n_dst_pad = packed.num_dst_tiles * packed.dst_tile_rows
    e_s = src_rows(packed, jnp.asarray(e_s, jnp.float32)[:, None])
    e_d = jnp.asarray(e_d, jnp.float32)[:, None]
    e_d = jnp.pad(e_d, ((0, n_dst_pad - e_d.shape[0]), (0, 0)))
    return dst_rows(packed, fn(e_s, e_d, src_rows(packed, h)))


def na_attention_aggregate(
    src: np.ndarray,
    dst: np.ndarray,
    e_s: jax.Array,
    e_d: jax.Array,
    h: jax.Array,
    num_dst: int,
    leaky_slope: float = 0.2,
    backend: Optional[str] = None,
    packed: Optional[PackedEdges] = None,
) -> jax.Array:
    """Edge-softmax attention NA with logits ``leaky_relu(e_s[src] +
    e_d[dst])`` (see :func:`na_attention_packed`); returns (num_dst, D).

    ``packed`` supplies a cached packing of the (src, dst) stream (parity
    with ``na_aggregate``) — without it the stream is packed on the spot.
    """
    if backend == "jnp":
        e_s, e_d = jnp.asarray(e_s, jnp.float32), jnp.asarray(e_d, jnp.float32)
        logits = jax.nn.leaky_relu(e_s[src] + e_d[dst], leaky_slope)
        alpha = _ref.edge_softmax_ref(logits, jnp.asarray(dst), num_dst)
        # alpha stays on device: the jnp oracle is differentiable end to
        # end (the grad-parity tests differentiate through this path)
        return _ref.seg_sum_na_ref(src, dst, h, num_dst, weight=alpha)
    if packed is None:
        packed = pack_edge_blocks(src, dst, int(h.shape[0]), num_dst)
    return na_attention_packed(packed, e_s, e_d, h, leaky_slope, backend=backend)


def compose_boolean(
    a_dense: np.ndarray, b_dense: np.ndarray, backend: Optional[str] = None
):
    """Boolean adjacency product (SGB composition) via block-sparse SpGEMM."""
    if backend == "jnp":
        out = _ref.spgemm_ref(jnp.asarray(a_dense, jnp.float32),
                              jnp.asarray(b_dense, jnp.float32))
        return np.asarray(out), {}
    return compose_dense_blocked(a_dense, b_dense, interpret=use_interpret(backend))


def compose_boolean_padded(
    a: np.ndarray,  # (Mp, Kp) 0/1, tile-padded
    b: np.ndarray,  # (Kp, Np) 0/1, tile-padded
    a_occ: np.ndarray,
    b_occ: np.ndarray,
    backend: Optional[str] = None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """SGB composition over pre-padded operands with cached occupancy —
    the device executor's chain primitive (see ``core.sgb.DeviceComposer``).
    Returns (padded result, its occupancy, pruning stats)."""
    from repro.kernels.spgemm_bsr import compose_padded_blocked, tile_occupancy

    if backend == "jnp":
        out = np.asarray(jax.block_until_ready(
            _ref.spgemm_ref(jnp.asarray(a, jnp.float32),
                            jnp.asarray(b, jnp.float32))))
        return out, tile_occupancy(out), {}
    return compose_padded_blocked(a, b, a_occ, b_occ,
                                  interpret=use_interpret(backend))
