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
from repro.kernels.edge_softmax import edge_softmax_stats
from repro.kernels.flash_attention import flash_attention as _fa
from repro.kernels.seg_sum import (PackedEdges, gather_rows, pack_edge_blocks,
                                   seg_sum_na)
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


def _build_attention_packed_vjp(packed: PackedEdges, interpret: bool):
    """``custom_vjp``-wrapped fused attention NA for one packing.

    Forward is the kernel path (blocked logit scatter, online (m, s)
    stats, alpha-weighted ``seg_sum_na``).  The backward pass reuses the
    cached ``PackedEdges`` and the forward's online (m, s) stats to
    recompute alpha, then scatters cotangents to both the features and
    the logits (and through them the attention parameters) with jnp
    segment-adds over the packing's device-resident flat edge map — no
    host re-packing anywhere:

        grad_alpha_e = h[src_e] . g_out[dst_e] + g_alpha_e
        grad_logit_e = alpha_e (grad_alpha_e - t[dst_e]),
                       t[d] = sum_{e: dst_e=d} alpha_e grad_alpha_e
        grad_h[s]    = sum_{e: src_e=s} alpha_e g_out[dst_e]
    """
    src_g, dst_g = packed.device_flat_edges()
    num_dst = packed.num_dst

    def stats_alpha(logits):
        lb = packed.scatter_blocks(logits, fill=-1e30)
        m, s = edge_softmax_stats(packed, lb, interpret=interpret)
        alpha = (jnp.exp(logits - gather_rows(m, dst_g))
                 / jnp.maximum(gather_rows(s, dst_g), 1e-9))
        return m, s, alpha

    def primal(logits, h):
        _, _, alpha = stats_alpha(logits)
        out = seg_sum_na(
            packed, h, interpret=interpret,
            weights=packed.scatter_blocks(alpha, fill=0.0),
        )
        return out, alpha

    @jax.custom_vjp
    def attention(logits, h):
        return primal(logits, h)

    def fwd(logits, h):
        m, s, alpha = stats_alpha(logits)
        out = seg_sum_na(
            packed, h, interpret=interpret,
            weights=packed.scatter_blocks(alpha, fill=0.0),
        )
        return (out, alpha), (logits, m, s, h)

    def bwd(res, cots):
        logits, m, s, h = res
        g_out, g_alpha = cots
        alpha = jnp.exp(logits - m[dst_g]) / jnp.maximum(s[dst_g], 1e-9)
        g_e = g_out[dst_g]  # (E, D)
        grad_alpha = jnp.sum(h[src_g].astype(jnp.float32) * g_e, axis=1)
        grad_alpha = grad_alpha + g_alpha
        t = jnp.zeros((num_dst,), jnp.float32).at[dst_g].add(alpha * grad_alpha)
        grad_logits = alpha * (grad_alpha - t[dst_g])
        grad_h = jnp.zeros_like(h).at[src_g].add(
            (alpha[:, None] * g_e).astype(h.dtype))
        return grad_logits, grad_h

    attention.defvjp(fwd, bwd)
    return attention


def attention_packed_vjp(packed: PackedEdges, interpret: bool):
    """Memoized accessor — one custom-VJP function per (packing,
    interpret), cached on the packing so jitted train steps retrace
    nothing across steps (grad-safe ``BandedBatch`` reuse)."""
    cache = getattr(packed, "_attn_vjp_fns", None)
    if cache is None:
        cache = {}
        packed._attn_vjp_fns = cache
    fn = cache.get(interpret)
    if fn is None:
        fn = _build_attention_packed_vjp(packed, interpret)
        cache[interpret] = fn
    return fn


def na_attention_packed(
    packed: PackedEdges,
    edge_logits: jax.Array,  # (E,) logits in the packing's scheduled order
    h: jax.Array,  # (N_src, D) features in the packing's src numbering
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Device-resident fused attention NA over a cached packing.

    Per-edge logits scatter into the blocked layout on device
    (``PackedEdges.scatter_blocks``), the Pallas stats kernel folds them
    into online per-destination (m, s), and the alpha-weighted aggregation
    reuses the same blocks — no host re-packing or per-block Python loops
    anywhere on the per-layer path.  Differentiable in ``edge_logits`` and
    ``h`` (see ``_build_attention_packed_vjp``).  Kernel backends only
    ("pallas" / "interpret"); the jnp oracle needs the flat edge list and
    lives in ``na_attention_aggregate``.
    """
    assert backend != "jnp", "na_attention_packed is the kernel path"
    fn = attention_packed_vjp(packed, use_interpret(backend))
    return fn(jnp.asarray(edge_logits, jnp.float32), h)


def na_attention_aggregate(
    src: np.ndarray,
    dst: np.ndarray,
    edge_logits: np.ndarray,
    h: jax.Array,
    num_dst: int,
    backend: Optional[str] = None,
    packed: Optional[PackedEdges] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Edge-softmax attention NA; returns (aggregated, alpha).

    ``packed`` supplies a cached packing of the (src, dst) stream (parity
    with ``na_aggregate``) — without it the stream is packed on the spot.
    """
    if backend == "jnp":
        alpha = _ref.edge_softmax_ref(jnp.asarray(edge_logits), jnp.asarray(dst), num_dst)
        # keep alpha on device: the jnp oracle stays differentiable end to
        # end (the grad-parity tests differentiate through this path)
        out = _ref.seg_sum_na_ref(src, dst, h, num_dst, weight=alpha)
        return out, alpha
    if packed is None:
        packed = pack_edge_blocks(src, dst, int(h.shape[0]), num_dst)
    return na_attention_packed(packed, edge_logits, h, backend=backend)


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
