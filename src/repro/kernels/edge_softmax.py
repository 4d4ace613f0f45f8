"""Per-destination edge-softmax statistics kernels (flash-style online m/s).

The attention NA sub-stage needs alpha_e = exp(l_e - m[dst_e]) / s[dst_e]
with m/s the per-destination max / sum-of-exp.  A destination's edges can
span several edge blocks (and, after restructuring, two subgraphs), so the
kernel accumulates (m, s) *online* across consecutive blocks of the same
destination tile — exactly the flash-attention rescaling trick applied to
graph aggregation:

    m_new = max(m_old, max_block)
    s_new = s_old * exp(m_old - m_new) + sum_e exp(l_e - m_new[dst_e])

Two kernels, one name (``na_softmax_stats``):

  * ``_attn_stats_kernel`` (the model's attention path) builds each
    slot's logit itself, ``leaky_relu(e_s[src] + e_d[dst])``, from the
    per-vertex score columns of the block's source band and destination
    tile, and writes the blocked logits out for the aggregation kernel
    (``seg_sum.attention_seg_sum_call``), which forms alpha from them and
    the final (m, s): no per-edge array is built outside the two kernels.
    With H heads (HAN's eight) the columns, (m, s) and logits carry one
    column or row per head, and ``_attn_stats_heads_kernel`` does the
    one-hot selections as MXU products (``attention_stats_call`` picks).
  * ``_stats_kernel`` reads logits already in the blocked layout: the
    sharded executor's merged multi-relation stream
    (``edge_softmax_stats_blocks``) and the flat-logit reference path
    (``edge_softmax_stats`` over ``PackedEdges.scatter_blocks``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import use_interpret
from repro.kernels.seg_sum import PackedEdges, block_rows

_NEG = -1e30


def _stats_kernel(
    dtile_ref, first_ref,  # scalar-prefetch
    logit_ref, dstl_ref, valid_ref,  # (1, EB)
    m_ref, s_ref,  # (TD, 1) accumulators: one row per destination
    *, eb: int, td: int,
):
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    logit = logit_ref[...]  # (1, EB)
    dstl = dstl_ref[...].astype(jnp.int32)  # host arrays are int16
    valid = valid_ref[...] > 0
    scat = jax.lax.broadcasted_iota(jnp.int32, (td, eb), 0) == dstl
    eff = scat & valid  # (TD, EB): edge e lands on row t
    masked = jnp.where(eff, logit, _NEG)
    blockmax = jnp.max(masked, axis=1, keepdims=True)  # (TD, 1)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, blockmax)
    # guard: exp(-inf - -inf) -> use 0 scale when m_old was -inf
    scale = jnp.where(m_old > _NEG / 2, jnp.exp(m_old - m_new), 0.0)
    # per-edge m_new[dst] and per-row sums as masked reductions over the
    # one-hot: exact in f32, where an MXU pass would round to bf16
    m_e = jnp.sum(jnp.where(eff, m_new, 0.0), axis=0, keepdims=True)  # (1, EB)
    ex = jnp.where(valid, jnp.exp(logit - m_e), 0.0)
    s_add = jnp.sum(jnp.where(eff, ex, 0.0), axis=1, keepdims=True)  # (TD, 1)
    s_ref[...] = s_ref[...] * scale + s_add
    m_ref[...] = m_new


@functools.partial(
    jax.jit, static_argnames=("num_dst_tiles", "dst_tile_rows", "interpret")
)
def _stats_call(dst_tile, first, logits, dst_local, valid,
                num_dst_tiles, dst_tile_rows, interpret):
    nb, eb = logits.shape
    td = dst_tile_rows
    row = pl.BlockSpec((None, 1, eb), lambda i, t, f: (i, 0, 0))
    col = pl.BlockSpec((td, 1), lambda i, t, f: (t[i], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[row, row, row],
        out_specs=[col, col],
    )
    kern = functools.partial(_stats_kernel, eb=eb, td=td)
    m, s = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_dst_tiles * td, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_dst_tiles * td, 1), jnp.float32),
        ],
        interpret=interpret,
        name="na_softmax_stats",
    )(dst_tile, first, block_rows(logits), block_rows(dst_local),
      block_rows(valid))
    return m.reshape(num_dst_tiles, td), s.reshape(num_dst_tiles, td)


def _attn_stats_kernel(
    band_ref, dtile_ref, first_ref,  # scalar-prefetch
    srcl_ref, dstl_ref, valid_ref,  # (1, EB) rows of block i
    es_ref,  # (BAND, 1): e_s over block i's source band
    ed_ref,  # (TD, 1): e_d over block i's destination tile
    m_ref, s_ref,  # (TD, 1) accumulators: one row per destination
    logit_ref,  # (1, EB): block i's logits, for the aggregation kernel
    *, eb: int, band: int, td: int, slope: float,
):
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    valid = valid_ref[...] > 0
    scat = jax.lax.broadcasted_iota(jnp.int32, (td, eb), 0) == dstl_ref[...]
    sel = jax.lax.broadcasted_iota(jnp.int32, (band, eb), 0) == srcl_ref[...]
    # each slot's e_s[src] + e_d[dst] as masked sums over the one-hots:
    # exact in f32 (one term each), where an MXU pass would round to bf16
    pre = (jnp.sum(jnp.where(sel, es_ref[...], 0.0), axis=0, keepdims=True)
           + jnp.sum(jnp.where(scat, ed_ref[...], 0.0), axis=0, keepdims=True))
    logit = jnp.where(valid, jnp.where(pre >= 0, pre, slope * pre), _NEG)
    logit_ref[...] = logit
    eff = scat & valid  # (TD, EB): edge e lands on row t
    blockmax = jnp.max(jnp.where(eff, logit, _NEG), axis=1, keepdims=True)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, blockmax)
    scale = jnp.where(m_old > _NEG / 2, jnp.exp(m_old - m_new), 0.0)
    m_e = jnp.sum(jnp.where(eff, m_new, 0.0), axis=0, keepdims=True)  # (1, EB)
    ex = jnp.where(valid, jnp.exp(logit - m_e), 0.0)
    s_add = jnp.sum(jnp.where(eff, ex, 0.0), axis=1, keepdims=True)  # (TD, 1)
    s_ref[...] = s_ref[...] * scale + s_add
    m_ref[...] = m_new


def _attn_stats_heads_kernel(
    band_ref, dtile_ref, first_ref,  # scalar-prefetch
    srcl_ref, dstl_ref, valid_ref,  # (1, EB) rows of block i
    es_ref,  # (BAND, H): e_s over block i's source band, a column per head
    ed_ref,  # (TD, H): e_d over block i's destination tile
    m_ref, s_ref,  # (TD, H) accumulators: one row per destination
    logit_ref,  # (H, EB): block i's logits, a row per head
    *, eb: int, band: int, td: int, slope: float,
):
    """``_attn_stats_kernel`` for H > 1 heads, every head's statistics in
    one step.  The one-hot selections run on the MXU at ``HIGHEST``, each
    a single term per output and so exact in f32: ``e_s[src]`` and
    ``e_d[dst]`` of every head, ``m[dst]`` and each destination's sum of
    ``exp``.  Only the per-destination max stays a masked reduction, one
    per head."""
    i = pl.program_id(0)
    heads = es_ref.shape[1]

    @pl.when(first_ref[i] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    hi = jax.lax.Precision.HIGHEST
    tn = (((0,), (0,)), ((), ()))  # contract the leading dims: a^T @ b
    nt = (((1,), (1,)), ((), ()))  # contract the trailing dims: a @ b^T
    valid = valid_ref[...] > 0  # (1, EB)
    scat = jax.lax.broadcasted_iota(jnp.int32, (td, eb), 0) == dstl_ref[...]
    sel = jax.lax.broadcasted_iota(jnp.int32, (band, eb), 0) == srcl_ref[...]
    pre = (jax.lax.dot_general(es_ref[...], sel.astype(jnp.float32), tn, precision=hi)
           + jax.lax.dot_general(ed_ref[...], scat.astype(jnp.float32), tn,
                                 precision=hi))  # (H, EB)
    logit = jnp.where(valid, jnp.where(pre >= 0, pre, slope * pre), _NEG)
    logit_ref[...] = logit
    eff = scat & valid  # (TD, EB): edge e lands on row t
    head = jax.lax.broadcasted_iota(jnp.int32, (td, heads), 1)
    blockmax = jnp.full((td, heads), _NEG, jnp.float32)
    for k in range(heads):
        col = jnp.max(jnp.where(eff, logit[k:k + 1, :], _NEG), axis=1, keepdims=True)
        blockmax = jnp.where(head == k, col, blockmax)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, blockmax)
    scale = jnp.where(m_old > _NEG / 2, jnp.exp(m_old - m_new), 0.0)
    onehot = eff.astype(jnp.float32)
    m_e = jax.lax.dot_general(m_new, onehot, tn, precision=hi)  # (H, EB)
    ex = jnp.where(valid, jnp.exp(logit - m_e), 0.0)
    s_add = jax.lax.dot_general(onehot, ex, nt, precision=hi)  # (TD, H)
    s_ref[...] = s_ref[...] * scale + s_add
    m_ref[...] = m_new


@functools.partial(
    jax.jit,
    static_argnames=("num_dst_tiles", "src_band", "dst_tile_rows", "slope",
                     "interpret"),
)
def attention_stats_call(band, dst_tile, first, src_local, dst_local, valid,
                         e_s, e_d, num_dst_tiles, src_band, dst_tile_rows,
                         slope, interpret):
    """The attention path's stats kernel over a packing's block arrays
    (``(nb, 1, EB)`` rows) and the ``(rows, H)`` score columns ``e_s``
    (``src_band`` rows per band) and ``e_d`` (``dst_tile_rows`` per
    tile, any bias folded in), one column per head.  Returns the
    ``(num_dst_tiles * dst_tile_rows, H)`` columns (m, s), whose rows of
    tiles no block touches hold uninitialized memory, and the ``(nb, H,
    EB)`` blocked logits, ``-1e30`` on padding slots.  One head runs
    ``_attn_stats_kernel``, more ``_attn_stats_heads_kernel``."""
    nb, eb = src_local.shape[0], src_local.shape[-1]
    td = dst_tile_rows
    heads = e_s.shape[1]
    row = pl.BlockSpec((None, 1, eb), lambda i, b, t, f: (i, 0, 0))
    col = pl.BlockSpec((td, heads), lambda i, b, t, f: (t[i], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[row, row, row,
                  pl.BlockSpec((src_band, heads), lambda i, b, t, f: (b[i], 0)),
                  col],
        out_specs=[col, col,
                   pl.BlockSpec((None, heads, eb), lambda i, b, t, f: (i, 0, 0))],
    )
    body = _attn_stats_kernel if heads == 1 else _attn_stats_heads_kernel
    kern = functools.partial(body, eb=eb, band=src_band, td=td, slope=slope)
    stats = jax.ShapeDtypeStruct((num_dst_tiles * td, heads), jnp.float32)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[stats, stats,
                   jax.ShapeDtypeStruct((nb, heads, eb), jnp.float32)],
        interpret=interpret,
        name="na_softmax_stats",
    )(band, dst_tile, first, src_local, dst_local, valid, e_s, e_d)


def edge_softmax_stats(
    packed: PackedEdges,
    logits_blocked: jax.Array,  # (nb, EB) f32 blocked layout (np or device)
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-destination (m, s); rows never touched get m=-1e30, s=0.

    ``logits_blocked`` may be a device array built by
    ``PackedEdges.scatter_blocks``, as in the flat-logit reference path
    the attention kernels are tested against.  (m, s) accumulate online
    across every block of a destination tile, including non-consecutive
    revisits: ``first_in_tile`` means first touch ever (see
    kernels/seg_sum.py).
    """
    if interpret is None:
        interpret = use_interpret()
    td = packed.dst_tile_rows
    num_dst_tiles = packed.num_dst_tiles
    # count-derived validity, NOT the weights: zero-weight edges still
    # belong to their destination's softmax
    _, dtile, first, _, dstl = packed.device_blocked()
    m, s = _stats_call(
        dtile, first, jnp.asarray(logits_blocked, jnp.float32), dstl,
        packed.device_valid(), num_dst_tiles, td, interpret,
    )
    touched = np.zeros(num_dst_tiles, bool)
    if packed.num_blocks:
        touched[np.asarray(packed.dst_tile)] = True
    tmask = jnp.asarray(touched)[:, None]
    m = jnp.where(tmask, m, _NEG).reshape(-1)[: packed.num_dst]
    s = jnp.where(tmask, s, 0.0).reshape(-1)[: packed.num_dst]
    return m, s


def edge_softmax_stats_blocks(
    dst_tile, first, logits_blocked, dst_local, valid, *,
    num_dst_tiles: int, dst_tile_rows: int, interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Raw blocked-stream stats kernel entry over explicit block arrays.

    The sibling of :func:`edge_softmax_stats` for callers that own the
    block arrays instead of a ``PackedEdges`` — the sharded executor
    (``repro.distributed.hgnn``) feeds per-device sub-streams (possibly
    traced, inside ``shard_map``) whose tiles live in a concatenated
    multi-relation space.  Returns tile-shaped ``(m, s)`` of
    ``(num_dst_tiles, dst_tile_rows)`` each; rows of tiles never touched
    by a ``first == 1`` block hold uninitialized memory, and padding
    blocks must carry all-invalid slots so they leave their target tile's
    stats at the (-1e30, 0) init.
    """
    if interpret is None:
        interpret = use_interpret()
    return _stats_call(dst_tile, first, logits_blocked, dst_local, valid,
                       num_dst_tiles, dst_tile_rows, interpret)


def block_logits(packed: PackedEdges, edge_logits_in_order: np.ndarray) -> np.ndarray:
    """Scatter a flat (E,) logit array (in scheduled edge order) into the
    (nb, EB) blocked layout matching ``packed`` (padding gets -1e30).

    Host-side variant (one fancy-indexed scatter via the edge map); the
    device-resident path uses ``packed.scatter_blocks(logits, fill=-1e30)``.
    """
    nb, eb = packed.src_local.shape
    blk, slot = packed.edge_map()
    assert edge_logits_in_order.shape[0] == blk.shape[0]
    out = np.full((nb, eb), _NEG, np.float32)
    out[blk, slot] = np.asarray(edge_logits_in_order, np.float32)
    return out
