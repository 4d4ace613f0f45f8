"""Blocked NA aggregation kernel: weighted gather + segment-sum on the MXU.

TPU adaptation of the NA sub-stage datapath (DESIGN.md §2).  The MXU has no
scatter/gather unit, so sparse aggregation is expressed as two small one-hot
matmuls per edge block:

    gathered  = onehot(src_local) @ H_band                 # (EB,BAND)@(BAND,D)
    out_tile += onehot(dst_local) @ (gathered * w)         # (TD,EB)@(EB,D)

The Graph Restructurer makes this efficient: after restructuring, each edge
block's sources fall in a narrow row *band* of the feature matrix, so the
kernel streams one (BAND, D) feature tile HBM->VMEM per block instead of
random rows.  The host-side ``pack_edge_blocks`` materializes this banded
block format: it groups each destination tile's run of the scheduled stream
by source band and cuts each (tile run, band) group into blocks, so the
blocks it needs (and feature bytes moved) count those groups: a layout
whose tiles gather from fewer bands packs into fewer, fuller blocks.
Grouping hides most of the schedule's own order, so the paper's
buffer-thrashing claim is metered by ``stream_tile_loads``, the tiles a
walk over the stream in its own order loads
(``benchmarks/paper_figures.py::bench_dram_access`` and
``benchmarks/gfp_bench.py`` report both).

Grid: one step per edge block in tile-run order; the output tile is
zero-initialized on the FIRST TOUCH EVER of its destination tile
(``first_in_tile``) and accumulated on every later visit — including
non-consecutive revisits, which the restructured schedule produces when a
backbone destination's edges span two subgraphs.  Bands are aligned to
BAND-row units so the feature BlockSpec index is just the band id
(scalar-prefetched).

``seg_sum_na`` is differentiable: a ``jax.custom_vjp`` wraps the Pallas
call, and the backward pass is a gather through the same cached
edge -> (block, slot) map — ``grad_h[s] = sum_{e: src_e=s} w_e g[dst_e]``
and (for traced blocked weights, the attention path) ``grad_w[b, k] =
h[src] . g[dst]`` — composed in jnp over device-resident flat edge
indices derived once per packing.  No host re-packing happens on the
backward path, so a cached ``BandedBatch`` serves training steps as-is.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import use_interpret

# Edge-block geometry.  VMEM at defaults (fp32): gather one-hot 256x512x4 =
# 512 KB, scatter one-hot 128x256x4 = 128 KB, feature band 512xD, out tile
# 128xD — comfortably inside ~16 MB VMEM for D <= 1024.
EDGE_BLOCK = 256  # edges per block (EB)
SRC_BAND = 512  # feature rows per band (BAND); also the band alignment
DST_TILE = 128  # output rows per tile (TD)


@dataclasses.dataclass
class PackedEdges:
    """Banded edge-block format consumed by the kernel (host-built)."""

    src_local: np.ndarray  # (nb, EB) int: src - band*SRC_BAND (pad: w=0)
    dst_local: np.ndarray  # (nb, EB) int: dst - dst_tile*DST_TILE
    # (nb, EB) float32 edge weights, 0 for padding.  None = unweighted:
    # the ones-over-valid-slots mask is materialized lazily by
    # ``valid_weight()`` on first kernel use (packing a graph no model
    # ends up running never pays for it) and cached on the instance, so
    # the shared per-semantic-graph packing builds it at most once.
    weight: Optional[np.ndarray]
    band: np.ndarray  # (nb,) int32 band unit index
    dst_tile: np.ndarray  # (nb,) int32
    first_in_tile: np.ndarray  # (nb,) int32: 1 = first touch EVER of dst tile
    count: np.ndarray  # (nb,) int32 valid edges in block (rest is padding)
    num_src: int
    num_dst: int
    # Edge -> (block, slot) index map over the scheduled stream: edge p of
    # the flat stream lives at [edge_block_id[p], edge_slot[p]] of the
    # blocked arrays.  A permutation, not a chunking: the packer groups
    # each tile run by band, so a block's slots need not be contiguous in
    # the stream.  Per-layer weights/logits scatter through it.
    edge_block_id: np.ndarray  # (E,) int32
    edge_slot: np.ndarray  # (E,) int32
    edge_block: int = EDGE_BLOCK
    src_band: int = SRC_BAND
    dst_tile_rows: int = DST_TILE

    @property
    def num_blocks(self) -> int:
        return int(self.band.shape[0])

    @property
    def num_edges(self) -> int:
        return int(self.count.sum())

    @property
    def num_slots(self) -> int:
        """Edge slots the kernel steps over: blocks x ``edge_block``."""
        return self.num_blocks * self.edge_block

    @property
    def fill(self) -> float:
        """Valid edges over slots: the share of each grid step's one-hot
        work that aggregates a real edge (0 for an empty packing)."""
        return self.num_edges / self.num_slots if self.num_blocks else 0.0

    def hbm_feature_bytes(self, d: int, elem_bytes: int = 4) -> int:
        """Feature bytes streamed HBM->VMEM: one (BAND, D) tile per block.

        ``elem_bytes`` defaults to 4 (fp32) — the kernel gathers and
        accumulates in fp32; pass 2 only when the feature tiles themselves
        are stored bf16.
        """
        return self.num_blocks * self.src_band * d * elem_bytes

    def edge_map(self) -> Tuple[np.ndarray, np.ndarray]:
        """(edge_block_id, edge_slot) for the flat scheduled stream."""
        return self.edge_block_id, self.edge_slot

    def valid_mask(self) -> np.ndarray:
        """(nb, EB) float32: 1 on valid slots, 0 on padding (memoized).

        Purely count-derived — NOT the edge weights: a weighted packing
        can legitimately carry zero weights on valid slots, and validity
        (e.g. the softmax stats mask) must still include those edges.
        """
        vm = getattr(self, "_valid_mask", None)
        if vm is None:
            eb = self.src_local.shape[1]
            vm = (
                np.arange(eb, dtype=np.int32)[None, :] < self.count[:, None]
            ).astype(np.float32)
            self._valid_mask = vm
        return vm

    def valid_weight(self) -> np.ndarray:
        """(nb, EB) float32 weights; unweighted packs resolve to the
        ones-over-valid-slots mask (built lazily, cached)."""
        if self.weight is None:
            self.weight = self.valid_mask()
        return self.weight

    def with_weights(self, flat_weights: np.ndarray) -> "PackedEdges":
        """Same blocking, new per-edge weights given in scheduled order."""
        blk, slot = self.edge_map()
        assert flat_weights.shape[0] == blk.shape[0]
        nb, eb = self.src_local.shape
        ww = np.zeros((nb, eb), np.float32)
        ww[blk, slot] = np.asarray(flat_weights, np.float32)
        return dataclasses.replace(self, weight=ww)

    def scatter_blocks(self, flat: jax.Array, fill: float = 0.0) -> jax.Array:
        """Device-side scatter of per-edge values (scheduled order) into the
        (nb, EB) blocked layout; padding slots get ``fill``.

        This is the device-resident sibling of ``with_weights`` /
        ``edge_softmax.block_logits``: the index map is a static constant
        (uploaded once per packing, cached device-side), so per-layer
        logits/weights never round-trip through the host.
        """
        nb, eb = self.src_local.shape
        out = jnp.full((nb, eb), fill, jnp.float32)
        blk, slot = self.device_edge_map()
        if blk.shape[0] == 0:
            return out
        return out.at[blk, slot].set(jnp.asarray(flat, jnp.float32))

    def device_edge_map(self) -> Tuple[jax.Array, jax.Array]:
        """Device-resident copy of ``edge_map()``, uploaded once and
        cached on the instance (the attention path scatters twice per
        layer per semantic graph — re-staging (E,) index constants every
        call would be a per-layer host round-trip)."""
        dm = getattr(self, "_device_map", None)
        if dm is None:
            blk, slot = self.edge_map()
            # ensure_compile_time_eval: the first call may happen inside a
            # jitted train step's trace — the cached arrays must be
            # concrete, not tracers, or they leak into later traces
            with jax.ensure_compile_time_eval():
                dm = (jnp.asarray(blk), jnp.asarray(slot))
            self._device_map = dm
        return dm

    def flat_global_edges(self) -> Tuple[np.ndarray, np.ndarray]:
        """(src, dst) global ids of the flat scheduled stream, recovered
        from the blocked layout (memoized).  This is the index map the
        VJPs gather through: the banded forward and its backward agree on
        edge order by construction because both read the same blocks."""
        fe = getattr(self, "_flat_edges", None)
        if fe is None:
            blk, slot = self.edge_map()
            src = (
                self.src_local[blk, slot].astype(np.int64)
                + self.band[blk].astype(np.int64) * self.src_band
            )
            dst = (
                self.dst_local[blk, slot].astype(np.int64)
                + self.dst_tile[blk].astype(np.int64) * self.dst_tile_rows
            )
            fe = (src.astype(np.int32), dst.astype(np.int32))
            self._flat_edges = fe
        return fe

    def device_flat_edges(self) -> Tuple[jax.Array, jax.Array]:
        """Device-resident ``flat_global_edges()`` (uploaded once; the
        backward pass of every layer of every train step reuses it)."""
        dfe = getattr(self, "_device_flat_edges", None)
        if dfe is None:
            src, dst = self.flat_global_edges()
            with jax.ensure_compile_time_eval():  # see device_edge_map
                dfe = (jnp.asarray(src), jnp.asarray(dst))
            self._device_flat_edges = dfe
        return dfe

    def device_blocked(self) -> Tuple[jax.Array, ...]:
        """Device-resident copies of the static block arrays consumed by
        the NA kernel (band, dst_tile, first_in_tile, src_local,
        dst_local), uploaded once per packing."""
        db = getattr(self, "_device_blocked", None)
        if db is None:
            with jax.ensure_compile_time_eval():  # see device_edge_map
                db = (
                    jnp.asarray(self.band),
                    jnp.asarray(self.dst_tile),
                    jnp.asarray(self.first_in_tile),
                    jnp.asarray(self.src_local),
                    jnp.asarray(self.dst_local),
                )
            self._device_blocked = db
        return db


def _first_touch_flags(dt: np.ndarray) -> np.ndarray:
    """1 for the first block EVER targeting each dst tile, else 0.

    The flag gates the kernel's output-tile zero-init, so it must mean
    "first touch ever": the restructured schedule revisits a tile
    non-consecutively when a backbone destination's edges span two
    subgraphs, and re-zeroing on revisit would discard the accumulation
    from the earlier subgraph.
    """
    ft = np.zeros(dt.shape[0], np.int32)
    if dt.shape[0]:
        _, first_idx = np.unique(dt, return_index=True)
        ft[first_idx] = 1
    return ft


def shard_blocked(packed: PackedEdges, block_ids: np.ndarray) -> dict:
    """Host-side slice of a packing's block stream for one shard.

    ``block_ids`` selects blocks (ascending, so the shard preserves the
    schedule's within-tile accumulation order) and the result carries
    everything the raw kernel entry (``seg_sum_blocks``) needs for that
    sub-stream.  ``first`` is recomputed over the slice: a shard plan that
    keeps every block of a dst tile on one device (the
    ``repro.distributed.hgnn`` invariant) makes first-touch-in-shard
    coincide with first-touch-ever, so the kernel's zero-init stays
    correct per device without cross-device coordination.
    """
    ids = np.asarray(block_ids, np.int64)
    assert ids.size == 0 or (np.diff(ids) > 0).all(), \
        "block_ids must be strictly ascending (schedule order)"
    dt = packed.dst_tile[ids]
    return {
        "band": packed.band[ids].astype(np.int32),
        "dst_tile": dt.astype(np.int32),
        "first": _first_touch_flags(dt),
        "src_local": packed.src_local[ids],
        "dst_local": packed.dst_local[ids],
        "weight": packed.valid_weight()[ids],
        "count": packed.count[ids].astype(np.int32),
    }


def _tile_run_starts(dtile: np.ndarray) -> np.ndarray:
    """(E,) bool: True where a maximal run of constant dst tile starts."""
    new = np.empty(dtile.shape[0], bool)
    new[0] = True
    np.not_equal(dtile[1:], dtile[:-1], out=new[1:])
    return new


def stream_tile_loads(
    src: np.ndarray,
    dst: np.ndarray,
    *,
    edge_block: int = EDGE_BLOCK,
    src_band: int = SRC_BAND,
    dst_tile: int = DST_TILE,
) -> int:
    """(BAND, D) feature-tile loads of a walk over the stream in its own order.

    One load per ``edge_block`` chunk of every maximal run of constant
    (dst tile, band): the blocks a packer that cuts the stream in order,
    without grouping tile runs by band, would emit.  This meters the
    schedule's own locality (the paper's buffer-thrashing claim), which
    ``pack_edge_blocks``' block count does not: grouping by band packs a
    plain (dst, src) order and a restructured one alike.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if src.size == 0:
        return 0
    new = _tile_run_starts(dst // dst_tile)
    band = src // src_band
    new[1:] |= band[1:] != band[:-1]
    run_len = np.diff(np.append(np.flatnonzero(new), src.size))
    return int((-(-run_len // edge_block)).sum())


def pack_edge_blocks(
    src: np.ndarray,
    dst: np.ndarray,
    num_src: int,
    num_dst: int,
    weight: Optional[np.ndarray] = None,
    edge_block: int = EDGE_BLOCK,
    src_band: int = SRC_BAND,
    dst_tile: int = DST_TILE,
) -> PackedEdges:
    """Cut the (already scheduled) edge stream into banded blocks.

    Within each maximal run of constant destination tile, the edges are
    grouped by ``src_band``-aligned source band (a stable sort, so each
    group keeps stream order), and each (tile run, band) group is cut
    into ``edge_block`` chunks.  Tile runs keep their stream order, so a
    tile the schedule revisits is revisited in the same order and
    ``first_in_tile`` still means first touch ever.  The stream itself is
    not reordered: the edge map (``edge_block_id``/``edge_slot``) says
    where each stream edge landed.  Layouts whose tiles gather from many
    bands, or whose tiles are split into many runs, need more blocks:
    the block count is the number of (tile run, band) groups, cut into
    chunks (``stream_tile_loads`` meters the order within a run).

    Fully vectorized: one stable argsort groups the stream, group
    boundaries come from adjacent (dst-tile, band) changes in the grouped
    order, groups are split into ``edge_block`` chunks with O(num_blocks)
    run-length arithmetic, and the blocked arrays are built with one
    fancy-indexed scatter per array — O(E log E) numpy work with no
    Python-level edge loop (``pack_edge_blocks_reference`` keeps the loop
    form as the oracle).  Local indices are stored int16 (they are
    bounded by the block geometry, 512/128) and unweighted packs defer
    the ones-mask (``PackedEdges.weight = None``): the dense (nb, EB)
    arrays are the packer's memory-bandwidth floor.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    E = src.size
    if E == 0:
        z2 = np.zeros((0, edge_block), np.int16)
        return PackedEdges(
            z2, z2.copy(), np.zeros((0, edge_block), np.float32),
            np.zeros(0, np.int32), np.zeros(0, np.int32),
            np.zeros(0, np.int32), np.zeros(0, np.int32), num_src, num_dst,
            edge_block=edge_block, src_band=src_band, dst_tile_rows=dst_tile,
            edge_block_id=np.zeros(0, np.int32), edge_slot=np.zeros(0, np.int32),
        )

    dtile = dst // dst_tile
    band = src // src_band
    # grouped order: tile runs in stream order, bands ascending within each
    tile_run = np.cumsum(_tile_run_starts(dtile)) - 1
    order = np.argsort(tile_run * (int(band.max()) + 1) + band, kind="stable")
    g_tile, g_band = dtile[order], band[order]
    # group = maximal stretch of constant (dst tile, band) in grouped
    # order (adjacent tile runs differ in tile); block = group chunk
    newrun = np.empty(E, bool)
    newrun[0] = True
    np.logical_or(g_tile[1:] != g_tile[:-1], g_band[1:] != g_band[:-1],
                  out=newrun[1:])
    run_starts = np.flatnonzero(newrun)
    run_len = np.diff(np.append(run_starts, E))
    blocks_per_run = -(-run_len // edge_block)
    nb = int(blocks_per_run.sum())
    run_of_blk = np.repeat(np.arange(run_starts.size), blocks_per_run)
    blk_cum = np.concatenate(([0], np.cumsum(blocks_per_run)[:-1]))
    chunk = np.arange(nb) - blk_cum[run_of_blk]  # block index within run
    starts = run_starts[run_of_blk] + chunk * edge_block
    cnt = np.diff(np.append(starts, E)).astype(np.int32)
    # edge map: grouped position q holds stream edge order[q]
    blk = np.empty(E, np.int32)
    slot = np.empty(E, np.int32)
    blk[order] = np.repeat(np.arange(nb), cnt)
    slot[order] = np.arange(E) - np.repeat(starts, cnt)

    bandv = g_band[starts].astype(np.int32)
    dt = g_tile[starts].astype(np.int32)
    ft = _first_touch_flags(dt)

    sl = np.zeros((nb, edge_block), np.int16)
    dl = np.zeros((nb, edge_block), np.int16)
    sl[blk, slot] = src - band * src_band
    dl[blk, slot] = dst - dtile * dst_tile
    if weight is None:
        ww = None  # lazy ones-mask (valid_weight)
    else:
        ww = np.zeros((nb, edge_block), np.float32)
        ww[blk, slot] = np.asarray(weight, np.float32)
    return PackedEdges(
        sl, dl, ww, bandv, dt, ft, cnt, num_src, num_dst,
        edge_block=edge_block, src_band=src_band, dst_tile_rows=dst_tile,
        edge_block_id=blk, edge_slot=slot,
    )


def pack_edge_blocks_reference(
    src: np.ndarray,
    dst: np.ndarray,
    num_src: int,
    num_dst: int,
    weight: Optional[np.ndarray] = None,
    edge_block: int = EDGE_BLOCK,
    src_band: int = SRC_BAND,
    dst_tile: int = DST_TILE,
) -> PackedEdges:
    """The packer in Python-loop form, kept as the equivalence oracle and
    the baseline of ``benchmarks/gfp_bench.py``'s packer-throughput meter:
    walk each tile run, bucket its edges by band in stream order, and
    emit each bucket in ``edge_block`` chunks, bands ascending."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.ones(src.shape, np.float32) if weight is None else np.asarray(weight, np.float32)
    E = src.size
    blocks = []  # (stream positions, band, tile)
    i = 0
    while i < E:
        tile = int(dst[i] // dst_tile)
        j = i
        groups = {}
        while j < E and dst[j] // dst_tile == tile:
            groups.setdefault(int(src[j] // src_band), []).append(j)
            j += 1
        for band in sorted(groups):
            pos = groups[band]
            for c in range(0, len(pos), edge_block):
                blocks.append((pos[c:c + edge_block], band, tile))
        i = j

    nb = len(blocks)
    sl = np.zeros((nb, edge_block), np.int32)
    dl = np.zeros((nb, edge_block), np.int32)
    ww = np.zeros((nb, edge_block), np.float32)
    bandv = np.zeros((nb,), np.int32)
    dt = np.zeros((nb,), np.int32)
    cnt = np.zeros((nb,), np.int32)
    blk = np.zeros((E,), np.int32)
    slot = np.zeros((E,), np.int32)
    for k, (pos, band, tile) in enumerate(blocks):
        n = len(pos)
        sl[k, :n] = src[pos] - band * src_band
        dl[k, :n] = dst[pos] - tile * dst_tile
        ww[k, :n] = w[pos]
        bandv[k] = band
        dt[k] = tile
        cnt[k] = n
        blk[pos] = k
        slot[pos] = np.arange(n)
    return PackedEdges(
        sl, dl, ww, bandv, dt, _first_touch_flags(dt), cnt, num_src, num_dst,
        edge_block=edge_block, src_band=src_band, dst_tile_rows=dst_tile,
        edge_block_id=blk, edge_slot=slot,
    )


def splice_pack_edge_blocks(
    src: np.ndarray,
    dst: np.ndarray,
    old_src: np.ndarray,
    old_dst: np.ndarray,
    old: PackedEdges,
    num_src: int,
    num_dst: int,
    edge_block: int = EDGE_BLOCK,
    src_band: int = SRC_BAND,
    dst_tile: int = DST_TILE,
) -> Optional[Tuple[PackedEdges, int, int]]:
    """Repack an edited edge stream by splicing the unchanged blocks of
    an existing packing around a freshly packed edit window.

    ``pack_edge_blocks`` is deterministic on the scheduled stream and
    local to its destination-tile runs: each maximal run of constant dst
    tile packs into its own consecutive blocks (grouped by band,
    ``edge_block`` chunks per group), whatever the rest of the stream
    holds.  Hence any prefix of the stream that (a) is unchanged and (b)
    ends on a tile-run boundary packs into exactly the same block rows,
    and likewise for a suffix that *starts* on a tile-run boundary — only
    the window between them needs the packer.  This function finds the
    longest common prefix/suffix of the old and new streams, snaps the
    window edges outward to tile-run starts (a tile-run start inside the
    common region is one of both streams, because the flag at position
    ``i`` only reads positions ``i-1`` and ``i``), packs the window, and
    concatenates.  The result is bitwise-equal to ``pack_edge_blocks``
    over the full new stream: per-block arrays are reused verbatim, the
    edge->(block, slot) map keeps the prefix's entries, offsets the
    window's by the prefix's blocks and the suffix's by the change in
    block count, and ``first_in_tile`` (first-touch-EVER semantics) is
    recomputed over the spliced block sequence — O(nb)/O(E) arithmetic,
    not a repack.

    Only unweighted packings are spliced (``old`` must have been built
    with ``weight=None``; a lazily materialized ones-mask on it is fine —
    it is ignored and the spliced packing starts lazy again).  Returns
    ``(packed, reused_blocks, total_blocks)``, or ``None`` when the old
    packing is not splice-compatible (different geometry, reference-packer
    dtype, or an empty stream) — callers fall back to a full repack.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    old_src = np.asarray(old_src, np.int64)
    old_dst = np.asarray(old_dst, np.int64)
    En, Eo = src.size, old_src.size
    if En == 0 or Eo == 0:
        return None
    if (old.edge_block != edge_block or old.src_band != src_band
            or old.dst_tile_rows != dst_tile
            or old.src_local.dtype != np.int16):
        return None

    # longest common prefix / suffix (clamped so they never overlap)
    m = min(En, Eo)
    eq = (src[:m] == old_src[:m]) & (dst[:m] == old_dst[:m])
    p = m if eq.all() else int(np.argmin(eq))
    eqs = (src[En - m:] == old_src[Eo - m:]) & (dst[En - m:] == old_dst[Eo - m:])
    rev = eqs[::-1]
    q = m if rev.all() else int(np.argmin(rev))
    if p + q > m:
        q = m - p

    # tile-run starts of the NEW stream; window edges snap to tile-run
    # starts strictly inside the common prefix (index <= p-1) / suffix
    # (index >= En-q+1), where old and new agree on the flag
    rs = np.flatnonzero(_tile_run_starts(dst // dst_tile))
    lo = int(rs[rs <= p - 1].max()) if p > 0 else 0
    hi_cand = rs[rs >= En - q + 1]
    hi = int(hi_cand.min()) if hi_cand.size else En
    hi_o = hi - En + Eo

    # a tile run's blocks hold exactly its edges, so the prefix's blocks
    # are those whose counts sum to lo, and the suffix's likewise
    cnt_o = old.count.astype(np.int64)
    starts_o = np.concatenate(([0], np.cumsum(cnt_o)[:-1]))
    n_pre = int(np.searchsorted(starts_o, lo))
    n_suf = int(np.searchsorted(starts_o, hi_o))
    blk_o, slot_o = old.edge_map()
    # anything else means the old packing did not come from
    # pack_edge_blocks on this stream
    if n_pre < starts_o.size and starts_o[n_pre] != lo:
        return None
    if n_suf < starts_o.size and starts_o[n_suf] != hi_o:
        return None
    if (lo and blk_o[:lo].max() >= n_pre) or (
            hi_o < Eo and blk_o[hi_o:].min() < n_suf):
        return None

    mid = pack_edge_blocks(
        src[lo:hi], dst[lo:hi], num_src, num_dst, weight=None,
        edge_block=edge_block, src_band=src_band, dst_tile=dst_tile)

    srcl = np.concatenate(
        [old.src_local[:n_pre], mid.src_local, old.src_local[n_suf:]])
    dstl = np.concatenate(
        [old.dst_local[:n_pre], mid.dst_local, old.dst_local[n_suf:]])
    bandv = np.concatenate([old.band[:n_pre], mid.band, old.band[n_suf:]])
    dt = np.concatenate(
        [old.dst_tile[:n_pre], mid.dst_tile, old.dst_tile[n_suf:]])
    cnt = np.concatenate([old.count[:n_pre], mid.count, old.count[n_suf:]])
    nb = int(cnt.shape[0])
    shift = n_pre + mid.num_blocks - n_suf  # suffix blocks move by this
    blk = np.concatenate(
        [blk_o[:lo], mid.edge_block_id + n_pre, blk_o[hi_o:] + shift])
    slot = np.concatenate([slot_o[:lo], mid.edge_slot, slot_o[hi_o:]])
    packed = PackedEdges(
        srcl, dstl, None, bandv, dt, _first_touch_flags(dt), cnt,
        num_src, num_dst,
        edge_block=edge_block, src_band=src_band, dst_tile_rows=dst_tile,
        edge_block_id=blk, edge_slot=slot,
    )
    reused = n_pre + (old.num_blocks - n_suf)
    return packed, reused, nb


def _na_kernel(
    band_ref, dtile_ref, first_ref,  # scalar-prefetch (SMEM)
    srcl_ref, dstl_ref, w_ref, h_ref,  # VMEM inputs; per-block rows are (1, EB)
    out_ref,  # VMEM output tile (TD, D)
    *, eb: int, band: int, td: int,
):
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    srcl = srcl_ref[0, :].astype(jnp.int32)  # host arrays are int16
    dstl = dstl_ref[0, :].astype(jnp.int32)
    w = w_ref[0, :]
    # HIGHEST: the one-hots are exact at any precision, but the MXU's
    # default f32 pass rounds the feature operand to bf16
    hi = jax.lax.Precision.HIGHEST
    sel = srcl[:, None] == jax.lax.broadcasted_iota(jnp.int32, (eb, band), 1)
    gathered = jnp.dot(sel.astype(jnp.float32), h_ref[...].astype(jnp.float32),
                       precision=hi)
    scat = jax.lax.broadcasted_iota(jnp.int32, (td, eb), 0) == dstl[None, :]
    contrib = jnp.dot(scat.astype(jnp.float32), gathered * w[:, None],
                      precision=hi)
    out_ref[...] += contrib.astype(out_ref.dtype)


def block_rows(x: jax.Array) -> jax.Array:
    """(nb, EB) per-block array -> the (nb, 1, EB) layout the kernels tile.

    Mosaic needs a block's last two dims to be multiples of (8, 128) or
    the whole array dims; a (1, EB) block of an (nb, EB) array is neither,
    while a (1, EB) block of (nb, 1, EB) is the whole trailing pair.
    """
    return x.reshape(x.shape[0], 1, x.shape[-1])


@functools.partial(
    jax.jit, static_argnames=("num_dst_tiles", "src_band", "dst_tile_rows", "interpret")
)
def _seg_sum_call(
    band, dst_tile, first, src_local, dst_local, weight, h,
    num_dst_tiles, src_band, dst_tile_rows, interpret,
):
    nb, eb = src_local.shape
    d = h.shape[1]
    row = pl.BlockSpec((None, 1, eb), lambda i, b, t, f: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[
            row, row, row,
            pl.BlockSpec((src_band, d), lambda i, b, t, f: (b[i], 0)),
        ],
        out_specs=pl.BlockSpec((dst_tile_rows, d), lambda i, b, t, f: (t[i], 0)),
    )
    kern = functools.partial(_na_kernel, eb=eb, band=src_band, td=dst_tile_rows)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_dst_tiles * dst_tile_rows, d), h.dtype),
        interpret=interpret,
        name="na_seg_sum",
    )(band, dst_tile, first, block_rows(src_local), block_rows(dst_local),
      block_rows(weight), h)


def _build_banded_matvec(packed: PackedEdges, interpret: bool,
                         weight_grad: bool):
    """``custom_vjp``-wrapped banded matvec for one packing.

    Forward is the Pallas kernel over the padded feature matrix; backward
    is a jnp gather/segment-add through the packing's cached flat edge map
    (``device_flat_edges``) — the transpose of the one-hot matmuls the
    kernel performs, with no host re-packing.  ``weight_grad=False`` skips
    the (E, D) weight-cotangent product for constant weights (the mean-NA
    path, whose ones-mask never needs a gradient).
    """
    num_dst_tiles = max(1, -(-packed.num_dst // packed.dst_tile_rows))
    band, dtile, first, srcl, dstl = packed.device_blocked()

    def primal(h_pad, w):
        return _seg_sum_call(
            band, dtile, first, srcl, dstl, w, h_pad,
            num_dst_tiles, packed.src_band, packed.dst_tile_rows, interpret,
        )

    @jax.custom_vjp
    def matvec(h_pad, w):
        return primal(h_pad, w)

    def fwd(h_pad, w):
        return primal(h_pad, w), (h_pad, w)

    def bwd(res, g):
        h_pad, w = res
        src_g, dst_g = packed.device_flat_edges()
        blk, slot = packed.device_edge_map()
        w_e = w[blk, slot]  # (E,) weights of the scheduled stream
        g_e = g[dst_g]  # (E, D) output cotangents gathered per edge
        grad_h = jnp.zeros_like(h_pad).at[src_g].add(
            (w_e[:, None] * g_e).astype(h_pad.dtype))
        if weight_grad:
            grad_w = jnp.zeros_like(w).at[blk, slot].add(
                jnp.sum(h_pad[src_g].astype(jnp.float32) * g_e, axis=1))
        else:
            grad_w = jnp.zeros_like(w)
        return grad_h, grad_w

    matvec.defvjp(fwd, bwd)
    return matvec


def banded_matvec_vjp(packed: PackedEdges, interpret: bool,
                      weight_grad: bool):
    """Memoized accessor for ``_build_banded_matvec`` — one function
    identity per (packing, interpret, weight_grad), so an outer ``jax.jit``
    train step retraces nothing when the same cached packing serves every
    step (grad-safe ``BandedBatch`` reuse)."""
    cache = getattr(packed, "_vjp_fns", None)
    if cache is None:
        cache = {}
        packed._vjp_fns = cache
    key = (interpret, weight_grad)
    fn = cache.get(key)
    if fn is None:
        fn = _build_banded_matvec(packed, interpret, weight_grad)
        cache[key] = fn
    return fn


def seg_sum_na(
    packed: PackedEdges,
    h: jax.Array,
    interpret: Optional[bool] = None,
    weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Weighted NA aggregation; returns (num_dst, D).  Differentiable in
    ``h`` and (when given) ``weights`` via the packing's custom VJP.

    ``weights`` optionally overrides ``packed.weight`` with an already
    device-resident (nb, EB) blocked array (see
    ``PackedEdges.scatter_blocks``) — the attention path feeds per-layer
    alpha this way without re-materializing host-side blocks; its
    cotangent flows back through the blocked layout.  ``interpret=None``
    runs the platform's kernel backend (``repro.kernels.backend``).
    """
    if interpret is None:
        interpret = use_interpret()
    band_units = int(packed.band.max()) + 1 if packed.num_blocks else 1
    n_src_pad = max(band_units * packed.src_band, packed.num_src)
    if h.shape[0] < n_src_pad:
        h = jnp.concatenate(
            [h, jnp.zeros((n_src_pad - h.shape[0], h.shape[1]), h.dtype)], axis=0
        )
    num_dst_tiles = max(1, -(-packed.num_dst // packed.dst_tile_rows))
    weight_grad = weights is not None
    w = jnp.asarray(packed.valid_weight()) if weights is None else jnp.asarray(weights)
    out = banded_matvec_vjp(packed, interpret, weight_grad)(h, w)
    # tiles never visited by any block hold uninitialized memory -> zero them
    touched = np.zeros(num_dst_tiles, bool)
    if packed.num_blocks:
        touched[np.asarray(packed.dst_tile)] = True
    if not touched.all():
        mask = jnp.asarray(
            np.repeat(touched, packed.dst_tile_rows)[: out.shape[0]]
        )
        out = jnp.where(mask[:, None], out, 0)
    return out[: packed.num_dst]


def seg_sum_blocks(
    band, dst_tile, first, src_local, dst_local, weight, h, *,
    num_dst_tiles: int, src_band: int = SRC_BAND,
    dst_tile_rows: int = DST_TILE, interpret: Optional[bool] = None,
) -> jax.Array:
    """Raw blocked-stream NA kernel entry over explicit block arrays.

    The sibling of :func:`seg_sum_na` for callers that own the block
    arrays instead of a ``PackedEdges`` — the sharded executor
    (``repro.distributed.hgnn``) slices per-device sub-streams out of a
    cached packing (``shard_blocked``), offsets bands/tiles into a
    concatenated multi-relation space, and feeds them here, possibly as
    traced operands inside ``shard_map``.  ``h`` must cover
    ``max(band) + 1`` bands of ``src_band`` rows; the output is
    ``(num_dst_tiles * dst_tile_rows, D)`` with rows of never-touched
    tiles holding uninitialized memory (callers mask, exactly like
    ``seg_sum_na``'s epilogue).
    """
    if interpret is None:
        interpret = use_interpret()
    return _seg_sum_call(band, dst_tile, first, src_local, dst_local,
                         weight, h, num_dst_tiles, src_band, dst_tile_rows,
                         interpret)
