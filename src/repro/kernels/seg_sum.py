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

Two formats, one kernel name.  A semantic graph that joins a large share
of its (dst, src) pairs leaves its edge blocks full, and each block still
builds a (EB, BAND) and a (TD, EB) one-hot for its 256 edges: about 82
kFLOP per edge where aggregating it takes 2·D.  Such a packing is instead
aggregated as dense adjacency tiles: one (TD, BAND) tile of summed static
edge weights (the count of each (dst, src) pair for the mean path) per
nonempty (dst tile, band) pair, ordered by dst tile so each output tile
stays resident, and a grid step per tile of ``out_tile += A_tile @
H_band`` at ``HIGHEST``.  That is the same f32 sum with exact zeros added;
only its order changes.  ``seg_sum_na`` takes the dense format when the
weights are static (``weights is None``: the packing's mask or host
weights) and the packing's pairs number at most 1/``DENSE_BLOCKS_PER_TILE``
of its blocks (``PackedEdges.dense_format``); traced blocked weights
and sparse packings stay on edge blocks, as do the raw block entries
(``seg_sum_blocks``, ``_seg_sum_call``).  The dense kernel's
``pallas_call`` keeps the name ``na_seg_sum``: a packing takes one format
or the other, so each aggregation is still one ``na_seg_sum`` call, and
what reads the device trace by kernel name (its roofline share, the
kernel stage of the forward) reads either format.

The attention path has an edge-block kernel of its own, also named
``na_seg_sum`` (``attention_seg_sum_call``): it reads the blocked logits
and the (TD, H) softmax stats columns of the block's tile that
``edge_softmax.attention_stats_call`` wrote, one per head, forms each
slot's alpha and weighs the scatter with it, so no per-edge weight array
is built outside the kernels.  One head runs ``_attn_na_kernel``, which
weighs the scatter one-hot's columns; H heads run
``_attn_na_heads_kernel``, which weighs each gathered column by the
alpha of its own head.

``seg_sum_na`` is differentiable: a ``jax.custom_vjp`` wraps the Pallas
call, and the backward pass is a gather through the same cached
edge -> (block, slot) map — ``grad_h[s] = sum_{e: src_e=s} w_e g[dst_e]``
and (for traced blocked weights) ``grad_w[b, k] = h[src] . g[dst]`` — composed in jnp over device-resident flat edge
indices derived once per packing.  No host re-packing happens on the
backward path, so a cached ``BandedBatch`` serves training steps as-is.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

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
# Static-weight aggregation takes the dense format when each dense tile
# replaces at least this many edge blocks.  On a TPU v5e an edge block
# step takes about 1.5 us whatever its fill and a dense tile step about
# 0.7 us, so time alone would go dense sooner; the factor bounds bytes:
# at 8 the tiles (256 KB each) hold at most about 11x the bytes of the
# block arrays they replace (3 KB per block).
DENSE_BLOCKS_PER_TILE = 8


@dataclasses.dataclass
class PackedEdges:
    """Banded edge-block format consumed by the kernel (host-built)."""

    src_local: np.ndarray  # (nb, EB) int: src - band*SRC_BAND (pad: w=0)
    dst_local: np.ndarray  # (nb, EB) int: dst - dst_tile*DST_TILE
    # (nb, EB) float32 edge weights, 0 for padding.  None = unweighted:
    # the kernels weigh by the ones-over-valid-slots mask, materialized
    # lazily by ``valid_mask()`` on first kernel use (packing a graph no
    # model ends up running never pays for it) and cached on the
    # instance, so the shared per-semantic-graph packing builds it at
    # most once.
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

    @property
    def num_dst_tiles(self) -> int:
        """Destination tiles of the kernels' output (at least 1)."""
        return max(1, -(-self.num_dst // self.dst_tile_rows))

    @property
    def num_bands(self) -> int:
        """Band units the blocks read from (1 for an empty packing)."""
        return int(self.band.max()) + 1 if self.num_blocks else 1

    def _pair_keys(self, tile: np.ndarray, band: np.ndarray) -> np.ndarray:
        """(dst tile, band) pairs as int64 keys, ordered by tile, then band."""
        return tile.astype(np.int64) * self.num_bands + band

    def dense_pairs(self) -> Tuple[np.ndarray, np.ndarray]:
        """(band, dst_tile) of each nonempty (dst tile, band) pair of the
        whole packing, ordered by dst tile, then band (memoized).  A tile
        the schedule revisits is one pair however many tile runs hold it."""
        dp = getattr(self, "_dense_pairs", None)
        if dp is None:
            key = np.unique(self._pair_keys(self.dst_tile, self.band))
            dp = ((key % self.num_bands).astype(np.int32),
                  (key // self.num_bands).astype(np.int32))
            self._dense_pairs = dp
        return dp

    @property
    def num_dense_tiles(self) -> int:
        """Dense tiles of the packing: its nonempty (dst tile, band) pairs."""
        return int(self.dense_pairs()[0].shape[0])

    @property
    def dense_format(self) -> bool:
        """Whether aggregation with static weights takes the dense-tile
        format: each tile replaces at least ``DENSE_BLOCKS_PER_TILE`` edge
        blocks."""
        return bool(self.num_blocks) and (
            self.num_dense_tiles * DENSE_BLOCKS_PER_TILE <= self.num_blocks)

    def dense_tiles(self) -> Tuple[np.ndarray, ...]:
        """(band, dst_tile, first, tiles) of the dense format (memoized):
        per nonempty pair in :meth:`dense_pairs` order, its band, its dst
        tile, 1 on the first pair of each dst tile, and its
        ``(dst_tile_rows, src_band)`` float32 tile whose entry [d, s] sums
        the static weights (``valid_weight``) of the edges from s to d,
        local indices.  One ``np.bincount`` over every slot of every block
        (padding slots weigh 0)."""
        dt = getattr(self, "_dense_tiles", None)
        if dt is None:
            band, tile = self.dense_pairs()
            pair = np.searchsorted(self._pair_keys(tile, band),
                                   self._pair_keys(self.dst_tile, self.band))
            flat = ((pair[:, None] * self.dst_tile_rows + self.dst_local)
                    * self.src_band + self.src_local)
            size = tile.shape[0] * self.dst_tile_rows * self.src_band
            tiles = np.bincount(flat.ravel(), weights=self.valid_weight().ravel(),
                                minlength=size).astype(np.float32)
            dt = (band, tile, _first_touch_flags(tile),
                  tiles.reshape(-1, self.dst_tile_rows, self.src_band))
            self._dense_tiles = dt
        return dt

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
        return self.valid_mask() if self.weight is None else self.weight

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
        return out.at[blk, slot].set(jnp.asarray(flat, jnp.float32),
                                     **IN_BOUNDS)

    def _device(self, attr: str, host):
        """The device copy cached under ``attr``, uploaded from ``host()``
        on first use.  ``ensure_compile_time_eval``: the first use may
        happen inside a jitted function's trace — the cached arrays must
        be concrete, not tracers, or they leak into later traces."""
        arr = getattr(self, attr, None)
        if arr is None:
            with jax.ensure_compile_time_eval():
                arr = jax.tree.map(jnp.asarray, host())
            setattr(self, attr, arr)
        return arr

    def device_edge_map(self) -> Tuple[jax.Array, jax.Array]:
        """Device-resident copy of ``edge_map()``, uploaded once and
        cached on the instance (the attention path scatters twice per
        layer per semantic graph — re-staging (E,) index constants every
        call would be a per-layer host round-trip)."""
        return self._device("_device_map", self.edge_map)

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
        return self._device("_device_flat_edges", self.flat_global_edges)

    def device_blocked(self) -> Tuple[jax.Array, ...]:
        """Device-resident copies of the static block arrays consumed by
        the NA kernel (band, dst_tile, first_in_tile, src_local,
        dst_local), uploaded once per packing.  The per-slot arrays are
        stored ``(nb, 1, EB)`` (:func:`block_rows`) and int32: a program
        that takes them as arguments in that shape and type hands them to
        the kernel as they are, where int16 ones are copied into another
        tiling on every call."""
        return self._device("_device_blocked", lambda: (
            self.band, self.dst_tile, self.first_in_tile,
            block_rows(self.src_local.astype(np.int32)),
            block_rows(self.dst_local.astype(np.int32))))

    def device_valid(self) -> jax.Array:
        """Device-resident ``valid_mask()``, stored ``(nb, 1, EB)``
        (uploaded once)."""
        return self._device("_device_valid", lambda: block_rows(self.valid_mask()))

    def device_weight(self) -> jax.Array:
        """Device-resident ``valid_weight()``, stored ``(nb, 1, EB)``: the
        valid mask itself for an unweighted packing (uploaded once)."""
        if self.weight is None:
            return self.device_valid()
        return self._device("_device_weight", lambda: block_rows(self.weight))

    def device_dense(self) -> Tuple[jax.Array, ...]:
        """Device-resident ``dense_tiles()`` (uploaded once)."""
        return self._device("_device_dense", self.dense_tiles)

    def device_arrays(self, dense: bool = True) -> Dict[str, object]:
        """Every device array the NA kernels read from this packing, as one
        pytree: the block arrays, the valid mask, the weights of a
        weighted packing and, unless ``dense`` is false, the dense tiles
        of a packing in the dense format.  No forward reads the edge map
        or the flat edges; a backward pass uploads them on first use.

        A jitted function that takes this pytree as an argument and
        :meth:`bind` s it runs on the packing without holding its arrays as
        constants of its program."""
        names = ["blocked", "valid"]
        if self.weight is not None:
            names.append("weight")
        if dense and self.dense_format:
            names.append("dense")
        return {n: getattr(self, _DEVICE[n][0])() for n in names}

    def bind(self, arrays: Dict[str, object]) -> "PackedEdges":
        """A view of this packing whose device arrays are ``arrays`` (a
        :meth:`device_arrays` pytree, or the tracers a jitted function was
        given for one).  The view shares the host arrays; it has its own
        caches, so a device array ``arrays`` leaves out is uploaded (as a
        constant) on first use, as on the packing itself."""
        view = dataclasses.replace(self)  # fields only: no cache is shared
        for name, arr in arrays.items():
            setattr(view, _DEVICE[name][1], arr)
        return view


# Indexing by index arrays known to lie in bounds (a packing's maps and
# permutations): no wrap of negative indices and no bounds handling.  Where
# the indices are arguments of a program rather than constants, the
# compiler cannot prove either needless, and plain ``x[idx]`` pays a pass
# over the indices for each.
IN_BOUNDS = {"mode": "promise_in_bounds", "wrap_negative_indices": False}


def gather_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` for ``idx`` known to lie in ``[0, len(x))``."""
    return x.at[idx].get(**IN_BOUNDS)


# the device_arrays() entries: name -> (accessor, instance cache)
_DEVICE = {"blocked": ("device_blocked", "_device_blocked"),
           "valid": ("device_valid", "_device_valid"),
           "weight": ("device_weight", "_device_weight"),
           "dense": ("device_dense", "_device_dense")}


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

    srcl = srcl_ref[0, :].astype(jnp.int32)  # int16 in the host packing
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


def _attn_na_kernel(
    band_ref, dtile_ref, first_ref,  # scalar-prefetch (SMEM)
    srcl_ref, dstl_ref, valid_ref, logit_ref,  # (1, EB) rows of block i
    m_ref, s_ref,  # (TD, 1) final softmax stats of block i's tile
    h_ref,  # (BAND, D) feature band
    out_ref,  # (TD, D) output tile
    *, eb: int, band: int, td: int,
):
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    srcl = srcl_ref[0, :]
    scat = jax.lax.broadcasted_iota(jnp.int32, (td, eb), 0) == dstl_ref[...]
    # m[dst], s[dst] per slot as masked sums over the one-hot (exact)
    m_e = jnp.sum(jnp.where(scat, m_ref[...], 0.0), axis=0, keepdims=True)
    s_e = jnp.sum(jnp.where(scat, s_ref[...], 0.0), axis=0, keepdims=True)
    alpha = jnp.where(valid_ref[...] > 0,
                      jnp.exp(logit_ref[...] - m_e) / jnp.maximum(s_e, 1e-9),
                      0.0)  # (1, EB)
    hi = jax.lax.Precision.HIGHEST
    sel = srcl[:, None] == jax.lax.broadcasted_iota(jnp.int32, (eb, band), 1)
    gathered = jnp.dot(sel.astype(jnp.float32), h_ref[...].astype(jnp.float32),
                       precision=hi)
    # alpha weights the scatter one-hot's columns: no row-to-column relayout
    contrib = jnp.dot(jnp.where(scat, alpha, 0.0), gathered, precision=hi)
    out_ref[...] += contrib.astype(out_ref.dtype)


def _attn_na_heads_kernel(
    band_ref, dtile_ref, first_ref,  # scalar-prefetch (SMEM)
    srcl_ref, dstl_ref, valid_ref,  # (1, EB) rows of block i
    logit_ref,  # (H, EB) logits of block i, a row per head
    m_ref, s_ref,  # (TD, H) final softmax stats of block i's tile
    h_ref,  # (BAND, D) feature band; head k owns columns k*D/H:(k+1)*D/H
    out_ref,  # (TD, D) output tile
    *, eb: int, band: int, td: int,
):
    """``_attn_na_kernel`` for H > 1 heads: each output column weighed by
    the alpha of its own head.  ``m[dst]``, ``s[dst]`` and each column's
    alpha are one-hot products on the MXU at ``HIGHEST`` (one term each,
    so exact in f32); the weighted rows then scatter as in the mean
    kernel."""
    i = pl.program_id(0)
    heads, d = m_ref.shape[1], out_ref.shape[1]

    @pl.when(first_ref[i] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    hi = jax.lax.Precision.HIGHEST
    tn = (((0,), (0,)), ((), ()))  # contract the leading dims: a^T @ b
    scat = (jax.lax.broadcasted_iota(jnp.int32, (td, eb), 0)
            == dstl_ref[...]).astype(jnp.float32)
    m_e = jax.lax.dot_general(m_ref[...], scat, tn, precision=hi)  # (H, EB)
    s_e = jax.lax.dot_general(s_ref[...], scat, tn, precision=hi)
    alpha = jnp.where(valid_ref[...] > 0,
                      jnp.exp(logit_ref[...] - m_e) / jnp.maximum(s_e, 1e-9),
                      0.0)  # (H, EB)
    own = (jax.lax.broadcasted_iota(jnp.int32, (heads, d), 1) // (d // heads)
           == jax.lax.broadcasted_iota(jnp.int32, (heads, d), 0))
    alpha_cols = jax.lax.dot_general(alpha, own.astype(jnp.float32), tn,
                                     precision=hi)  # (EB, D)
    srcl = srcl_ref[0, :]
    sel = srcl[:, None] == jax.lax.broadcasted_iota(jnp.int32, (eb, band), 1)
    gathered = jnp.dot(sel.astype(jnp.float32), h_ref[...].astype(jnp.float32),
                       precision=hi)
    contrib = jnp.dot(scat, gathered * alpha_cols, precision=hi)
    out_ref[...] += contrib.astype(out_ref.dtype)


def _dense_kernel(
    band_ref, dtile_ref, first_ref,  # scalar-prefetch (SMEM)
    a_ref, h_ref,  # VMEM inputs: (TD, BAND) weight tile, (BAND, D) band
    out_ref,  # VMEM output tile (TD, D)
):
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    # HIGHEST: the default f32 pass rounds both operands to bf16
    contrib = jnp.dot(a_ref[...], h_ref[...].astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    out_ref[...] += contrib.astype(out_ref.dtype)


def block_rows(x: jax.Array) -> jax.Array:
    """(nb, EB) per-block array -> the (nb, 1, EB) layout the kernels tile
    (an array already in it is returned as it is).

    Mosaic needs a block's last two dims to be multiples of (8, 128) or
    the whole array dims; a (1, EB) block of an (nb, EB) array is neither,
    while a (1, EB) block of (nb, 1, EB) is the whole trailing pair.  On a
    TPU the two layouts differ in memory, so the reshape of a device
    array is a copy: the packing stores its arrays in this one.
    """
    return x if x.ndim == 3 else x.reshape(x.shape[0], 1, x.shape[-1])


@functools.partial(
    jax.jit, static_argnames=("num_dst_tiles", "src_band", "dst_tile_rows", "interpret")
)
def _seg_sum_call(
    band, dst_tile, first, src_local, dst_local, weight, h,
    num_dst_tiles, src_band, dst_tile_rows, interpret,
):
    nb, eb = src_local.shape[0], src_local.shape[-1]
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


@functools.partial(jax.jit, static_argnames=("num_dst_tiles", "interpret"))
def _dense_call(band, dst_tile, first, tiles, h, num_dst_tiles, interpret):
    """The dense-tile format's kernel: one grid step per (dst tile, band)
    tile, in dst-tile order, ``out[tile] += tiles[i] @ h[band]``."""
    nt, td, src_band = tiles.shape
    d = h.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((None, td, src_band), lambda i, b, t, f: (i, 0, 0)),
            pl.BlockSpec((src_band, d), lambda i, b, t, f: (b[i], 0)),
        ],
        out_specs=pl.BlockSpec((td, d), lambda i, b, t, f: (t[i], 0)),
    )
    return pl.pallas_call(
        _dense_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_dst_tiles * td, d), h.dtype),
        interpret=interpret,
        name="na_seg_sum",
    )(band, dst_tile, first, tiles, h)


@functools.partial(
    jax.jit, static_argnames=("num_dst_tiles", "src_band", "dst_tile_rows", "interpret")
)
def attention_seg_sum_call(
    band, dst_tile, first, src_local, dst_local, valid, logits, m, s, h,
    num_dst_tiles, src_band, dst_tile_rows, interpret,
):
    """The attention path's aggregation kernel: ``out[d] += alpha_e
    h[src_e]`` over edge blocks, alpha formed per slot from the ``(nb, H,
    EB)`` blocked ``logits`` and the ``(rows, H)`` stats columns (m, s) of
    ``edge_softmax.attention_stats_call``; with H heads, head k's alpha
    weighs columns ``k*D/H`` to ``(k+1)*D/H``.  Rows of tiles no block
    touches hold uninitialized memory.  One head runs ``_attn_na_kernel``,
    more ``_attn_na_heads_kernel``."""
    nb, eb = src_local.shape[0], src_local.shape[-1]
    d = h.shape[1]
    td = dst_tile_rows
    heads = m.shape[1]
    row = pl.BlockSpec((None, 1, eb), lambda i, b, t, f: (i, 0, 0))
    col = pl.BlockSpec((td, heads), lambda i, b, t, f: (t[i], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[
            row, row, row,
            pl.BlockSpec((None, heads, eb), lambda i, b, t, f: (i, 0, 0)),
            col, col,
            pl.BlockSpec((src_band, d), lambda i, b, t, f: (b[i], 0)),
        ],
        out_specs=pl.BlockSpec((td, d), lambda i, b, t, f: (t[i], 0)),
    )
    body = _attn_na_kernel if heads == 1 else _attn_na_heads_kernel
    kern = functools.partial(body, eb=eb, band=src_band, td=td)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_dst_tiles * td, d), h.dtype),
        interpret=interpret,
        name="na_seg_sum",
    )(band, dst_tile, first, src_local, dst_local, valid, logits, m, s, h)


def _build_banded_matvec(packed: PackedEdges, interpret: bool,
                         weight_grad: bool):
    """``custom_vjp``-wrapped banded matvec for one packing.

    Forward is the Pallas kernel over the padded feature matrix, over
    dense tiles where the weights are static (``weight_grad=False``) and
    the packing takes the dense format, else over edge blocks; backward
    is a jnp gather/segment-add through the packing's cached flat edge map
    (``device_flat_edges``) — the transpose of the one-hot matmuls the
    kernel performs, with no host re-packing.  ``weight_grad=False`` skips
    the (E, D) weight-cotangent product for constant weights (the mean-NA
    path, whose ones-mask never needs a gradient).
    """
    num_dst_tiles = packed.num_dst_tiles
    if not weight_grad and packed.dense_format:
        dense = packed.device_dense()

        def primal(h_pad, w):
            return _dense_call(*dense, h_pad, num_dst_tiles, interpret)
    else:
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
        w2 = w.reshape(w.shape[0], -1)  # (nb, EB), however w is stored
        w_e = w2[blk, slot]  # (E,) weights of the scheduled stream
        g_e = g[dst_g]  # (E, D) output cotangents gathered per edge
        grad_h = jnp.zeros_like(h_pad).at[src_g].add(
            (w_e[:, None] * g_e).astype(h_pad.dtype))
        if weight_grad:
            grad_w = jnp.zeros_like(w2).at[blk, slot].add(
                jnp.sum(h_pad[src_g].astype(jnp.float32) * g_e, axis=1)
            ).reshape(w.shape)
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
    ``PackedEdges.scatter_blocks``), as the flat-logit reference of the
    attention kernels feeds alpha; its cotangent flows back through the
    blocked layout, and it always runs on edge blocks.  Without it the weights are static and a packing in
    the dense format (``PackedEdges.dense_format``) is aggregated as
    dense tiles.  ``interpret=None`` runs the platform's kernel backend
    (``repro.kernels.backend``).
    """
    if interpret is None:
        interpret = use_interpret()
    weight_grad = weights is not None
    w = packed.device_weight() if weights is None else jnp.asarray(weights)
    out = banded_matvec_vjp(packed, interpret, weight_grad)(src_rows(packed, h), w)
    return dst_rows(packed, out)


def src_rows(packed: PackedEdges, x: jax.Array) -> jax.Array:
    """``x`` (one row per source) with zero rows appended to cover every
    band a block of ``packed`` reads: the row count the kernels index."""
    n_src_pad = max(packed.num_bands * packed.src_band, packed.num_src)
    if x.shape[0] < n_src_pad:
        x = jnp.concatenate(
            [x, jnp.zeros((n_src_pad - x.shape[0],) + x.shape[1:], x.dtype)], axis=0
        )
    return x


def dst_rows(packed: PackedEdges, out: jax.Array) -> jax.Array:
    """The ``num_dst`` rows of a kernel's tile-padded output over
    ``packed``: tiles never visited by any block hold uninitialized
    memory, so they are zeroed."""
    touched = np.zeros(packed.num_dst_tiles, bool)
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
