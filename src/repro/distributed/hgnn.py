"""Sharded multi-device HGNN execution over packed edge-block streams.

The restructured banded layout (kernels/seg_sum.py) gives the semantic
graphs a natural shard boundary: every edge block targets exactly one
dst tile, and per-destination state (attention softmax stats, the
first-touch zero-init) never crosses a tile.  A :class:`ShardPlan`
therefore assigns *whole dst tiles* of each semantic graph's block
stream to mesh devices:

* ``mode="relation"`` — HiHGNN-style inter-semantic-graph parallelism:
  every relation's stream stays whole and relations spread over devices
  by LPT greedy on edge counts.
* ``mode="edge_block"`` — relations whose edge count exceeds the mean
  per-device load additionally split along dst-tile boundaries (the
  same tile geometry ``splice_pack_edge_blocks`` preserves across
  deltas), so one oversized relation no longer serializes the mesh.

:class:`ShardedHGNNExecutor` runs the banded forward under one
``shard_map``: per device, every assigned block (across *all*
relations) executes as a single stats + seg-sum kernel pair per layer
over a concatenated feature space — each relation's banded src rows are
padded to a band boundary and its dst tiles offset into a shared tile
space, so the unmodified single-device kernels
(``kernels.seg_sum.seg_sum_blocks`` /
``kernels.edge_softmax.edge_softmax_stats_blocks``) consume the merged
stream directly.  Because a dst tile lives wholly on one device, each
device's NA output rows are exact (not partial) for the tiles it owns;
one ``psum`` over the mesh then materializes every relation's full NA
output on every device — the semantic-fusion all-gather point.  This
merged step is the executor's only NA code: it is the ``aggregate`` of
the model's one layer loop (``HGNN._layers``), which runs FP / SF / head
replicated, so logits match the single-device banded forward to fp
tolerance.

Wire-up lives in ``repro.api``: ``ExecutorSpec(shard=..., mesh_shape=...)``
declares the mode, ``Session.compile`` builds and caches the plan by
graph fingerprint, and ``HGNNServeEngine.register(device_group=...)``
pins tenants to disjoint device groups.  Everything here runs on CPU
hosts via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.hgnn.models import HGNN, BandedBatch, na_inputs, na_output
from repro.kernels.backend import use_interpret
from repro.kernels.edge_softmax import edge_softmax_stats_blocks
from repro.kernels.seg_sum import seg_sum_blocks, shard_blocked
from repro.launch.mesh import make_mesh_for

SHARD_MODES = ("relation", "edge_block")
_AXIS = "dev"
_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class ShardSlice:
    """One relation's edge blocks assigned to one mesh device.

    ``block_ids`` index the relation's packed stream, strictly ascending
    so the shard preserves the schedule's within-tile accumulation
    order.  Every dst tile's blocks land in exactly one slice (the plan
    invariant that keeps per-destination softmax and zero-init local to
    a device).
    """

    metapath: str
    device: int
    block_ids: np.ndarray
    num_edges: int


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Assignment of every packed edge block to a mesh device.

    Built once per (graph fingerprint, targets, mode, device count) by
    ``repro.api.Session.compile`` and shared by every model over the
    same products.  ``feature_dim`` scales the MAC estimate in
    :meth:`summary` (one multiply-add per edge per feature).
    """

    mode: str
    num_devices: int
    feature_dim: int
    slices: Tuple[ShardSlice, ...]

    def slices_for(self, device: int) -> List[ShardSlice]:
        """The device's slices, in deterministic metapath order."""
        return sorted((s for s in self.slices if s.device == device), key=lambda s: s.metapath)

    def device_block_counts(self) -> np.ndarray:
        """(num_devices,) edge blocks assigned per device."""
        out = np.zeros(self.num_devices, np.int64)
        for s in self.slices:
            out[s.device] += int(s.block_ids.size)
        return out

    def device_edge_counts(self) -> np.ndarray:
        """(num_devices,) edges assigned per device."""
        out = np.zeros(self.num_devices, np.int64)
        for s in self.slices:
            out[s.device] += s.num_edges
        return out

    def device_mac_counts(self) -> np.ndarray:
        """(num_devices,) NA multiply-adds per device (edges x features)."""
        return self.device_edge_counts() * int(self.feature_dim)

    def load_balance(self) -> float:
        """Max-over-mean per-device edge load (1.0 = perfectly balanced).

        The skew number the observability satellite reports: a ratio of
        2.0 means the slowest device carries twice the mean load, so the
        mesh runs at half its balanced throughput.
        """
        edges = self.device_edge_counts()
        total = int(edges.sum())
        if total == 0:
            return 1.0
        return float(edges.max() / (total / self.num_devices))

    def summary(self) -> Dict:
        """Per-device block/edge/MAC counts plus the load-balance ratio.

        Example::

            plan.summary()["load_balance"]  # max/mean device edge load
        """
        return {
            "mode": self.mode,
            "num_devices": self.num_devices,
            "per_device_edge_blocks": self.device_block_counts().tolist(),
            "per_device_edges": self.device_edge_counts().tolist(),
            "per_device_macs": self.device_mac_counts().tolist(),
            "load_balance": self.load_balance(),
        }


def build_shard_plan(
    graphs: Sequence[BandedBatch],
    num_devices: int,
    mode: str,
    feature_dim: int = 64,
) -> ShardPlan:
    """Assign every semantic graph's packed blocks to ``num_devices``.

    ``mode="relation"`` keeps each relation's stream whole;
    ``mode="edge_block"`` additionally splits relations whose edge count
    exceeds the mean per-device load into dst-tile groups.  Atoms (whole
    relations or tile groups) are placed by LPT greedy — heaviest atom
    onto the least-loaded device — which is deterministic and within
    4/3 of the optimal makespan.  Both modes keep every dst tile's
    blocks on one device; every block is assigned exactly once.
    """
    if mode not in SHARD_MODES:
        raise ValueError(f"shard mode {mode!r} not in {SHARD_MODES}")
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    atoms: List[Tuple[int, str, np.ndarray]] = []  # (edges, metapath, ids)
    total_edges = sum(int(g.packed.count.sum()) for g in graphs)
    split_above = total_edges / max(num_devices, 1)
    for g in graphs:
        p = g.packed
        if p.num_blocks == 0:
            continue
        edges = int(p.count.sum())
        ids_all = np.arange(p.num_blocks, dtype=np.int64)
        oversized = edges > split_above and p.num_blocks > 1
        if mode == "edge_block" and num_devices > 1 and oversized:
            tiles, inverse = np.unique(p.dst_tile, return_inverse=True)
            for t in range(tiles.size):
                ids = ids_all[inverse == t]
                atoms.append((int(p.count[ids].sum()), g.metapath, ids))
        else:
            atoms.append((edges, g.metapath, ids_all))
    order = sorted(range(len(atoms)), key=lambda i: (-atoms[i][0], atoms[i][1], i))
    load = np.zeros(num_devices, np.int64)
    assigned: Dict[Tuple[str, int], List[np.ndarray]] = {}
    for i in order:
        edges, metapath, ids = atoms[i]
        dev = int(np.argmin(load))  # ties resolve to the lowest device id
        load[dev] += edges
        assigned.setdefault((metapath, dev), []).append(ids)
    slices = []
    packed_by_mp = {g.metapath: g.packed for g in graphs}
    for (metapath, dev), id_lists in sorted(assigned.items()):
        ids = np.sort(np.concatenate(id_lists))
        num_edges = int(packed_by_mp[metapath].count[ids].sum())
        slices.append(ShardSlice(metapath=metapath, device=dev, block_ids=ids, num_edges=num_edges))
    return ShardPlan(
        mode=mode,
        num_devices=num_devices,
        feature_dim=int(feature_dim),
        slices=tuple(slices),
    )


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """Concatenated multi-relation band/tile space (host-side, static).

    Relation ``r``'s banded src rows live at band offset
    ``band_offsets[r]`` (in ``src_band`` units) of the merged feature
    matrix and its dst tiles at ``tile_offsets[r]`` of the merged
    output; one extra tile past ``total_tiles`` absorbs padding blocks.
    """

    band_offsets: Tuple[int, ...]
    seg_bands: Tuple[int, ...]
    tile_offsets: Tuple[int, ...]
    seg_tiles: Tuple[int, ...]
    total_bands: int
    total_tiles: int
    src_band: int
    dst_tile_rows: int
    edge_block: int


def _build_geometry(graphs: Sequence[BandedBatch]) -> _Geometry:
    """Lay every relation's bands and tiles out in one shared space."""
    if not graphs:
        raise ValueError("sharded execution needs at least one semantic graph")
    sb = graphs[0].packed.src_band
    td = graphs[0].packed.dst_tile_rows
    eb = graphs[0].packed.edge_block
    band_offsets, seg_bands, tile_offsets, seg_tiles = [], [], [], []
    b_off = t_off = 0
    for g in graphs:
        p = g.packed
        if (p.src_band, p.dst_tile_rows, p.edge_block) != (sb, td, eb):
            raise ValueError("all packings must share the block geometry")
        bands = int(p.band.max()) + 1 if p.num_blocks else 1
        bands = max(bands, -(-p.num_src // sb))
        tiles = max(1, -(-p.num_dst // td))
        band_offsets.append(b_off)
        seg_bands.append(bands)
        tile_offsets.append(t_off)
        seg_tiles.append(tiles)
        b_off += bands
        t_off += tiles
    return _Geometry(
        band_offsets=tuple(band_offsets),
        seg_bands=tuple(seg_bands),
        tile_offsets=tuple(tile_offsets),
        seg_tiles=tuple(seg_tiles),
        total_bands=b_off,
        total_tiles=t_off,
        src_band=sb,
        dst_tile_rows=td,
        edge_block=eb,
    )


def _empty_stream(eb: int) -> Dict[str, np.ndarray]:
    """A zero-block stream (a device the plan assigned nothing to)."""
    return {
        "band": np.zeros(0, np.int32),
        "dst_tile": np.zeros(0, np.int32),
        "first": np.zeros(0, np.int32),
        "src_local": np.zeros((0, eb), np.int16),
        "dst_local": np.zeros((0, eb), np.int16),
        "weight": np.zeros((0, eb), np.float32),
        "count": np.zeros(0, np.int32),
    }


def _stack_device_blocks(
    graphs: Sequence[BandedBatch],
    plan: ShardPlan,
    geom: _Geometry,
) -> Dict[str, np.ndarray]:
    """Per-device block streams, offset into the shared space and padded.

    Returns ``(ndev, nb_max, ...)`` stacked host arrays, the shard_map
    operands of the ``P("dev")`` specs.  Padding blocks target the extra
    garbage tile with ``first=1`` (each one re-zeros rows nothing reads)
    and carry zero weights / all-invalid slots, so they contribute
    nothing to real tiles or softmax stats.
    """
    sb, td, eb = geom.src_band, geom.dst_tile_rows, geom.edge_block
    by_mp = {g.metapath: (i, g) for i, g in enumerate(graphs)}
    per_dev: List[Dict[str, np.ndarray]] = []
    for dev in range(plan.num_devices):
        parts: List[Dict[str, np.ndarray]] = []
        for s in plan.slices_for(dev):
            r, g = by_mp[s.metapath]
            blk = shard_blocked(g.packed, s.block_ids)
            blk["band"] = blk["band"] + geom.band_offsets[r]
            blk["dst_tile"] = blk["dst_tile"] + geom.tile_offsets[r]
            parts.append(blk)
        if parts:
            stream = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        else:
            stream = _empty_stream(eb)
        per_dev.append(stream)
    nb_max = max(1, max(int(s["band"].shape[0]) for s in per_dev))
    stacked: Dict[str, List[np.ndarray]] = {}
    for stream in per_dev:
        nb = int(stream["band"].shape[0])
        pad = nb_max - nb
        full = {
            "band": np.concatenate([stream["band"], np.zeros(pad, np.int32)]),
            "dst_tile": np.concatenate(
                [stream["dst_tile"], np.full(pad, geom.total_tiles, np.int32)]
            ),
            "first": np.concatenate([stream["first"], np.ones(pad, np.int32)]),
            "src_local": np.concatenate(
                [stream["src_local"], np.zeros((pad, eb), stream["src_local"].dtype)]
            ),
            "dst_local": np.concatenate(
                [stream["dst_local"], np.zeros((pad, eb), stream["dst_local"].dtype)]
            ),
            "weight": np.concatenate([stream["weight"], np.zeros((pad, eb), np.float32)]),
            "count": np.concatenate([stream["count"], np.zeros(pad, np.int32)]),
        }
        # blocked global ids (int32: band * src_band overflows int16)
        full["src_id"] = full["band"][:, None] * sb + full["src_local"].astype(np.int32)
        full["dst_id"] = full["dst_tile"][:, None] * td + full["dst_local"].astype(np.int32)
        slot = np.arange(eb, dtype=np.int32)[None, :]
        full["valid"] = (slot < full["count"][:, None]).astype(np.float32)
        for k, v in full.items():
            stacked.setdefault(k, []).append(v)
    return {k: np.stack(v) for k, v in stacked.items()}


class ShardedHGNNExecutor:
    """``shard_map``-based banded forward bound to one :class:`ShardPlan`.

    Holds the per-device stacked block streams (host-built once) and a
    lazily-jitted forward whose body is the model's one layer loop
    (``HGNN._layers``) with this executor's NA step: the per-relation
    terms from ``core.hgnn.models.na_inputs``, one merged NA kernel pair
    over each device's stream, and one ``psum`` per layer that
    rematerializes full NA outputs (the SF all-gather point); FP, SF and
    the head run replicated.  ``traces`` counts jit traces — the serving
    no-retrace guard.
    """

    def __init__(
        self,
        model: HGNN,
        graphs: Sequence[BandedBatch],
        plan: ShardPlan,
        *,
        devices: Optional[Sequence] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        interpret: Optional[bool] = None,
    ):
        """Bind ``model`` + its banded batches to ``plan`` over a mesh.

        ``mesh`` must be 1-D with axis ``"dev"``; when absent one is
        made from ``devices`` (default: all of ``jax.devices()``,
        truncated to the plan's device count).  ``interpret=None`` runs
        the platform's kernel backend (``repro.kernels.backend``).
        """
        if mesh is None:
            devs = list(jax.devices()) if devices is None else list(devices)
            if len(devs) > plan.num_devices:
                devs = devs[: plan.num_devices]
            mesh = make_mesh_for(devs, (_AXIS,))
        if mesh.devices.size != plan.num_devices:
            raise ValueError(
                f"plan expects {plan.num_devices} devices, mesh has {mesh.devices.size}"
            )
        self.model = model
        self.graphs = list(graphs)
        self.plan = plan
        self.mesh = mesh
        self.interpret = use_interpret() if interpret is None else bool(interpret)
        self.geometry = _build_geometry(self.graphs)
        # each device's stream lives on that device, placed once
        self._blocks = jax.device_put(
            _stack_device_blocks(self.graphs, plan, self.geometry),
            NamedSharding(mesh, P(_AXIS)),
        )
        self._fn = None
        self._traces = 0
        self._lock = threading.Lock()

    @property
    def traces(self) -> int:
        """How many times the sharded forward has (re)traced."""
        return self._traces

    def forward(self, params: Dict, features: Dict[str, jax.Array]) -> jax.Array:
        """Logits for every target vertex, executed over the mesh.

        Matches ``HGNN.execute`` over the same banded batches on one
        device to fp tolerance; repeated calls reuse one jit trace.
        """
        if self._fn is None:
            with self._lock:
                if self._fn is None:
                    self._fn = self._build_forward()
        return self._fn(params, features, self._blocks)

    def lower(self, params, features):
        """The jitted forward lowered at ``(params, features)`` (arrays
        or ``jax.ShapeDtypeStruct``s); the forward must have run once."""
        return self._fn.lower(params, features, self._blocks)

    # ------------------------------------------------------------ builder --
    def _aggregate(self, blk, li, lp, hp):
        """One layer's NA outputs on this device's stream ``blk``, in
        graph order (the ``aggregate`` of ``HGNN._layers``).

        Each relation's banded features (and, for attention, its per-row
        logit terms) are padded into the shared band/tile space.  One
        seg-sum kernel serves every relation: with the packing weights
        for the mean, or with alpha, which the online stats kernel
        resolves in place over blocked logits gathered from the
        concatenated terms — exact per destination because every dst
        tile's edges are device-local.
        """
        geom, graphs, cfg = self.geometry, self.graphs, self.model.cfg
        sb, td = geom.src_band, geom.dst_tile_rows
        feat_segs, e_src_segs, e_dst_segs = [], [], []
        attention = False
        for r, g in enumerate(graphs):
            with obs.scope(obs.na_scope(li, g.metapath)):
                x = na_inputs(cfg, lp, g, hp)
                attention = x.attention
                hb = x.h_src[g.src_gather]
                row_pad = geom.seg_bands[r] * sb - hb.shape[0]
                feat_segs.append(jnp.pad(hb, ((0, row_pad), (0, 0))))
                if attention:
                    e_src_segs.append(jnp.pad(hb @ x.a_src, (0, row_pad)))
                    e_d = x.h_dst[g.dst_gather] @ x.a_dst
                    if x.bias is not None:
                        # the per-relation scalar bias folds into the
                        # dst-side term: dst rows are relation-exclusive
                        e_d = e_d + x.bias
                    e_dst_segs.append(
                        jnp.pad(e_d, (0, geom.seg_tiles[r] * td - e_d.shape[0])))
        # one stats + seg-sum kernel pair serves every relation
        with obs.scope(obs.na_scope(li, "+".join(g.metapath for g in graphs))):
            h_cat = jnp.concatenate(feat_segs, axis=0)
            w = blk["weight"]
            if attention:
                e_s = jnp.concatenate(e_src_segs)
                e_d = jnp.concatenate(e_dst_segs + [jnp.zeros((td,), jnp.float32)])
                lb = jax.nn.leaky_relu(e_s[blk["src_id"]] + e_d[blk["dst_id"]], 0.2)
                lb = jnp.where(blk["valid"] > 0, lb, _NEG)
                m, s = edge_softmax_stats_blocks(
                    blk["dst_tile"], blk["first"], lb, blk["dst_local"], blk["valid"],
                    num_dst_tiles=geom.total_tiles + 1, dst_tile_rows=td,
                    interpret=self.interpret)
                m_flat, s_flat = m.reshape(-1), s.reshape(-1)
                w = (jnp.exp(lb - m_flat[blk["dst_id"]])
                     / jnp.maximum(s_flat[blk["dst_id"]], 1e-9)) * blk["valid"]
            out = seg_sum_blocks(
                blk["band"], blk["dst_tile"], blk["first"], blk["src_local"],
                blk["dst_local"], w, h_cat, num_dst_tiles=geom.total_tiles + 1,
                src_band=sb, dst_tile_rows=td, interpret=self.interpret)
            # zero rows of tiles this device never touches (their owners
            # contribute them), then sum exact per-tile results across the
            # mesh: the semantic-fusion all-gather point
            touched = jnp.zeros((geom.total_tiles + 1,), jnp.float32)
            touched = touched.at[blk["dst_tile"]].max((blk["count"] > 0).astype(jnp.float32))
            rmask = jnp.repeat(touched[: geom.total_tiles] > 0, td)
            z_all = jnp.where(rmask[:, None], out[: geom.total_tiles * td], 0.0)
            z_all = jax.lax.psum(z_all, _AXIS)
        zs = []
        for r, g in enumerate(graphs):
            with obs.scope(obs.na_scope(li, g.metapath)):
                lo = geom.tile_offsets[r] * td
                zb = z_all[lo : lo + g.num_dst]
                if not attention:
                    zb = zb / jnp.maximum(g.deg, 1.0)[:, None]
                zs.append(na_output(cfg, zb[g.dst_scatter]))
        return zs

    def _build_forward(self):
        """Jit the shard_map'd layer loop (one trace, counted)."""
        model = self.model

        def body(params, features, blocks):
            blk = {k: v[0] for k, v in blocks.items()}  # this device's shard
            h = model._layers(params, model._inputs(features), self.graphs,
                              functools.partial(self._aggregate, blk))
            # replicated result; a broadcast leading axis satisfies the
            # check_vma=False requirement that out_specs mention the mesh
            # axis (the caller reads shard 0)
            return model._head(params, h)[None]

        sharded = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(), P(_AXIS)),
            out_specs=P(_AXIS),
            check_vma=False,
        )

        def fwd(params, features, blocks):
            self._traces += 1  # trace-time side effect: the retrace guard
            return sharded(params, features, blocks)[0]

        return jax.jit(fwd)
