"""Semantic Graph Build (SGB) stage: planners + executor + cost model.

Three planners:
  * ``plan_naive``   — the conventional scheme of §3.1: every target metapath
                       is built from scratch by left-folding one-hop relations.
  * ``plan_ctt``     — the paper's scheme: the CTT decomposes each target into
                       the longest previously-materialized segments; each new
                       semantic graph is stored back into the CTT.
  * ``plan_ctt_dp``  — beyond-paper: optimal segmentation by dynamic
                       programming over the materialized set, minimizing
                       *predicted* join work using cached edge counts
                       (the CTT's greedy longest-match is not always optimal).

A ``Plan`` is a list of composition steps (left, right, out); the executor
runs them through ``compose_relations`` and accounts exact MACs and bytes —
these counters are what benchmarks/ report as the paper's Figs. 14–15.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.ctt import CallbackTrieTree
from repro.hetero.graph import CompositionCost, HetGraph, Relation, compose_relations


@dataclasses.dataclass(frozen=True)
class PlanStep:
    left: str
    right: str
    out: str

    def __repr__(self) -> str:
        return f"{self.left} ∘ {self.right} -> {self.out}"


@dataclasses.dataclass
class Plan:
    """Ordered composition steps; ``targets`` are the requested metapaths."""

    steps: List[PlanStep]
    targets: List[str]
    kind: str  # "naive" | "ctt" | "ctt_dp"

    @property
    def num_compositions(self) -> int:
        return len(self.steps)


def _fold_name(segs: Sequence[str]) -> List[PlanStep]:
    """Left-fold segments (overlapping by one type) into composition steps."""
    steps = []
    acc = segs[0]
    for seg in segs[1:]:
        out = acc + seg[1:]
        steps.append(PlanStep(acc, seg, out))
        acc = out
    return steps


def plan_naive(graph: HetGraph, targets: Sequence[str]) -> Plan:
    """Conventional generation: each target re-built from one-hop relations.

    No reuse across targets — AP-PS-SP is recomputed for both APSPA and
    APSPP (the exact redundancy of §3.1).  Steps for already-built
    intermediates are intentionally repeated; the executor de-dupes nothing.
    """
    steps: List[PlanStep] = []
    for t in sorted(targets, key=lambda m: (len(m), m)):
        _check_valid(graph, t)
        if len(t) == 2:
            continue  # one-hop relations pre-exist
        hops = [t[i : i + 2] for i in range(len(t) - 1)]
        steps.extend(_fold_name(hops))
    return Plan(steps=steps, targets=list(targets), kind="naive")


def plan_ctt(
    graph: HetGraph,
    targets: Sequence[str],
    cache_intermediates: bool = False,
    preloaded: Sequence[str] = (),
) -> Plan:
    """CTT-guided generation (§4.2): reuse materialized semantic graphs.

    Targets are processed shortest-first (as the paper generates two-hop
    semantic graphs before longer ones, Fig. 6).  After each target is
    generated it is inserted into the CTT; with ``cache_intermediates`` the
    fold's intermediate products are inserted too (beyond-paper knob —
    trades CTT-buffer/HBM footprint for more reuse).

    ``preloaded`` seeds the CTT with already-materialized metapaths (the
    pipeline's semantic-graph cache): decomposition reuses them exactly as
    if an earlier target in this plan had produced them, so a warm cache
    shrinks the plan — possibly to zero steps.
    """
    ctt = CallbackTrieTree(graph.relation_names)
    steps: List[PlanStep] = []
    produced = set(graph.relation_names)
    for p in preloaded:
        ctt.insert(p)
        produced.add(p)
    for t in sorted(targets, key=lambda m: (len(m), m)):
        _check_valid(graph, t)
        segs = ctt.decompose(t)
        for st in _fold_name(segs) if len(segs) > 1 else []:
            if st.out in produced:
                continue  # already materialized by an earlier target
            steps.append(st)
            produced.add(st.out)
            if cache_intermediates:
                ctt.insert(st.out)
        ctt.insert(t)
        produced.add(t)
    return Plan(steps=steps, targets=list(targets), kind="ctt")


def plan_ctt_dp(
    graph: HetGraph,
    targets: Sequence[str],
    edge_counts: Optional[Dict[str, int]] = None,
    preloaded: Sequence[str] = (),
) -> Plan:
    """Beyond-paper: optimal segmentation via DP instead of greedy walk.

    For each target, choose the segmentation over the *currently
    materialized* set minimizing (#compositions, predicted join work).
    Prediction uses known edge counts when available (one-hop counts are
    always known; longer segments once produced get their true counts),
    falling back to #compositions.  Intermediates are always cached.
    ``preloaded`` seeds the materialized set (see :func:`plan_ctt`); pass
    their edge counts via ``edge_counts`` for accurate cost prediction.
    """
    ctt = CallbackTrieTree(graph.relation_names)
    known: Dict[str, int] = dict(edge_counts or {})
    for r in graph.relation_names:
        known.setdefault(r, graph.relation(r).num_edges)
    steps: List[PlanStep] = []
    produced = set(graph.relation_names)
    for p in preloaded:
        ctt.insert(p)
        produced.add(p)

    def seg_cost(seg: str) -> float:
        return float(known.get(seg, 10 * max(known.values())))

    for t in sorted(targets, key=lambda m: (len(m), m)):
        _check_valid(graph, t)
        n = len(t)
        # dp[i] = (num_segments, predicted_cost, segmentation) covering t[:i+1]
        INF = (1 << 30, float("inf"), [])
        dp: List[Tuple[int, float, List[str]]] = [INF] * n
        dp[0] = (0, 0.0, [])
        for i in range(n - 1):
            if dp[i][0] >= 1 << 30:
                continue
            for j in range(i + 2, n + 1):
                seg = t[i:j]
                if seg in ctt:
                    cand = (dp[i][0] + 1, dp[i][1] + seg_cost(seg), dp[i][2] + [seg])
                    if (cand[0], cand[1]) < (dp[j - 1][0], dp[j - 1][1]):
                        dp[j - 1] = cand
        segs = dp[n - 1][2]
        if not segs:
            raise KeyError(f"no segmentation for {t!r}")
        for st in _fold_name(segs) if len(segs) > 1 else []:
            if st.out in produced:
                continue
            steps.append(st)
            produced.add(st.out)
            ctt.insert(st.out)
        ctt.insert(t)
        produced.add(t)
    return Plan(steps=steps, targets=list(targets), kind="ctt_dp")


def _check_valid(graph: HetGraph, metapath: str) -> None:
    if not graph.metapath_is_valid(metapath):
        raise ValueError(f"metapath {metapath!r} invalid for dataset {graph.name}")


@dataclasses.dataclass
class SGBResult:
    graphs: Dict[str, Relation]  # every materialized metapath -> semantic graph
    cost: CompositionCost  # total MACs + bytes
    per_step: List[Tuple[PlanStep, CompositionCost]]
    backend: str = "host"
    device_stats: Optional[Dict[str, int]] = None  # tile-pruning counters

    def target_graphs(self, targets: Sequence[str]) -> Dict[str, Relation]:
        return {t: self.graphs[t] for t in targets}


class DeviceComposer:
    """PlanStep executor lowered onto the ``spgemm_bsr`` Pallas kernel.

    Relations live as tile-padded dense 0/1 matrices plus tile-occupancy
    bitmaps for the whole plan: one-hop inputs are densified lazily on
    first use, every intermediate stays padded on device, and step outputs
    are converted back to edge lists once, after the whole plan runs.  The
    MAC counter uses the exact join-pair formula (colsum_A · rowsum_B over
    the middle type), so device costs are bit-identical to the host
    sorted-merge join's — the two backends differ only in *where* the
    composition runs.

    ``kernel_backend``: None (the platform's kernel backend, see
    ``repro.kernels.backend``), "pallas" (TPU), "interpret" (kernel body
    in the Pallas interpreter), or "jnp" (dense oracle — fastest CPU
    validation path).
    """

    def __init__(
        self,
        graph: HetGraph,
        kernel_backend: Optional[str] = None,
        preloaded: Optional[Dict[str, Relation]] = None,
    ):
        if kernel_backend not in (None, "pallas", "interpret", "jnp"):
            raise ValueError(f"unknown kernel_backend {kernel_backend!r}")
        self.graph = graph
        self.kernel_backend = kernel_backend
        self._preloaded = dict(preloaded or {})
        # name -> (padded dense, occupancy, (rows, cols))
        self._mats: Dict[str, Tuple] = {}
        self.stats: Dict[str, int] = {
            "tile_pairs_total": 0, "tile_pairs_live": 0, "compositions": 0,
        }

    def _get(self, name: str):
        from repro.kernels.spgemm_bsr import pad_to_tiles, tile_occupancy

        if name not in self._mats:
            rel = self._preloaded.get(name) or self.graph.relation(name)
            padded = pad_to_tiles(rel.dense())
            self._mats[name] = (padded, tile_occupancy(padded),
                                (rel.num_src, rel.num_dst))
        return self._mats[name]

    def compose(self, step: PlanStep) -> CompositionCost:
        from repro.kernels import ops, ref

        a, ao, (m, k) = self._get(step.left)
        b, bo, (k2, n) = self._get(step.right)
        if k != k2:
            raise ValueError(f"middle-type cardinality mismatch in {step!r}")
        macs = ref.spgemm_macs_ref(a, b)
        out, occ, st = ops.compose_boolean_padded(
            a, b, ao, bo, backend=self.kernel_backend)
        self.stats["tile_pairs_total"] += st.get("tile_pairs_total", 0)
        self.stats["tile_pairs_live"] += st.get("tile_pairs_live", 0)
        self.stats["compositions"] += 1
        self._mats[step.out] = (out, occ, (m, n))
        # edge counts straight off the dense forms (padding is all-zero);
        # byte accounting matches Relation.nbytes (2 int32 per edge)
        left_edges = int(np.count_nonzero(a))
        right_edges = int(np.count_nonzero(b))
        out_edges = int(np.count_nonzero(out))
        return CompositionCost(
            macs=macs,
            bytes_read=(left_edges + right_edges) * 2 * 4,
            bytes_written=out_edges * 2 * 4,
        )

    def extract(self, name: str) -> Relation:
        """Materialized metapath -> canonical edge-list relation."""
        dense, _, (rows, cols) = self._mats[name]
        src_t, dst_t = name[0], name[-1]
        return Relation.from_dense(src_t, dst_t, dense[:rows, :cols])


def execute_plan(
    graph: HetGraph,
    plan: Plan,
    backend: str = "host",
    kernel_backend: Optional[str] = None,
    preloaded: Optional[Dict[str, Relation]] = None,
) -> SGBResult:
    """Run every composition step; count exact MACs/bytes.

    ``backend="host"`` joins edge lists with the numpy sorted-merge oracle;
    ``backend="device"`` lowers each step onto the block-sparse SpGEMM
    Pallas kernel (see :class:`DeviceComposer`).  Both produce
    edge-identical relations and identical MAC counts.

    ``preloaded`` supplies already-materialized semantic graphs (from the
    pipeline cache) that a cache-aware plan may reference as step inputs.

    The naive plan intentionally re-executes duplicated steps (that is the
    redundancy the CTT removes); materialized results are still keyed by
    name, so re-execution overwrites with an identical graph.
    """
    if backend not in ("host", "device"):
        raise ValueError(f"unknown backend {backend!r}")
    total = CompositionCost.zero()
    per_step: List[Tuple[PlanStep, CompositionCost]] = []
    mats: Dict[str, Relation] = dict(graph.relations)
    if preloaded:
        mats.update(preloaded)
    if backend == "device":
        composer = DeviceComposer(
            graph, kernel_backend=kernel_backend, preloaded=preloaded)
        for st in plan.steps:
            cost = composer.compose(st)
            total = total + cost
            per_step.append((st, cost))
        # unique outputs only: the naive plan duplicates steps by design
        for out_name in {st.out for st in plan.steps}:
            mats[out_name] = composer.extract(out_name)
        return SGBResult(
            graphs=mats,
            cost=total,
            per_step=per_step,
            backend="device",
            device_stats=dict(composer.stats),
        )
    for st in plan.steps:
        left, right = mats[st.left], mats[st.right]
        out, cost = compose_relations(left, right)
        mats[st.out] = out
        total = total + cost
        per_step.append((st, cost))
    return SGBResult(
        graphs=mats,
        cost=total,
        per_step=per_step,
        backend="host",
    )


def _with_shape(rel: Relation, num_src: int, num_dst: int) -> Relation:
    """Same edge set under (possibly grown) vertex counts.

    The canonical (src, dst) sort order is shape-independent, so the
    arrays carry over verbatim — no re-sort, no copy.
    """
    if (rel.num_src, rel.num_dst) == (num_src, num_dst):
        return rel
    return Relation(rel.src_type, rel.dst_type, num_src, num_dst,
                    rel.src, rel.dst)


def _rel_diff(new: Relation, old: Relation) -> Relation:
    """Edges of ``new`` absent from ``old`` (both canonical) — the Δ
    operand of the incremental composition identity."""
    old = _with_shape(old, new.num_src, new.num_dst)
    nk = new.src.astype(np.int64) * new.num_dst + new.dst.astype(np.int64)
    ok = old.src.astype(np.int64) * old.num_dst + old.dst.astype(np.int64)
    keep = ~np.isin(nk, ok, assume_unique=True)
    return Relation(new.src_type, new.dst_type, new.num_src, new.num_dst,
                    new.src[keep], new.dst[keep])


def _hops(metapath: str) -> set:
    return {metapath[i:i + 2] for i in range(len(metapath) - 1)}


def execute_plan_delta(
    graph: HetGraph,
    plan: Plan,
    old_products: Dict[str, Relation],
    removed_relations: frozenset,
    preloaded: Optional[Dict[str, Relation]] = None,
) -> SGBResult:
    """Run a plan over a delta-mutated graph, reusing prior products.

    For each step ``out = left ∘ right`` where the pre-delta product of
    ``out`` (and of both operands) is known, the boolean semiring's
    monotonicity gives the exact incremental identity

        out_new = out_old ∪ (Δleft ∘ right_new) ∪ (left_old ∘ Δright)

    with ``Δx = x_new \\ x_old`` — O(Δ·deg) join work instead of a full
    recompose.  The identity only holds insert-side: any step whose
    metapath crosses a relation with *removed* edges (``out_old`` may
    hold edges that no longer exist) falls back to a full composition, as
    does any step whose prior product was evicted.  Either way every
    output is built through ``Relation.from_edges``' canonical
    sort-and-dedup, so results are bitwise-equal to a from-scratch
    rebuild of the mutated graph.

    ``old_products`` maps names to their pre-delta relations (one-hop
    relations of the old graph plus cached semantic graphs under the old
    fingerprint); ``removed_relations`` names one-hop relations with edge
    removals.  Host backend only — the delta path is a cache-update
    optimization, and the cache is host-side.

    The returned ``SGBResult.device_stats`` reports
    ``incremental_steps`` / ``full_steps``.
    """
    total = CompositionCost.zero()
    per_step: List[Tuple[PlanStep, CompositionCost]] = []
    mats: Dict[str, Relation] = dict(graph.relations)
    if preloaded:
        mats.update(preloaded)
    deltas: Dict[str, Optional[Relation]] = {}

    def delta_of(name: str) -> Optional[Relation]:
        if name not in deltas:
            old = old_products.get(name)
            new = mats.get(name)
            deltas[name] = None if old is None or new is None else _rel_diff(
                new, old)
        return deltas[name]

    stats = {"incremental_steps": 0, "full_steps": 0}
    for st in plan.steps:
        left_new, right_new = mats[st.left], mats[st.right]
        old_out = old_products.get(st.out)
        incremental = (
            old_out is not None
            and not (_hops(st.out) & removed_relations)
            and delta_of(st.left) is not None
            and delta_of(st.right) is not None
        )
        if incremental:
            dl, dr = delta_of(st.left), delta_of(st.right)
            old_l = _with_shape(
                old_products[st.left], left_new.num_src, left_new.num_dst)
            p1, c1 = compose_relations(dl, right_new)
            p2, c2 = compose_relations(old_l, dr)
            old_out = _with_shape(
                old_out, left_new.num_src, right_new.num_dst)
            out = Relation.from_edges(
                old_out.src_type, old_out.dst_type,
                old_out.num_src, old_out.num_dst,
                np.concatenate([old_out.src, p1.src, p2.src]),
                np.concatenate([old_out.dst, p1.dst, p2.dst]))
            cost = CompositionCost(
                macs=c1.macs + c2.macs,
                bytes_read=c1.bytes_read + c2.bytes_read + old_out.nbytes,
                bytes_written=out.nbytes)
            stats["incremental_steps"] += 1
        else:
            out, cost = compose_relations(left_new, right_new)
            stats["full_steps"] += 1
        mats[st.out] = out
        total = total + cost
        per_step.append((st, cost))
    return SGBResult(
        graphs=mats,
        cost=total,
        per_step=per_step,
        backend="host+delta",
        device_stats=stats,
    )


def make_plan(
    graph: HetGraph,
    targets: Sequence[str],
    planner: str = "ctt",
    preloaded: Sequence[str] = (),
    edge_counts: Optional[Dict[str, int]] = None,
) -> Plan:
    """Dispatch to a planner by name. ``planner`` in {naive, ctt, ctt_cache,
    ctt_dp}; ``preloaded`` metapaths seed the CTT planners (cache reuse)."""
    if planner == "naive":
        return plan_naive(graph, targets)
    if planner == "ctt":
        return plan_ctt(graph, targets, preloaded=preloaded)
    if planner == "ctt_cache":
        return plan_ctt(graph, targets, cache_intermediates=True,
                        preloaded=preloaded)
    if planner == "ctt_dp":
        return plan_ctt_dp(graph, targets, edge_counts=edge_counts,
                           preloaded=preloaded)
    raise ValueError(f"unknown planner {planner!r}")


def build_semantic_graphs(
    graph: HetGraph,
    targets: Sequence[str],
    planner: str = "ctt",
    backend: str = "host",
    kernel_backend: Optional[str] = None,
) -> SGBResult:
    """One-call SGB stage: plan + execute. ``planner`` in {naive, ctt, ctt_dp}."""
    plan = make_plan(graph, targets, planner=planner)
    return execute_plan(graph, plan, backend=backend,
                        kernel_backend=kernel_backend)
