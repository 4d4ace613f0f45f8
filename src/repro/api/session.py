"""Execution sessions: one compile-and-run surface for the whole stack.

A ``Session`` owns a ``FrontendPipeline`` + ``SemanticGraphCache``
configured from one ``ExecutorSpec`` and exposes a single entry point::

    sess = Session(ExecutorSpec(na_executor="banded"))
    compiled = sess.compile(graph, targets, HGNNConfig(model="rgat", ...))
    params = compiled.init(0)
    logits = compiled.forward(params, device_features(graph))

``compile`` runs the frontend (SGB -> Restructure -> packing, cache-served
where possible), builds the batch flavor the executor consumes — callers
never pick ``batches()`` vs ``banded_batches()`` again — and binds it to
the model in a ``CompiledHGNN`` whose ``init/forward/loss/fit/evaluate``
take no backend kwargs.  Frontend products and compiled models are
memoized on the session, so the multi-model scenario (rgcn + rgat + shgn
over one HetG) packs each semantic graph exactly once and every later
compile is pure reuse; ``session.stats()`` reports the cache hit-rates
that prove it.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.spec import ExecutorSpec
from repro.core.hgnn.models import (HGNN, HGNNConfig, bind_graphs,
                                    graph_arrays, static_na_weights)
from repro.core.subgraph import DependencyExtractor, DependencySubset
from repro.distributed.hgnn import (ShardedHGNNExecutor, ShardPlan,
                                    build_shard_plan)
from repro.hetero.delta import GraphDelta
from repro.hetero.graph import HetGraph
from repro.kernels.backend import use_interpret
from repro.pipeline.cache import SemanticGraphCache
from repro.pipeline.frontend import (DeltaResult, FrontendPipeline,
                                     FrontendResult)


def canonical_node_ids(node_ids, num_target: int, *,
                       ctx: str = "node_ids") -> "np.ndarray":
    """Validate target-vertex ids (integer dtype, 1-D, non-empty, within
    ``[0, num_target)``) and return them as a canonical int32 array.

    The one validator shared by ``CompiledHGNN.forward_subset`` and the
    serving engine's admission path (``ctx`` prefixes the error message,
    e.g. ``"request 3: nodes"``), so the two surfaces cannot drift.

    Example::

        ids = canonical_node_ids([4, 7], compiled.num_target)
    """
    arr = np.asarray(node_ids)
    if not np.issubdtype(arr.dtype, np.integer):
        raise TypeError(
            f"{ctx} must be an integer array, got dtype {arr.dtype}")
    if arr.ndim != 1 or arr.size == 0:
        raise ValueError(
            f"{ctx} must be a non-empty 1-D id array, got shape "
            f"{arr.shape}")
    lo, hi = int(arr.min()), int(arr.max())
    if lo < 0 or hi >= num_target:
        raise ValueError(
            f"{ctx}: id {lo if lo < 0 else hi} out of bounds "
            f"(valid range [0, {num_target}))")
    return arr.astype(np.int32, copy=False)


def _abstract(tree):
    """Shapes and dtypes of a pytree of arrays, for ``jit(...).lower``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x)), tree)


def device_features(graph: HetGraph) -> Dict[str, jax.Array]:
    """Upload a HetGraph's raw feature dict to device arrays (the form
    every compiled entry point takes).

    Example::

        feats = device_features(graph)          # {"P": (N_P, d_P), ...}
        logits = compiled.forward(params, feats)
    """
    return {t: jnp.asarray(x) for t, x in graph.features.items()}


def _changed_product_dsts(old_sem: Dict, new_sem: Dict,
                          touched: Sequence[str]) -> Dict[str, np.ndarray]:
    """Destination ids of added/removed product edges per touched metapath
    (the extractor-memo invalidation key: frontier expansion only indexes
    in-neighborhoods by destination, so the source side never matters)."""
    changed: Dict[str, np.ndarray] = {}
    for mp in touched:
        a, b = old_sem[mp], new_sem[mp]
        m = max(a.num_dst, b.num_dst)
        ka = a.src.astype(np.int64) * m + a.dst.astype(np.int64)
        kb = b.src.astype(np.int64) * m + b.dst.astype(np.int64)
        diff = np.setxor1d(ka, kb, assume_unique=True)
        changed[mp] = np.unique(diff % m)
    return changed


@dataclasses.dataclass(frozen=True)
class SessionStats:
    """One snapshot of everything a session reuses.

    ``frontend_runs`` counts pipeline passes that actually executed;
    ``frontend_served`` counts compile/frontend requests answered from the
    session's own memo without touching the pipeline at all.  The cache
    counters are cumulative for the session's ``SemanticGraphCache``
    (which may be shared with other sessions — sharing is the point).

    ``shard`` is ``None`` on unsharded sessions; on sharded ones it
    aggregates every cached plan's device loads —
    ``stats()["shard"]["load_balance"]`` is the max-over-mean per-device
    edge load across the session (1.0 = perfectly balanced).
    """

    compiles: int
    compiles_cached: int
    frontend_runs: int
    frontend_served: int
    cache_hits: int
    cache_misses: int
    cache_evictions: int
    cache_entries: int
    cache_nbytes: int
    shard: Optional[Dict] = None

    @property
    def hit_rate(self) -> float:
        """Cache hits over total lookups (e.g. ``stats.hit_rate > 0.3``)."""
        return self.cache_hits / max(1, self.cache_hits + self.cache_misses)

    def __getitem__(self, key: str):
        """Dict-style field access (``stats()["shard"]``)."""
        if key.startswith("_") or not hasattr(self, key):
            raise KeyError(key)
        return getattr(self, key)


class CompiledHGNN:
    """A model bound to its frontend products and executor — no knobs left.

    Holds the ``HGNN`` + the correct batch flavor for the session's
    ``ExecutorSpec`` (``SemanticGraphBatch`` for jnp, ``BandedBatch`` over
    the cached ``PackedEdges`` for banded) and exposes the full model
    lifecycle.  ``forward``/``forward_subset``/``loss`` are jitted once
    and take the batches' device arrays as an argument (one pytree, built
    once: ``models.graph_arrays``), so the graph is data of the compiled
    programs rather than constants baked into them, and repeated calls —
    the serving scenario — never retrace.

    ``timings["forward_compile"]`` is the host seconds of the forward's
    first call: tracing, compiling (or loading from the persistent compile
    cache) and dispatching it.
    """

    def __init__(self, session: "Session", spec: ExecutorSpec, model: HGNN,
                 frontend: FrontendResult, graphs: List, fingerprint: str,
                 shard_plan: Optional[ShardPlan] = None,
                 devices: Optional[List] = None):
        self.session = session
        self.spec = spec
        self.model = model
        self.frontend = frontend
        self.graphs = graphs
        self.fingerprint = fingerprint
        # multi-device execution (spec.shard != "none"): the plan is built
        # eagerly by Session.compile (cached per fingerprint); the
        # shard_map executor traces lazily on first forward
        self.shard_plan = shard_plan
        self._devices = devices
        self._shard_exec: Optional[ShardedHGNNExecutor] = None
        self._forward = None
        self._forward_subset = None
        self._subset_traces = 0
        self._forward_dep = None
        self._dependency_traces = 0
        # the CompiledHGNN whose jitted dependency executor (and trace
        # counter) this one uses; compile_delta transplants the executor
        # across graph deltas, so chained swaps all point at the original
        self._dep_origin: "CompiledHGNN" = self
        self._extractor: Optional[DependencyExtractor] = None
        # frozen SF betas per (params, features) object pair — the
        # dependency path's calibration artifacts (strong refs keep the
        # id()-based keys valid for the life of each entry)
        self._beta_fn = None
        self._beta_memo: "OrderedDict[Tuple[int, int], Tuple]" = OrderedDict()
        # guards every lazy jit build: two threads racing the first call
        # must not each build (and trace) their own jitted function, or
        # compile work doubles and the no-retrace compile-count guard
        # (subset_traces) breaks
        self._build_lock = threading.Lock()
        self._loss = None
        self._accuracy = None
        # the graphs' device arrays, the last argument of the jitted entries
        self._graph_arrays = None
        # abstract arguments of the first forward call, for
        # forward_executable(); recorded once, when the forward is built
        self._forward_shapes = None
        self.timings: Dict[str, float] = {}
        obs.register(self)

    # ------------------------------------------------------- conveniences --
    @property
    def cfg(self) -> HGNNConfig:
        """The bound model's ``HGNNConfig`` (e.g. ``compiled.cfg.model``)."""
        return self.model.cfg

    @property
    def semantic(self) -> Dict:
        """The frontend's semantic graphs (label builders consume these)."""
        return self.frontend.semantic

    @property
    def num_target(self) -> int:
        """Vertex count of the classification target type."""
        return self.model.num_vertices[self.cfg.target_type]

    # ---------------------------------------------------------- lifecycle --
    def init(self, key: "jax.Array | int" = 0) -> Dict:
        """Parameter pytree; accepts a PRNG key or a plain int seed."""
        if isinstance(key, int):
            key = jax.random.key(key)
        return self.model.init(key)

    def forward(self, params, features) -> jax.Array:
        """Logits for every ``cfg.target_type`` vertex (jitted, no kwargs).

        Example::

            logits = compiled.forward(params, device_features(graph))
            assert logits.shape == (compiled.num_target, cfg.num_classes)
        """
        built = False
        if self.shard_plan is not None:
            if self._shard_exec is None:
                with self._build_lock:
                    if self._shard_exec is None:
                        self._shard_exec = ShardedHGNNExecutor(
                            self.model, self.graphs, self.shard_plan,
                            devices=self._devices,
                            interpret=use_interpret(
                                self.spec.kernel_backend))
                        self._forward_shapes = _abstract((params, features))
                        built = True
            call, args = self._shard_exec.forward, (params, features)
        else:
            if self._forward is None:
                with self._build_lock:
                    if self._forward is None:
                        self._forward = self._jit_on_graph(self.model.execute)
                        self._forward_shapes = _abstract(
                            (params, features, self._graph()))
                        built = True
            call, args = self._forward, (params, features, self._graph())
        t = time.perf_counter()
        out = call(*args)
        if built:
            self.timings["forward_compile"] = time.perf_counter() - t
        return out

    def _graph(self) -> List[Dict]:
        """The bound graphs' device arrays as one pytree (built once)."""
        if self._graph_arrays is None:
            self._graph_arrays = graph_arrays(
                self.graphs, dense=static_na_weights(self.cfg.model))
        return self._graph_arrays

    def _jit_on_graph(self, method):
        """``jax.jit`` of the model's ``method(p, f, graphs, *rest,
        kernel_backend=...)`` as a function of ``(p, f, graph, *rest)``:
        ``graph`` is :meth:`_graph`'s pytree, bound to the batches inside
        the trace, so the graph's arrays are arguments of the compiled
        program, not constants of it."""
        kernel_backend = self.spec.kernel_backend

        def fn(p, f, graph, *rest):
            return method(p, f, bind_graphs(self.graphs, graph), *rest,
                          kernel_backend=kernel_backend)

        return jax.jit(fn)

    @property
    def forward_built(self) -> bool:
        """Whether :meth:`forward` has been called (and so jitted)."""
        return self._forward_shapes is not None

    def forward_lowered(self):
        """:meth:`forward` lowered at the argument shapes of its first
        call (``jax.stages.Lowered``), or None before that call.

        Example::

            compiled.forward(params, feats)
            text = compiled.forward_lowered().as_text()  # StableHLO
        """
        if self._forward_shapes is None:
            return None
        fn = self._shard_exec if self._shard_exec is not None else self._forward
        return fn.lower(*self._forward_shapes)

    def forward_executable(self):
        """The compiled program of :meth:`forward` at the argument shapes
        of its first call, or None before that call.  It compiles again
        (the persistent compile cache serves it), so it is for
        inspection after the fact: ``repro.obs.forward_scopes`` reads its
        HLO.

        Example::

            compiled.forward(params, feats)
            text = compiled.forward_executable().as_text()
        """
        lowered = self.forward_lowered()
        return None if lowered is None else lowered.compile()

    def packing_counts(self) -> Dict[str, Dict]:
        """Per metapath, the banded packing's ``edges``, ``blocks``,
        ``slots`` (blocks x edges per block) and ``fill`` (edges over
        slots) of its edge blocks, and the ``dense_tiles`` and
        ``dense_edges`` the forward aggregates as dense tiles (both 0
        where it steps over the edge blocks: the attention models' traced
        weights, a sharded forward, a sparse packing); empty on the jnp
        executor.

        Example::

            c = compiled.packing_counts()["MAM"]
            fill = c["edges"] / c["slots"]
        """
        # the sharded forward always steps over the edge blocks
        static = static_na_weights(self.cfg.model) and self.shard_plan is None
        out = {}
        for g in self.graphs:
            pk = getattr(g, "packed", None)
            if pk is not None:
                dense = static and pk.dense_format
                out[g.metapath] = {"edges": pk.num_edges,
                                   "blocks": pk.num_blocks,
                                   "slots": pk.num_slots, "fill": pk.fill,
                                   "dense_tiles": pk.num_dense_tiles if dense else 0,
                                   "dense_edges": pk.num_edges if dense else 0}
        return out

    @property
    def subset_traces(self) -> int:
        """How many times :meth:`forward_subset` has (re)traced — stable
        across resubmissions that land in the same id bucket, so callers
        (and tests) can assert the serving hot path never recompiles::

            before = compiled.subset_traces
            compiled.forward_subset(params, feats, ids_a)
            compiled.forward_subset(params, feats, ids_b)  # same bucket
            assert compiled.subset_traces == before + 1
        """
        return self._subset_traces

    @property
    def shard_traces(self) -> int:
        """How many times the sharded (``shard_map``) forward has traced —
        the multi-device sibling of :attr:`subset_traces`: repeated
        ``forward`` calls on a sharded compile must report 1."""
        return self._shard_exec.traces if self._shard_exec is not None else 0

    @property
    def dependency_traces(self) -> int:
        """How many times the dependency-subset forward has (re)traced —
        stable across requests whose closures share a bucket signature
        (see ``DependencySubset.signature``), the dependency-mode sibling
        of :attr:`subset_traces`.  After a graph delta
        (``Session.compile_delta``) the counter is shared with the
        pre-delta compiled object: the dependency executor reads topology
        only through its traced ``DependencySubset`` pytree, so the swap
        transplants the jitted function — and an unchanged bucket
        signature provably costs zero new traces."""
        return self._dep_origin._dependency_traces

    def dependency_subset(self, node_ids, *, bucket_min: int = 8,
                          validate: bool = True) -> DependencySubset:
        """The k-hop dependency closure for an id set (memoized).

        Runs the host-side extractor (``core.subgraph``) over the
        frontend's cached semantic graphs — ``cfg.num_layers`` hops
        backward from the requested target ids — and returns the
        device-ready ``DependencySubset``.  Resubmissions of the same id
        set (any order, duplicates allowed) return the identical object;
        the serving engine reads ``.coverage`` off it to decide
        dependency-vs-full before paying for execution.

        Example::

            sub = compiled.dependency_subset(np.array([4, 7]))
            assert sub.coverage <= 1.0
        """
        if validate:
            node_ids = canonical_node_ids(node_ids, self.num_target)
        if self._extractor is None:
            with self._build_lock:
                if self._extractor is None:
                    self._extractor = DependencyExtractor(
                        self.model, self.graphs, self.frontend.semantic,
                        flavor=self.spec.na_executor)
        return self._extractor.extract(node_ids, bucket_min=bucket_min)

    def _fusion_betas(self, params, features):
        """Frozen SF betas for (params, features), memoized by object
        identity (strong refs pin the keys); serving recalibrates when
        ``swap_params`` installs a new params object."""
        key = (id(params), id(features))
        ent = self._beta_memo.get(key)
        if ent is not None and ent[0] is params and ent[1] is features:
            self._beta_memo.move_to_end(key)
            return ent[2]
        if self._beta_fn is None:
            with self._build_lock:
                if self._beta_fn is None:
                    self._beta_fn = self._jit_on_graph(self.model.fusion_betas)
        betas = self._beta_fn(params, features, self._graph())
        self._beta_memo[key] = (params, features, betas)
        while len(self._beta_memo) > 4:
            self._beta_memo.popitem(last=False)
        return betas

    def forward_subset(self, params, features, node_ids,
                       *, bucket_min: int = 8,
                       validate: bool = True,
                       mode: str = "head") -> jax.Array:
        """Logits for an explicit subset of target vertices (jitted).

        ``mode="head"`` (default): message passing still runs full-graph
        — a vertex's logits depend on its whole receptive field — but
        only the requested rows of the final hidden state are gathered
        through the classifier head, so a micro-batch of node-subset
        requests skips the full-head matmul and the full-logits
        device->host transfer.  Row ``i`` of the result is bitwise-equal
        to row ``node_ids[i]`` of :meth:`forward` under the same trace.

        ``mode="dependency"``: message passing itself runs over the ids'
        k-hop dependency closure (:meth:`dependency_subset`) — the
        vertex-centric executor, whose compute and peak live arrays are
        bounded by the receptive field, not the graph.  Rows match
        :meth:`forward` to reassociation tolerance; semantic-fusion betas
        are frozen from one full calibration forward per
        (params, features) pair (they are graph-level statistics — see
        ``HGNN.fusion_betas``), which serving pays at registration /
        parameter swap, never per request.

        ``node_ids`` (and, in dependency mode, every closure/edge array)
        is padded to power-of-two buckets (at least ``bucket_min``)
        before entering the jitted function, so repeated calls with
        different ids — the serving engine's resubmission pattern — only
        retrace when a bucket grows, never per request (see
        :attr:`subset_traces` / :attr:`dependency_traces`).

        ``validate=False`` skips the id re-validation for callers that
        already canonicalized through ``canonical_node_ids`` (the serving
        engine validates at admission; re-scanning the union inside the
        timed serving window would pay the cost twice).

        Example::

            rows = compiled.forward_subset(params, feats, np.array([4, 7]))
            assert rows.shape == (2, cfg.num_classes)
        """
        if mode not in ("head", "dependency"):
            raise ValueError(f"unknown forward_subset mode {mode!r} "
                             "(expected 'head' or 'dependency')")
        if validate:
            ids = canonical_node_ids(node_ids, self.num_target)
        else:
            ids = np.asarray(node_ids)
        if mode == "dependency":
            return self._forward_dependency(params, features, ids,
                                            bucket_min=bucket_min)
        if self._forward_subset is None:
            with self._build_lock:
                if self._forward_subset is None:

                    def execute_subset(*args, **kw):
                        # traced once per bucket shape; the counter
                        # increments at trace time only, which is what the
                        # no-retrace guard (subset_traces) observes
                        self._subset_traces += 1
                        return self.model.execute_subset(*args, **kw)

                    self._forward_subset = self._jit_on_graph(execute_subset)
        n = int(ids.shape[0])
        bucket = max(int(bucket_min), 1 << max(0, n - 1).bit_length())
        padded = np.zeros((bucket,), np.int32)
        padded[:n] = ids
        out = self._forward_subset(params, features, self._graph(),
                                   jnp.asarray(padded))
        return out[:n]

    def _forward_dependency(self, params, features, ids,
                            *, bucket_min: int = 8) -> jax.Array:
        """The dependency-mode body of :meth:`forward_subset`: extract
        (memoized), calibrate betas (memoized), run the one jitted
        dependency executor, and restore the caller's id order."""
        sub = self.dependency_subset(ids, bucket_min=bucket_min,
                                     validate=False)
        betas = self._fusion_betas(params, features)
        if self._forward_dep is None:
            with self._build_lock:
                if self._forward_dep is None:
                    kernel_backend = self.spec.kernel_backend

                    def fwd_dep(p, f, b, dep):
                        # traced once per bucket signature; the counter
                        # increments at trace time only (the dependency
                        # no-retrace guard observes it)
                        self._dependency_traces += 1
                        return self.model.execute_dependency_subset(
                            p, f, self.graphs, dep, b,
                            kernel_backend=kernel_backend)

                    self._forward_dep = jax.jit(fwd_dep)
        out = self._forward_dep(params, features, betas, sub.arrays)
        out = out[: sub.num_ids]
        ids_arr = np.asarray(ids)
        if (ids_arr.size == sub.num_ids
                and np.array_equal(ids_arr, sub.node_ids)):
            return out  # already sorted-unique (the serving union path)
        return out[jnp.asarray(np.searchsorted(sub.node_ids, ids_arr))]

    def loss(self, params, features, labels, mask=None) -> jax.Array:
        """Masked cross-entropy on the target type (jitted).  ``mask=None``
        means every vertex counts (an all-ones mask keeps the trace
        shape-static across masked and unmasked calls)."""
        if self._loss is None:
            with self._build_lock:
                if self._loss is None:
                    self._loss = self._jit_on_graph(self.model.execute_loss)
        if mask is None:
            mask = jnp.ones((self.num_target,), jnp.float32)
        return self._loss(params, features, self._graph(), labels, mask)

    def evaluate(self, params, features, labels, mask=None) -> jax.Array:
        """Masked accuracy on the target type (jitted; delegates to the
        train substrate's eval fn so the compiled and training paths share
        one accuracy definition)."""
        if self._accuracy is None:
            with self._build_lock:
                if self._accuracy is None:
                    from repro.train.hgnn_step import make_eval_fn

                    self._accuracy = make_eval_fn(
                        self.model, self.graphs,
                        kernel_backend=self.spec.kernel_backend)
        if mask is None:
            mask = jnp.ones((self.num_target,), jnp.float32)
        return self._accuracy(params, features, labels, mask)

    def fit(self, features, labels, masks, *, epochs: int = 100,
            seed: int = 0, lr: float = 3e-3, weight_decay: float = 0.0,
            epoch_callback=None, ckpt_dir: Optional[str] = None,
            ckpt_every: int = 1) -> Dict:
        """Full-graph semi-supervised training on the bound executor
        (delegates to ``train.hgnn_step.fit`` — jitted AdamW step, custom
        VJPs on the banded path — with the spec threaded through).

        ``ckpt_dir`` turns on atomic train-state checkpointing every
        ``ckpt_every`` epochs (``train.checkpoint.CheckpointManager``); a
        re-run over the same directory resumes from the latest complete
        checkpoint instead of epoch 0 — crash-mid-save leaves no
        restorable garbage.

        Example::

            out = compiled.fit(feats, labels, masks, epochs=50,
                               ckpt_dir="/ckpts/acm", ckpt_every=10)
        """
        from repro.train.hgnn_step import fit as _fit

        return _fit(self.model, self.graphs, features, labels, masks,
                    epochs=epochs, seed=seed, lr=lr,
                    weight_decay=weight_decay,
                    kernel_backend=self.spec.kernel_backend,
                    epoch_callback=epoch_callback, ckpt_dir=ckpt_dir,
                    ckpt_every=ckpt_every)


class Session:
    """One compile-and-run surface over one spec + one shared cache.

    Pass an existing ``SemanticGraphCache`` to share frontend products
    across sessions (e.g. a jnp session and a banded session over the same
    datasets reuse each other's semantic graphs and restructure results —
    the two-executor benchmarks do exactly this).

    ``max_memo`` bounds the session's own frontend/compile memos (LRU,
    like the underlying cache's ``max_entries``).  The default pins
    everything for the session's lifetime — right for serving a fixed
    tenant set; bound it for tenant-churn workloads so evicted cache
    entries are actually freed.  Eviction only drops the session's pin:
    already-returned ``CompiledHGNN`` objects keep working.
    """

    def __init__(self, spec: Optional[ExecutorSpec] = None,
                 cache: Optional[SemanticGraphCache] = None,
                 max_memo: Optional[int] = None):
        self.spec = spec or ExecutorSpec()
        self.cache = cache if cache is not None else SemanticGraphCache()
        self.max_memo = max_memo
        self.pipeline = FrontendPipeline(self.spec.pipeline_config(),
                                         cache=self.cache)
        self._frontends: "OrderedDict[Tuple[str, Tuple[str, ...]], FrontendResult]" = OrderedDict()
        self._compiled: "OrderedDict[Tuple, CompiledHGNN]" = OrderedDict()
        self._shard_plans: "OrderedDict[Tuple, ShardPlan]" = OrderedDict()
        self._frontend_runs = 0
        self._frontend_served = 0
        self._compiles = 0
        self._compiles_cached = 0

    # ------------------------------------------------------------ sharding --
    def _resolve_devices(self, devices) -> Optional[List]:
        """Concrete device list for a sharded compile (None if unsharded).

        ``devices`` may hold jax Device objects or integer indices into
        ``jax.devices()`` (the serving engine pins tenants by index);
        ``None`` takes every device, truncated to ``spec.mesh_shape``'s
        size when the spec fixes one.
        """
        if self.spec.shard == "none":
            return None
        pool = jax.devices()
        if devices is None:
            devs = list(pool)
            if self.spec.mesh_shape is not None:
                want = int(np.prod(self.spec.mesh_shape))
                if want > len(devs):
                    raise ValueError(
                        f"mesh_shape {self.spec.mesh_shape} needs {want} "
                        f"devices, jax reports {len(devs)}")
                devs = devs[:want]
            return devs
        return [pool[d] if isinstance(d, (int, np.integer)) else d
                for d in devices]

    def _shard_plan_for(self, fp: str, tkey: Tuple[str, ...], graphs: List,
                        num_devices: int, feature_dim: int) -> ShardPlan:
        """Build (or serve from the plan memo) the shard plan for a
        fingerprinted set of banded batches over ``num_devices``."""
        pkey = (fp, tkey, self.spec.shard, num_devices, feature_dim)
        plan = self._shard_plans.get(pkey)
        if plan is None:
            plan = build_shard_plan(graphs, num_devices, self.spec.shard,
                                    feature_dim=feature_dim)
            self._memo_put(self._shard_plans, pkey, plan)
        else:
            self._shard_plans.move_to_end(pkey)
        return plan

    def _memo_put(self, memo: OrderedDict, key, value) -> None:
        memo[key] = value
        memo.move_to_end(key)
        if self.max_memo is not None:
            while len(memo) > self.max_memo:
                memo.popitem(last=False)

    # ------------------------------------------------------------ frontend --
    def frontend(self, graph: HetGraph, targets: Sequence[str]
                 ) -> FrontendResult:
        """The frontend pass for ``(graph, targets)`` — run once per
        session, then served from the session memo (and, across sessions,
        from the shared cache)."""
        key = (graph.fingerprint(), tuple(sorted(targets)))
        res = self._frontends.get(key)
        if res is None:
            res = self.pipeline.run(graph, targets)
            self._memo_put(self._frontends, key, res)
            self._frontend_runs += 1
        else:
            self._frontends.move_to_end(key)
            self._frontend_served += 1
        return res

    # ------------------------------------------------------------- compile --
    def compile(self, graph: HetGraph, targets: Sequence[str],
                cfg: HGNNConfig, *, devices=None) -> CompiledHGNN:
        """Bind a model to the cached frontend products for this graph.

        The returned ``CompiledHGNN`` carries the batch flavor the spec's
        executor consumes; compiling more models over the same
        ``(graph, targets)`` reuses every frontend product (one
        ``PackedEdges`` per semantic graph for the whole session), and an
        identical ``(graph, targets, cfg)`` compile returns the same
        object — including its jitted entry points.

        On a sharded spec (``spec.shard != "none"``) the shard plan is
        built here (cached by graph fingerprint — every model over the
        same products shares it) and ``devices`` optionally pins the
        compile to a device group (jax Devices or indices into
        ``jax.devices()``) — the serving engine's per-tenant pinning.
        ``devices`` is rejected on unsharded specs.
        """
        if devices is not None and self.spec.shard == "none":
            raise ValueError(
                "devices= requires a sharded spec (ExecutorSpec.shard is "
                "'none'): an unsharded compile has no mesh to pin")
        if self.spec.shard != "none" and cfg.num_heads > 1:
            raise ValueError(
                f"the sharded executor (ExecutorSpec.shard={self.spec.shard!r}) "
                f"runs one attention head; {cfg.model} asks for "
                f"num_heads={cfg.num_heads}: compile it unsharded")
        fp = graph.fingerprint()
        devs = self._resolve_devices(devices)
        devkey = (None if devs is None
                  else tuple(getattr(d, "id", d) for d in devs))
        ckey = (fp, tuple(sorted(targets)), cfg, devkey)
        self._compiles += 1
        hit = self._compiled.get(ckey)
        if hit is not None:
            self._compiled.move_to_end(ckey)
            self._compiles_cached += 1
            return hit
        res = self.frontend(graph, targets)
        if self.spec.na_executor == "banded":
            graphs = res.banded_batches()
        else:
            graphs = res.batches()
        model = HGNN(cfg, graph.feature_dims, graph.num_vertices,
                     sorted(targets))
        plan = None
        if devs is not None:
            plan = self._shard_plan_for(fp, ckey[1], graphs, len(devs),
                                        cfg.hidden)
        compiled = CompiledHGNN(self, self.spec, model, res, graphs, fp,
                                shard_plan=plan, devices=devs)
        self._memo_put(self._compiled, ckey, compiled)
        return compiled

    # --------------------------------------------------------------- delta --
    def compile_delta(self, compiled: CompiledHGNN, graph: HetGraph,
                      delta: GraphDelta
                      ) -> Tuple[CompiledHGNN, HetGraph, DeltaResult]:
        """Re-bind a compiled model to a delta-mutated graph incrementally.

        Runs the frontend's delta path (``FrontendPipeline.apply_delta``:
        cache migration, incremental SGB, block-splice repack) instead of
        a cold rebuild, then builds the successor ``CompiledHGNN`` — equal
        in every product to ``compile(graph.apply_delta(delta), ...)`` on
        a cold cache, but carrying forward what a delta cannot invalidate:

          * the jitted dependency-subset executor (it reads topology only
            through the traced ``DependencySubset`` pytree, so requests
            whose closures keep their bucket signature cost zero new
            traces — the shared :attr:`CompiledHGNN.dependency_traces`
            counter proves it);
          * extractor memo entries whose closures no changed product edge
            lands on (``DependencyExtractor.migrate_from``).

        The full-graph forwards and fusion betas are *not* carried — they
        are traced for the topology's packed shapes, so the successor
        re-traces/recalibrates them on first use.  Returns
        ``(new_compiled, new_graph, delta_result)``.

        Example::

            c2, g2, dres = sess.compile_delta(c1, g1, delta)
            assert c2.dependency_traces == c1.dependency_traces
        """
        if graph.fingerprint() != compiled.fingerprint:
            raise ValueError(
                "graph does not match the compiled model's fingerprint "
                "(pass the graph the model was compiled for)")
        targets = [g.metapath for g in compiled.graphs]
        dres = self.pipeline.apply_delta(graph, delta, targets)
        new_graph, res = dres.graph, dres.result
        fp_new = new_graph.fingerprint()
        tkey = tuple(sorted(targets))
        self._memo_put(self._frontends, (fp_new, tkey), res)
        self._frontend_runs += 1
        if self.spec.na_executor == "banded":
            graphs = res.banded_batches()
        else:
            graphs = res.batches()
        cfg = compiled.cfg
        model = HGNN(cfg, new_graph.feature_dims, new_graph.num_vertices,
                     sorted(targets))
        devs = compiled._devices
        plan = None
        if devs is not None:
            # the delta moved edges, so the successor replans (cached by
            # the new fingerprint) over the predecessor's device group
            plan = self._shard_plan_for(fp_new, tkey, graphs, len(devs),
                                        cfg.hidden)
        successor = CompiledHGNN(self, self.spec, model, res, graphs,
                                 fp_new, shard_plan=plan, devices=devs)
        if compiled._forward_dep is not None:
            successor._forward_dep = compiled._forward_dep
            successor._dep_origin = compiled._dep_origin
        if compiled._extractor is not None:
            ext = DependencyExtractor(model, graphs, res.semantic,
                                      flavor=self.spec.na_executor)
            changed = _changed_product_dsts(
                compiled.frontend.semantic, res.semantic, dres.touched)
            ext.migrate_from(compiled._extractor, changed,
                             frozenset(dres.touched))
            successor._extractor = ext
        self._compiles += 1
        devkey = (None if devs is None
                  else tuple(getattr(d, "id", d) for d in devs))
        self._memo_put(self._compiled, (fp_new, tkey, cfg, devkey),
                       successor)
        return successor, new_graph, dres

    # --------------------------------------------------------------- stats --
    def stats(self) -> SessionStats:
        """Snapshot of the session's reuse counters (see ``SessionStats``).

        Example::

            sess.compile(g, targets, cfg); sess.compile(g, targets, cfg)
            assert sess.stats().compiles_cached == 1
        """
        cs = self.cache.stats
        return SessionStats(
            compiles=self._compiles,
            compiles_cached=self._compiles_cached,
            frontend_runs=self._frontend_runs,
            frontend_served=self._frontend_served,
            cache_hits=cs.hits,
            cache_misses=cs.misses,
            cache_evictions=cs.evictions,
            cache_entries=len(self.cache),
            cache_nbytes=self.cache.nbytes(),
            shard=self._shard_stats(),
        )

    def _shard_stats(self) -> Optional[Dict]:
        """Aggregate device loads over every cached shard plan (None when
        the spec is unsharded): per-device edge-block / edge / MAC counts
        summed elementwise, plus the resulting max-over-mean ratio."""
        if self.spec.shard == "none":
            return None
        plans = list(self._shard_plans.values())
        ndev = max((p.num_devices for p in plans), default=0)
        blocks = np.zeros(ndev, np.int64)
        edges = np.zeros(ndev, np.int64)
        macs = np.zeros(ndev, np.int64)
        for p in plans:
            blocks[: p.num_devices] += p.device_block_counts()
            edges[: p.num_devices] += p.device_edge_counts()
            macs[: p.num_devices] += p.device_mac_counts()
        total = int(edges.sum())
        lb = float(edges.max() / (total / ndev)) if total else 1.0
        return {
            "mode": self.spec.shard,
            "plans": len(plans),
            "per_device_edge_blocks": blocks.tolist(),
            "per_device_edges": edges.tolist(),
            "per_device_macs": macs.tolist(),
            "load_balance": lb,
        }
