"""RGCN / RGAT / Simple-HGN on semantic graphs — the paper's GFP workload.

The model consumes the output of the SGB stage: a list of semantic graphs
(directed bipartite edge sets between vertex types).  Per layer:

  FP  — per-vertex-type dense projection,
  NA  — per-semantic-graph aggregation (mean for RGCN, edge-softmax
        attention for RGAT / Simple-HGN with an edge-type embedding term),
  SF  — HAN-style semantic attention fusing all semantic graphs that end at
        the same destination type (plus a self/residual path).

Paper §5.3 configuration: hidden 64, layers {3: RGAT, 3: RGCN, 2: S-HGN}.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.hgnn.layers import (
    feature_projection,
    na_attention,
    na_attention_banded,
    na_mean,
    na_mean_banded,
    semantic_fusion_beta,
)
from repro.hetero.graph import HetGraph, Relation
from repro.kernels.backend import resolve as resolve_backend
from repro.kernels.seg_sum import PackedEdges, gather_rows


@dataclasses.dataclass(frozen=True)
class SemanticGraphBatch:
    """Device-ready semantic graph: static-shape edge index arrays."""

    metapath: str
    src_type: str
    dst_type: str
    num_src: int
    num_dst: int
    src: jax.Array  # (E,) int32
    dst: jax.Array  # (E,) int32
    edge_type_id: int  # index into the Simple-HGN edge-type embedding

    @staticmethod
    def from_relation(rel: Relation, metapath: str, edge_type_id: int,
                      order: Optional[np.ndarray] = None) -> "SemanticGraphBatch":
        src, dst = rel.src, rel.dst
        if order is not None:
            src, dst = src[order], dst[order]
        return SemanticGraphBatch(
            metapath=metapath,
            src_type=metapath[0],
            dst_type=metapath[-1],
            num_src=rel.num_src,
            num_dst=rel.num_dst,
            src=jnp.asarray(src),
            dst=jnp.asarray(dst),
            edge_type_id=edge_type_id,
        )

    @staticmethod
    def from_edge_stream(metapath: str, num_src: int, num_dst: int,
                         src: np.ndarray, dst: np.ndarray,
                         edge_type_id: int) -> "SemanticGraphBatch":
        """Build from an explicit (already scheduled) edge stream — the
        restructured layout path (see core/restructure.py)."""
        return SemanticGraphBatch(
            metapath=metapath,
            src_type=metapath[0],
            dst_type=metapath[-1],
            num_src=num_src,
            num_dst=num_dst,
            src=jnp.asarray(src, jnp.int32),
            dst=jnp.asarray(dst, jnp.int32),
            edge_type_id=edge_type_id,
        )

    def device_arrays(self, edge_maps: bool = True) -> Dict:
        """The batch's device arrays as one pytree (``edge_maps`` is the
        banded batch's option; this batch has only its edge list)."""
        return {"src": self.src, "dst": self.dst}

    def bind(self, arrays: Dict) -> "SemanticGraphBatch":
        """The same batch over ``arrays`` (see :func:`bind_graphs`)."""
        return dataclasses.replace(self, **arrays)


@dataclasses.dataclass(frozen=True)
class BandedBatch:
    """Device-ready semantic graph in the restructured BANDED layout.

    The sibling of ``SemanticGraphBatch`` consumed by the banded NA
    executor (``HGNN.execute(..., na_executor="banded")``, bound by
    ``repro.api.Session.compile``): it carries the pipeline's
    cached ``PackedEdges`` blocks (built once per semantic graph, shared
    across models and layers) plus the gather/scatter permutations that
    move per-layer features into the renumbered banded numbering and NA
    outputs back to global vertex order.  FP and SF stay in global
    numbering; only the NA hot loop runs banded.
    """

    metapath: str
    src_type: str
    dst_type: str
    num_src: int
    num_dst: int
    edge_type_id: int
    packed: PackedEdges  # renumbered banded blocks (host-built, cached)
    src_gather: jax.Array  # (num_src,) banded row -> global src id
    dst_gather: jax.Array  # (num_dst,) banded row -> global dst id
    dst_scatter: jax.Array  # (num_dst,) global dst -> banded row
    src_banded: jax.Array  # (E,) banded src ids, scheduled order
    dst_banded: jax.Array  # (E,) banded dst ids, scheduled order
    deg: jax.Array  # (num_dst,) in-degree per banded dst row (float32)

    @staticmethod
    def from_restructured(metapath: str, rg, packed: PackedEdges,
                          edge_type_id: int) -> "BandedBatch":
        """Build from a ``RestructuredGraph`` + its cached renumbered
        packing (``rg.packed(renumbered=True)``) — the two must come from
        the same layout knobs, which the pipeline cache guarantees."""
        rel = rg.original
        sperm, dperm = rg.permutations()  # global -> banded
        s, d = rg.scheduled_edges(renumbered=True)
        deg = np.bincount(d, minlength=rel.num_dst).astype(np.float32)
        return BandedBatch(
            metapath=metapath,
            src_type=metapath[0],
            dst_type=metapath[-1],
            num_src=rel.num_src,
            num_dst=rel.num_dst,
            edge_type_id=edge_type_id,
            packed=packed,
            src_gather=jnp.asarray(np.argsort(sperm), jnp.int32),
            dst_gather=jnp.asarray(np.argsort(dperm), jnp.int32),
            dst_scatter=jnp.asarray(dperm, jnp.int32),
            src_banded=jnp.asarray(s, jnp.int32),
            dst_banded=jnp.asarray(d, jnp.int32),
            deg=jnp.asarray(deg),
        )

    def device_arrays(self, edge_maps: bool = True) -> Dict:
        """The batch's device arrays as one pytree: the packing's
        (``PackedEdges.device_arrays``, with its edge maps when
        ``edge_maps``) and the permutations, banded edges and degrees."""
        return {"packed": self.packed.device_arrays(edge_maps),
                **{f: getattr(self, f) for f in _BANDED_ARRAYS}}

    def bind(self, arrays: Dict) -> "BandedBatch":
        """The same batch over ``arrays`` (see :func:`bind_graphs`)."""
        return dataclasses.replace(
            self, packed=self.packed.bind(arrays["packed"]),
            **{f: arrays[f] for f in _BANDED_ARRAYS})


_BANDED_ARRAYS = ("src_gather", "dst_gather", "dst_scatter", "src_banded",
                  "dst_banded", "deg")


def graph_arrays(graphs: List, model: str) -> List[Dict]:
    """The device arrays of ``graphs`` as one pytree, for a jitted function
    that takes the graph as an argument and binds it (:func:`bind_graphs`):
    the graph is then data of the compiled program, not constants baked
    into it.  The mean (``rgcn``) forward reads no edge map, so its
    batches leave them out (a backward pass uploads them on first use)."""
    return [g.device_arrays(edge_maps=model != "rgcn") for g in graphs]


def bind_graphs(graphs: List, arrays: List[Dict]) -> List:
    """``graphs`` over ``arrays`` (as :func:`graph_arrays` gives them, or
    a jitted function's tracers of them): the same batches, every device
    array taken from ``arrays``."""
    return [g.bind(a) for g, a in zip(graphs, arrays)]


@dataclasses.dataclass(frozen=True)
class HGNNConfig:
    model: str  # "rgcn" | "rgat" | "shgn"
    hidden: int = 64
    num_layers: int = 3
    num_classes: int = 3
    target_type: str = "P"
    edge_emb_dim: int = 16  # Simple-HGN edge-type embedding
    sf_att_dim: int = 64

    def __post_init__(self):
        assert self.model in ("rgcn", "rgat", "shgn"), self.model


def _dense_init(key, d_in, d_out, scale=None):
    scale = scale if scale is not None else (2.0 / max(1, d_in)) ** 0.5
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * scale


def init_params(
    key: jax.Array,
    cfg: HGNNConfig,
    feature_dims: Dict[str, int],
    metapaths: List[str],
    hidden_override: Optional[int] = None,
) -> Dict:
    """Build the parameter pytree. ``feature_dims`` maps vertex type -> raw
    dim (0 = featureless type: gets a learned embedding-like projection of a
    one-hot degree bucket; we give it a single learned vector)."""
    h = hidden_override or cfg.hidden
    params: Dict = {"layers": []}
    types = sorted(feature_dims)
    for layer in range(cfg.num_layers):
        key, *ks = jax.random.split(key, 9 + 4 * len(types) + 4 * len(metapaths))
        ki = iter(ks)
        lp: Dict = {"fp": {}, "na": {}, "sf": {}}
        for t in types:
            d_in = feature_dims[t] if layer == 0 else h
            if d_in == 0:  # featureless: learned constant row
                lp["fp"][t] = {
                    "w": _dense_init(next(ki), 1, h),
                    "b": jnp.zeros((h,), jnp.float32),
                }
            else:
                lp["fp"][t] = {
                    "w": _dense_init(next(ki), d_in, h),
                    "b": jnp.zeros((h,), jnp.float32),
                }
        for mp in metapaths:
            na: Dict = {"w_rel": _dense_init(next(ki), h, h)}
            if cfg.model in ("rgat", "shgn"):
                na["a_src"] = jax.random.normal(next(ki), (h,)) * 0.1
                na["a_dst"] = jax.random.normal(next(ki), (h,)) * 0.1
            lp["na"][mp] = na
        if cfg.model == "shgn":
            lp["edge_emb"] = jax.random.normal(next(ki), (len(metapaths), cfg.edge_emb_dim)) * 0.1
            lp["a_edge"] = jax.random.normal(next(ki), (cfg.edge_emb_dim,)) * 0.1
        for t in types:
            lp["sf"][t] = {
                "w": _dense_init(next(ki), h, cfg.sf_att_dim),
                "b": jnp.zeros((cfg.sf_att_dim,), jnp.float32),
                "q": jax.random.normal(next(ki), (cfg.sf_att_dim,)) * 0.1,
                "w_self": _dense_init(next(ki), h, h),
            }
        params["layers"].append(lp)
    key, k1 = jax.random.split(key)
    params["head"] = {
        "w": _dense_init(k1, h, cfg.num_classes),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return params


class HGNN:
    """Config + pure apply function (params are an explicit pytree)."""

    def __init__(self, cfg: HGNNConfig, feature_dims: Dict[str, int],
                 num_vertices: Dict[str, int], metapaths: List[str]):
        self.cfg = cfg
        self.feature_dims = dict(feature_dims)
        self.num_vertices = dict(num_vertices)
        self.metapaths = list(metapaths)

    def init(self, key: jax.Array) -> Dict:
        return init_params(key, self.cfg, self.feature_dims, self.metapaths)

    def hidden_states(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List[SemanticGraphBatch],
        *,
        na_executor: str = "jnp",
        kernel_backend: Optional[str] = None,
        betas_out: Optional[List] = None,
    ) -> Dict[str, jax.Array]:
        """Run every FP -> NA -> SF layer; returns the final per-type
        hidden states (global vertex numbering), pre-classifier-head.

        ``betas_out``, when given an empty list, collects one
        ``{dst_type: (P_t + 1,)}`` dict of semantic-attention weights per
        layer — the graph-level SF statistics the dependency-subset
        executor freezes (see :meth:`fusion_betas`).

        This is the shared body of :meth:`execute` (full head) and
        :meth:`execute_subset` (head over a gathered row subset): message
        passing is always full-graph — a target vertex's logits depend on
        its whole receptive field — so the two entry points differ only in
        which target rows go through the head.

        ``na_executor`` selects the NA executor:
          * "jnp"    — ``jax.ops.segment_*`` over global edge lists
                       (``graphs`` must be ``SemanticGraphBatch``);
          * "banded" — the Pallas NA kernels over the restructurer's cached
                       ``PackedEdges`` blocks (``graphs`` must be
                       ``BandedBatch``, see
                       ``FrontendResult.banded_batches()``); features are
                       permuted once per layer into the renumbered banded
                       layout and NA outputs permuted back, so FP/SF and
                       the returned logits keep global vertex numbering.
        ``kernel_backend`` ("interpret" | "pallas" | None for the
        platform's, see ``repro.kernels.backend``) only applies to the
        banded path.

        Both executors are differentiable: the banded NA kernels carry
        custom VJPs (kernels/seg_sum.py, kernels/ops.py) whose backward
        gathers through the cached packing, so ``jax.grad`` of a loss
        built on this apply works identically on either backend — the
        training path (train/hgnn_step.py) runs banded with the same
        cached ``BandedBatch`` list across every step.
        """
        cfg = self.cfg
        if na_executor not in ("jnp", "banded"):
            raise ValueError(f"unknown na_executor {na_executor!r}")
        banded = na_executor == "banded"
        if banded:
            kernel_backend = resolve_backend(kernel_backend)
        for g in graphs:
            if banded != isinstance(g, BandedBatch):
                raise TypeError(
                    f"na_executor={na_executor!r} needs "
                    f"{'BandedBatch' if banded else 'SemanticGraphBatch'} "
                    f"inputs, got {type(g).__name__} for {g.metapath!r}")
        h: Dict[str, jax.Array] = {}
        for t, n in self.num_vertices.items():
            if self.feature_dims.get(t, 0) > 0:
                h[t] = features[t]
            else:
                h[t] = jnp.ones((n, 1), jnp.float32)  # featureless placeholder

        for li, lp in enumerate(params["layers"]):
            # --- FP ---
            hp = {}
            for t, x in h.items():
                with obs.scope(obs.fp_scope(li, t)):
                    hp[t] = jax.nn.relu(feature_projection(
                        lp["fp"][t]["w"], lp["fp"][t]["b"], x))
            # --- NA per semantic graph ---
            z_by_dst: Dict[str, List[jax.Array]] = {}
            for g in graphs:
                with obs.scope(obs.na_scope(li, g.metapath)):
                    na_p = lp["na"][g.metapath]
                    h_src = hp[g.src_type] @ na_p["w_rel"]
                    edge_bias = None
                    if cfg.model == "shgn":
                        eb = lp["edge_emb"][g.edge_type_id] @ lp["a_edge"]
                        edge_bias = eb  # scalar broadcast over edges
                    if banded:
                        hb = gather_rows(h_src, g.src_gather)
                        if cfg.model == "rgcn":
                            zb = na_mean_banded(g.packed, hb, g.deg,
                                                backend=kernel_backend)
                        else:
                            zb = na_attention_banded(
                                hb, gather_rows(hp[g.dst_type], g.dst_gather),
                                g.src_banded, g.dst_banded, g.packed,
                                na_p["a_src"], na_p["a_dst"],
                                edge_bias=edge_bias, backend=kernel_backend,
                            )
                        # banded -> global dst order
                        z = gather_rows(zb, g.dst_scatter)
                    elif cfg.model == "rgcn":
                        z = na_mean(h_src, g.src, g.dst, g.num_dst)
                    else:
                        z = na_attention(
                            h_src, hp[g.dst_type], g.src, g.dst, g.num_dst,
                            na_p["a_src"], na_p["a_dst"], edge_bias=edge_bias,
                        )
                z_by_dst.setdefault(g.dst_type, []).append(z)
            # --- SF per destination type (+ self path for every type) ---
            h_next: Dict[str, jax.Array] = {}
            layer_betas: Dict[str, jax.Array] = {}
            for t, x in hp.items():
                with obs.scope(obs.sf_scope(li, t)):
                    sf = lp["sf"][t]
                    self_z = x @ sf["w_self"]
                    if t in z_by_dst:
                        stack = jnp.stack(z_by_dst[t] + [self_z])  # (P+1, N, D)
                        beta = semantic_fusion_beta(stack, sf["w"], sf["b"],
                                                    sf["q"])
                        layer_betas[t] = beta
                        h_next[t] = jax.nn.relu(
                            jnp.einsum("p,pnd->nd", beta, stack))
                    else:
                        h_next[t] = jax.nn.relu(self_z)
            if betas_out is not None:
                betas_out.append(layer_betas)
            h = h_next

        return h

    def fusion_betas(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List[SemanticGraphBatch],
        *,
        na_executor: str = "jnp",
        kernel_backend: Optional[str] = None,
    ) -> List[Dict[str, jax.Array]]:
        """Per-layer SF attention weights from one full forward.

        Semantic fusion's beta is a mean over *all* rows of a type — a
        graph-level statistic with no per-request dependence — so the
        dependency-subset executor cannot re-derive it from a partial row
        set and instead consumes these frozen values (recomputed only
        when parameters or features change; serving recalibrates on
        ``swap_params``).  Returns ``cfg.num_layers`` dicts keyed by
        destination type, each ``(num_graphs_into_type + 1,)``.
        """
        betas: List[Dict[str, jax.Array]] = []
        self.hidden_states(params, features, graphs,
                           na_executor=na_executor,
                           kernel_backend=kernel_backend,
                           betas_out=betas)
        return betas

    def execute_dependency_subset(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List[SemanticGraphBatch],
        dep: Dict,
        betas: List[Dict[str, jax.Array]],
        *,
        na_executor: str = "jnp",
        kernel_backend: Optional[str] = None,
    ) -> jax.Array:
        """FP -> NA -> SF over an induced k-hop dependency subgraph.

        ``dep`` is a ``core.subgraph.DependencySubset.arrays`` pytree for
        the same graph/executor flavor as ``graphs`` (every array traced,
        so requests sharing a bucket signature share one jit trace) and
        ``betas`` the frozen SF weights from :meth:`fusion_betas` under
        the same params/features.  Rows ``dep["node_rows"][:n]`` of the
        result match the same target rows of :meth:`execute` to
        reassociation tolerance: the closure keeps every edge into the
        hop-``L-1`` frontier, so requested rows aggregate their full
        receptive field while garbage on deeper-frontier rows only flows
        into outputs nothing reads.
        """
        from repro.core.subgraph import (na_attention_subset_banded,
                                         na_mean_subset_banded)

        cfg = self.cfg
        if na_executor not in ("jnp", "banded"):
            raise ValueError(f"unknown na_executor {na_executor!r}")
        banded = na_executor == "banded"
        if banded:
            kernel_backend = resolve_backend(kernel_backend)
        gather = dep["gather"]
        h: Dict[str, jax.Array] = {}
        for t in self.num_vertices:
            rows = gather[t]
            if self.feature_dims.get(t, 0) > 0:
                with obs.scope(obs.fp_scope(0, t)):  # the input rows of FP
                    h[t] = features[t][rows]
            else:
                h[t] = jnp.ones((rows.shape[0], 1), jnp.float32)

        for li, lp in enumerate(params["layers"]):
            hp = {}
            for t, x in h.items():
                with obs.scope(obs.fp_scope(li, t)):
                    hp[t] = jax.nn.relu(feature_projection(
                        lp["fp"][t]["w"], lp["fp"][t]["b"], x))
            z_by_dst: Dict[str, List[jax.Array]] = {}
            for g, dg in zip(graphs, dep["graphs"]):
                with obs.scope(obs.na_scope(li, g.metapath)):
                    na_p = lp["na"][g.metapath]
                    h_src = hp[g.src_type] @ na_p["w_rel"]
                    edge_bias = None
                    if cfg.model == "shgn":
                        edge_bias = lp["edge_emb"][g.edge_type_id] @ lp["a_edge"]
                    if banded:
                        if cfg.model == "rgcn":
                            z = na_mean_subset_banded(
                                g.packed, dg, h_src, backend=kernel_backend)
                        else:
                            z = na_attention_subset_banded(
                                g.packed, dg, h_src, hp[g.dst_type],
                                na_p["a_src"], na_p["a_dst"],
                                edge_bias=edge_bias, backend=kernel_backend)
                    elif cfg.model == "rgcn":
                        z = na_mean(h_src, dg["src"], dg["dst"],
                                    gather[g.dst_type].shape[0])
                    else:
                        z = na_attention(
                            h_src, hp[g.dst_type], dg["src"], dg["dst"],
                            gather[g.dst_type].shape[0],
                            na_p["a_src"], na_p["a_dst"], edge_bias=edge_bias)
                z_by_dst.setdefault(g.dst_type, []).append(z)
            h_next: Dict[str, jax.Array] = {}
            for t, x in hp.items():
                with obs.scope(obs.sf_scope(li, t)):
                    sf = lp["sf"][t]
                    self_z = x @ sf["w_self"]
                    if t in z_by_dst:
                        stack = jnp.stack(z_by_dst[t] + [self_z])
                        h_next[t] = jax.nn.relu(
                            jnp.einsum("p,pnd->nd", betas[li][t], stack))
                    else:
                        h_next[t] = jax.nn.relu(self_z)
            h = h_next

        with obs.scope(obs.HEAD):
            head = params["head"]
            rows = h[cfg.target_type][dep["node_rows"]]
            return rows @ head["w"] + head["b"]

    def execute(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List[SemanticGraphBatch],
        *,
        na_executor: str = "jnp",
        kernel_backend: Optional[str] = None,
    ) -> jax.Array:
        """Full GFP stage; returns logits for ``cfg.target_type`` vertices.

        This is the executor-dispatching implementation behind
        ``repro.api.CompiledHGNN.forward`` — callers should compile
        through a ``repro.api.Session``, which binds the batch flavor and
        these kwargs once from an ``ExecutorSpec``.  See :meth:`hidden_states`
        for the executor semantics (``na_executor``/``kernel_backend``)
        and differentiability notes shared with :meth:`execute_subset`.
        """
        h = self.hidden_states(params, features, graphs,
                               na_executor=na_executor,
                               kernel_backend=kernel_backend)
        with obs.scope(obs.HEAD):
            head = params["head"]
            return h[self.cfg.target_type] @ head["w"] + head["b"]

    def execute_subset(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List[SemanticGraphBatch],
        node_ids: jax.Array,
        *,
        na_executor: str = "jnp",
        kernel_backend: Optional[str] = None,
    ) -> jax.Array:
        """Logits for an explicit subset of ``cfg.target_type`` vertices.

        Message passing runs full-graph (a target vertex's receptive
        field spans the whole topology), but only the ``node_ids`` rows of
        the final hidden state are gathered through the classifier head —
        the serving micro-batch path, where a queue of small node-subset
        requests unions into one ``node_ids`` buffer
        (``repro.api.CompiledHGNN.forward_subset`` wraps this with a
        padded/bucketed id buffer so resubmissions never retrace).
        Row ``i`` of the result equals row ``node_ids[i]`` of
        :meth:`execute` under the same trace.
        """
        h = self.hidden_states(params, features, graphs,
                               na_executor=na_executor,
                               kernel_backend=kernel_backend)
        with obs.scope(obs.HEAD):
            head = params["head"]
            rows = h[self.cfg.target_type][node_ids]
            return rows @ head["w"] + head["b"]

    def execute_loss(self, params, features, graphs, labels: jax.Array,
                     mask: Optional[jax.Array] = None, *,
                     na_executor: str = "jnp",
                     kernel_backend: Optional[str] = None) -> jax.Array:
        """Masked cross-entropy over ``cfg.target_type`` vertices
        (semi-supervised node classification).  Differentiable on both NA
        executors: ``jax.grad`` of this loss on the banded executor
        matches the jnp executor's gradients to float tolerance."""
        logits = self.execute(params, features, graphs,
                              na_executor=na_executor,
                              kernel_backend=kernel_backend)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        if mask is not None:
            return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
        return jnp.mean(nll)


def package_batches(
    semantic: Dict[str, Relation],
    targets: List[str],
    restructured: bool = False,
    restructured_graphs: Optional[Dict[str, "object"]] = None,
) -> List[SemanticGraphBatch]:
    """The one packaging path: semantic graphs -> model-ready batches.

    Batches always carry *global* vertex ids (restructuring only reorders
    the edge stream; features and output rows keep the original
    numbering).  ``restructured_graphs`` supplies already-computed
    ``RestructuredGraph`` objects (the pipeline cache's), skipping the
    recompute.
    """
    from repro.core.restructure import restructure as _restructure

    out = []
    for i, mp in enumerate(sorted(targets)):
        rel = semantic[mp]
        if restructured:
            rg = (restructured_graphs or {}).get(mp)
            if rg is None:
                rg = _restructure(rel)
            s, d = rg.scheduled_edges()
            out.append(SemanticGraphBatch.from_edge_stream(
                mp, rel.num_src, rel.num_dst, s, d, i))
        else:
            out.append(SemanticGraphBatch.from_relation(rel, mp, i))
    return out


def graphs_from_sgb(
    graph: HetGraph,
    semantic: Dict[str, Relation],
    targets: List[str],
    restructured: bool = False,
    restructured_graphs: Optional[Dict[str, "object"]] = None,
) -> List[SemanticGraphBatch]:
    """Package SGB outputs for the model — optionally restructured.

    With ``restructured=True`` each semantic graph goes through the Graph
    Restructurer and its *scheduled* edge stream is used (same math, the
    locality-optimized order the backend would consume).
    """
    del graph  # packaging depends only on the semantic graphs
    return package_batches(semantic, targets, restructured=restructured,
                           restructured_graphs=restructured_graphs)


def graphs_from_pipeline(result) -> List[SemanticGraphBatch]:
    """Batches from a ``pipeline.FrontendResult`` — built once on the
    result and shared by every model (multi-model scenario)."""
    return result.batches()


def banded_graphs_from_pipeline(result) -> List[BandedBatch]:
    """Banded batches from a ``pipeline.FrontendResult`` for the banded
    NA executor — one ``PackedEdges`` per semantic graph, shared by every
    model and layer."""
    return result.banded_batches()
