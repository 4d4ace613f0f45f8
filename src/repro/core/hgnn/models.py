"""RGCN / RGAT / Simple-HGN / HAN on semantic graphs — the paper's GFP
workload.

The model consumes the output of the SGB stage: a list of semantic graphs
(directed bipartite edge sets between vertex types).  Per layer:

  FP  — per-vertex-type dense projection,
  NA  — per-semantic-graph aggregation (mean for RGCN, edge-softmax
        attention for RGAT / Simple-HGN with an edge-type embedding term,
        and HAN's multi-head node-level attention),
  SF  — HAN-style semantic attention fusing all semantic graphs that end at
        the same destination type (plus a self/residual path).

The layer is written once, in ``HGNN._layers``.  Each execution path
supplies only its NA step, and the batches pick it: the jnp pair over edge
lists for ``SemanticGraphBatch`` es, the banded Pallas kernels for
``BandedBatch`` es (each in a full-graph and a dependency-subset form), and
the merged multi-relation step of the sharded executor
(``repro.distributed.hgnn``).  Every NA step takes its inputs from
:func:`na_inputs` and ends in :func:`na_output`; :data:`NA_FORMS`, the
table of what each model's NA computes, is the one place on the forward
path that reads the model's name.

Paper §5.3 configuration: hidden 64, layers {3: RGAT, 3: RGCN, 2: S-HGN}.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, NamedTuple, Optional

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
from repro.core.subgraph import na_subset_banded
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

    def device_arrays(self, dense: bool = True) -> Dict:
        """The batch's device arrays as one pytree: its edge list."""
        return {"src": self.src, "dst": self.dst}

    def bind(self, arrays: Dict) -> "SemanticGraphBatch":
        """The same batch over ``arrays`` (see :func:`bind_graphs`)."""
        return dataclasses.replace(self, **arrays)


@dataclasses.dataclass(frozen=True)
class BandedBatch:
    """Device-ready semantic graph in the restructured BANDED layout.

    The sibling of ``SemanticGraphBatch`` that picks the banded NA step
    (``HGNN.execute`` over a list of them, bound by
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
    deg: jax.Array  # (num_dst,) in-degree per banded dst row (float32)

    @staticmethod
    def from_restructured(metapath: str, rg, packed: PackedEdges,
                          edge_type_id: int) -> "BandedBatch":
        """Build from a ``RestructuredGraph`` + its cached renumbered
        packing (``rg.packed(renumbered=True)``) — the two must come from
        the same layout knobs, which the pipeline cache guarantees."""
        rel = rg.original
        sperm, dperm = rg.permutations()  # global -> banded
        _, d = rg.scheduled_edges(renumbered=True)
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
            deg=jnp.asarray(deg),
        )

    def device_arrays(self, dense: bool = True) -> Dict:
        """The batch's device arrays as one pytree: the packing's
        (``PackedEdges.device_arrays``) and the permutations and degrees."""
        return {"packed": self.packed.device_arrays(dense),
                **{f: getattr(self, f) for f in _BANDED_ARRAYS}}

    def bind(self, arrays: Dict) -> "BandedBatch":
        """The same batch over ``arrays`` (see :func:`bind_graphs`)."""
        return dataclasses.replace(
            self, packed=self.packed.bind(arrays["packed"]),
            **{f: arrays[f] for f in _BANDED_ARRAYS})


_BANDED_ARRAYS = ("src_gather", "dst_gather", "dst_scatter", "deg")


def graph_arrays(graphs: List, dense: bool = True) -> List[Dict]:
    """The device arrays of ``graphs`` as one pytree, for a jitted function
    that takes the graph as an argument and binds it (:func:`bind_graphs`):
    the graph is then data of the compiled program, not constants baked
    into it.  No forward reads a packing's edge maps, so the pytree leaves
    them out (a backward pass uploads them on first use); ``dense=False``
    leaves out the dense tiles too, which only static weights read
    (:func:`static_na_weights`)."""
    return [g.device_arrays(dense) for g in graphs]


def bind_graphs(graphs: List, arrays: List[Dict]) -> List:
    """``graphs`` over ``arrays`` (as :func:`graph_arrays` gives them, or
    a jitted function's tracers of them): the same batches, every device
    array taken from ``arrays``."""
    return [g.bind(a) for g, a in zip(graphs, arrays)]


class NAForm(NamedTuple):
    """What one model's NA step computes (:data:`NA_FORMS`)."""

    attention: bool  # edge-softmax attention, else the degree-normalised mean
    edge_bias: bool = False  # Simple-HGN's per-metapath edge-type term
    project_dst: bool = False  # logits on the w_rel-projected destination (HAN)
    elu: bool = False  # ELU on the heads' concatenated output (HAN)


# Per model: RGCN (Schlichtkrull et al. 2018), RGAT, Simple-HGN (Lv et al.
# 2021) and HAN (Wang et al. 2019, arXiv:1903.07293), whose node-level
# attention scores W_k h_i against W_k h_j and passes each head's sum
# through an ELU.
NA_FORMS: Dict[str, NAForm] = {
    "rgcn": NAForm(attention=False),
    "rgat": NAForm(attention=True),
    "shgn": NAForm(attention=True, edge_bias=True),
    "han": NAForm(attention=True, project_dst=True, elu=True),
}


@dataclasses.dataclass(frozen=True)
class HGNNConfig:
    model: str  # a key of NA_FORMS: "rgcn" | "rgat" | "shgn" | "han"
    hidden: int = 64
    num_layers: int = 3
    num_classes: int = 3
    target_type: str = "P"
    edge_emb_dim: int = 16  # Simple-HGN edge-type embedding
    sf_att_dim: int = 64
    num_heads: int = 1  # attention heads, each over hidden // num_heads columns

    def __post_init__(self):
        if self.model not in NA_FORMS:
            raise ValueError(f"unknown model {self.model!r} (expected one of "
                             f"{sorted(NA_FORMS)})")
        if self.num_heads < 1 or self.hidden % self.num_heads:
            raise ValueError(f"num_heads {self.num_heads} must divide hidden "
                             f"{self.hidden}")
        if self.num_heads > 1 and not NA_FORMS[self.model].attention:
            raise ValueError(f"{self.model} has no attention to split into "
                             f"{self.num_heads} heads")


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
    form, heads = NA_FORMS[cfg.model], cfg.num_heads
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
            if form.attention:  # (h,) for one head, else (heads, h / heads)
                shape = (h,) if heads == 1 else (heads, h // heads)
                na["a_src"] = jax.random.normal(next(ki), shape) * 0.1
                na["a_dst"] = jax.random.normal(next(ki), shape) * 0.1
            lp["na"][mp] = na
        if form.edge_bias:
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


def static_na_weights(model: str) -> bool:
    """Whether ``model``'s NA aggregates with the packing's static weights
    (the mean model; attention weights are traced)."""
    return not NA_FORMS[model].attention


class NAInputs(NamedTuple):
    """What one semantic graph's NA reads in one layer (:func:`na_inputs`):
    the ``w_rel``-projected sources; for attention also the destination
    features and attention vectors, and Simple-HGN's scalar ``bias``."""

    h_src: jax.Array
    h_dst: Optional[jax.Array] = None
    a_src: Optional[jax.Array] = None
    a_dst: Optional[jax.Array] = None
    bias: Optional[jax.Array] = None

    @property
    def attention(self) -> bool:
        """Edge-softmax attention (else the degree-normalised mean)."""
        return self.a_src is not None


def na_inputs(cfg: HGNNConfig, lp: Dict, g, hp: Dict[str, jax.Array]) -> NAInputs:
    """The NA inputs of batch ``g`` in a layer with params ``lp`` and FP
    outputs ``hp``; every NA step takes its inputs from here, and its
    output goes through :func:`na_output`.  Simple-HGN's edge-type term is
    there where the layer holds an edge-type embedding; HAN's logits read
    the destination projected by ``w_rel`` (the sources' own rows where
    the metapath starts and ends at one type)."""
    na_p = lp["na"][g.metapath]
    h_src = hp[g.src_type] @ na_p["w_rel"]
    form = NA_FORMS[cfg.model]
    if not form.attention:
        return NAInputs(h_src)
    bias = None
    if "edge_emb" in lp:
        bias = lp["edge_emb"][g.edge_type_id] @ lp["a_edge"]  # one scalar per metapath
    h_dst = hp[g.dst_type]
    if form.project_dst:
        h_dst = h_src if g.dst_type == g.src_type else h_dst @ na_p["w_rel"]
    return NAInputs(h_src, h_dst, na_p["a_src"], na_p["a_dst"], bias)


def na_output(cfg: HGNNConfig, z: jax.Array) -> jax.Array:
    """The last op of every NA step: HAN's ELU over the heads' outputs
    (elementwise, so per head), else ``z`` as it is."""
    return jax.nn.elu(z) if NA_FORMS[cfg.model].elu else z


def _na_edges(x: NAInputs, src: jax.Array, dst: jax.Array, num_dst: int) -> jax.Array:
    """NA over an edge list (``jax.ops.segment_*``, the reference)."""
    if not x.attention:
        return na_mean(x.h_src, src, dst, num_dst)
    return na_attention(x.h_src, x.h_dst, src, dst, num_dst, x.a_src, x.a_dst,
                        edge_bias=x.bias)


def _na_banded(g: "BandedBatch", x: NAInputs, backend: str) -> jax.Array:
    """NA on the Pallas kernels over ``g``'s packing: features permuted
    into the banded numbering, the output back into global order."""
    hb = gather_rows(x.h_src, g.src_gather)
    if not x.attention:
        zb = na_mean_banded(g.packed, hb, g.deg, backend=backend)
    else:
        zb = na_attention_banded(
            g.packed, hb, gather_rows(x.h_dst, g.dst_gather), x.a_src, x.a_dst,
            edge_bias=x.bias, backend=backend)
    return gather_rows(zb, g.dst_scatter)


def _is_banded(graphs: List) -> bool:
    """Whether ``graphs`` are ``BandedBatch`` es (else ``SemanticGraphBatch``
    es): the batches pick the NA step.  A mixed list is a ``TypeError``."""
    kinds = {type(g) for g in graphs}
    if len(kinds) > 1 or not kinds <= {BandedBatch, SemanticGraphBatch}:
        raise TypeError("graphs must be all BandedBatch or all SemanticGraphBatch, "
                        f"got {sorted(k.__name__ for k in kinds)}")
    return BandedBatch in kinds


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

    # ------------------------------------------------- the one layer loop --
    def _inputs(self, features: Dict[str, jax.Array],
                rows: Optional[Dict[str, jax.Array]] = None) -> Dict[str, jax.Array]:
        """Layer 0's input per vertex type: its features, or a ones column
        for a featureless type; with ``rows``, only the rows ``rows[type]``."""
        h: Dict[str, jax.Array] = {}
        for t, n in self.num_vertices.items():
            if rows is not None:
                n = rows[t].shape[0]
            if self.feature_dims.get(t, 0) == 0:
                h[t] = jnp.ones((n, 1), jnp.float32)  # featureless placeholder
            elif rows is None:
                h[t] = features[t]
            else:
                with obs.scope(obs.fp_scope(0, t)):  # the input rows of FP
                    h[t] = features[t][rows[t]]
        return h

    def _layers(self, params: Dict, h: Dict[str, jax.Array], graphs: List,
                aggregate: Callable, *, betas: Optional[List] = None,
                betas_out: Optional[List] = None) -> Dict[str, jax.Array]:
        """Every FP -> NA -> SF layer over ``h``; the last layer's states.

        ``aggregate(li, lp, hp)`` is the path's NA step: layer ``li``'s NA
        outputs (params ``lp``, FP outputs ``hp``), one per batch of
        ``graphs``, in order.  SF fuses them per destination type with the
        semantic attention it computes, or with the frozen ``betas`` (one
        dict per layer) where given; ``betas_out`` collects the computed
        ones."""
        for li, lp in enumerate(params["layers"]):
            hp = {}
            for t, x in h.items():
                with obs.scope(obs.fp_scope(li, t)):
                    hp[t] = jax.nn.relu(feature_projection(
                        lp["fp"][t]["w"], lp["fp"][t]["b"], x))
            z_by_dst: Dict[str, List[jax.Array]] = {}
            for g, z in zip(graphs, aggregate(li, lp, hp)):
                z_by_dst.setdefault(g.dst_type, []).append(z)
            h, layer_betas = {}, {}
            for t, x in hp.items():
                with obs.scope(obs.sf_scope(li, t)):
                    sf = lp["sf"][t]
                    self_z = x @ sf["w_self"]
                    if t not in z_by_dst:
                        h[t] = jax.nn.relu(self_z)
                        continue
                    stack = jnp.stack(z_by_dst[t] + [self_z])  # (P+1, N, D)
                    if betas is None:
                        beta = layer_betas[t] = semantic_fusion_beta(
                            stack, sf["w"], sf["b"], sf["q"])
                    else:
                        beta = betas[li][t]
                    h[t] = jax.nn.relu(jnp.einsum("p,pnd->nd", beta, stack))
            if betas_out is not None:
                betas_out.append(layer_betas)
        return h

    def _head(self, params: Dict, h: Dict[str, jax.Array],
              rows: Optional[jax.Array] = None) -> jax.Array:
        """The classifier over the target type's states (its ``rows``)."""
        with obs.scope(obs.HEAD):
            head = params["head"]
            x = h[self.cfg.target_type]
            if rows is not None:
                x = x[rows]
            return x @ head["w"] + head["b"]

    def _per_graph(self, graphs: List, step: Callable) -> Callable:
        """The ``aggregate`` of :meth:`_layers` that runs ``step(g, inputs)``
        for each batch under its NA scope."""
        cfg = self.cfg

        def aggregate(li, lp, hp):
            zs = []
            for g in graphs:
                with obs.scope(obs.na_scope(li, g.metapath)):
                    zs.append(na_output(cfg, step(g, na_inputs(cfg, lp, g, hp))))
            return zs

        return aggregate

    # ------------------------------------------------------------ entries --
    def hidden_states(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List,
        *,
        kernel_backend: Optional[str] = None,
        betas_out: Optional[List] = None,
    ) -> Dict[str, jax.Array]:
        """Run every FP -> NA -> SF layer over the whole graph; returns the
        final per-type hidden states (global vertex numbering),
        pre-classifier-head.  The body of :meth:`execute`,
        :meth:`execute_subset`, :meth:`execute_loss` and
        :meth:`fusion_betas`.

        The batches pick the NA step:
          * ``SemanticGraphBatch`` es — ``jax.ops.segment_*`` over global
            edge lists (the reference);
          * ``BandedBatch`` es (``FrontendResult.banded_batches()``) — the
            Pallas NA kernels over the restructurer's cached
            ``PackedEdges`` blocks; features are permuted per layer into
            the banded numbering and NA outputs back, so FP/SF and the
            returned states keep global vertex numbering.
        A mixed list is a ``TypeError``.  ``kernel_backend`` ("interpret"
        | "pallas" | None for the platform's, see ``repro.kernels.backend``)
        only applies to banded batches.

        ``betas_out``, when given an empty list, collects one
        ``{dst_type: (P_t + 1,)}`` dict of semantic-attention weights per
        layer (see :meth:`fusion_betas`).

        Both NA steps are differentiable: the banded kernels carry custom
        VJPs (kernels/seg_sum.py, kernels/ops.py) whose backward gathers
        through the cached packing, so ``jax.grad`` of a loss built on
        this apply agrees across batch types, and one cached
        ``BandedBatch`` list serves every training step.
        """
        if _is_banded(graphs):
            step = functools.partial(_na_banded, backend=resolve_backend(kernel_backend))
        else:
            def step(g, x):
                return _na_edges(x, g.src, g.dst, g.num_dst)

        return self._layers(params, self._inputs(features), graphs,
                            self._per_graph(graphs, step), betas_out=betas_out)

    def fusion_betas(self, params: Dict, features: Dict[str, jax.Array],
                     graphs: List, *, kernel_backend: Optional[str] = None
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
                           kernel_backend=kernel_backend, betas_out=betas)
        return betas

    def execute_dependency_subset(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List,
        dep: Dict,
        betas: List[Dict[str, jax.Array]],
        *,
        kernel_backend: Optional[str] = None,
    ) -> jax.Array:
        """FP -> NA -> SF over an induced k-hop dependency subgraph.

        ``dep`` is a ``core.subgraph.DependencySubset.arrays`` pytree for
        the same batch type as ``graphs`` (every array traced, so requests
        sharing a bucket signature share one jit trace) and ``betas`` the
        frozen SF weights from :meth:`fusion_betas` under the same
        params/features.  Rows ``dep["node_rows"][:n]`` of the result
        match the same target rows of :meth:`execute` to reassociation
        tolerance: the closure keeps every edge into the hop-``L-1``
        frontier, so requested rows aggregate their full receptive field
        while garbage on deeper-frontier rows only flows into outputs
        nothing reads.
        """
        gather = dep["gather"]
        dg_of = dict(zip((g.metapath for g in graphs), dep["graphs"]))
        if _is_banded(graphs):
            backend = resolve_backend(kernel_backend)

            def step(g, x):
                return na_subset_banded(g.packed, dg_of[g.metapath], x,
                                        backend=backend)
        else:
            def step(g, x):
                dg = dg_of[g.metapath]
                return _na_edges(x, dg["src"], dg["dst"],
                                 gather[g.dst_type].shape[0])

        h = self._layers(params, self._inputs(features, gather), graphs,
                         self._per_graph(graphs, step), betas=betas)
        return self._head(params, h, dep["node_rows"])

    def execute(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List,
        *,
        kernel_backend: Optional[str] = None,
    ) -> jax.Array:
        """Full GFP stage; returns logits for ``cfg.target_type`` vertices.

        The implementation behind ``repro.api.CompiledHGNN.forward`` —
        callers should compile through a ``repro.api.Session``, which
        builds the batches an ``ExecutorSpec`` names.  See
        :meth:`hidden_states` for how the batches pick the NA step.
        """
        h = self.hidden_states(params, features, graphs,
                               kernel_backend=kernel_backend)
        return self._head(params, h)

    def execute_subset(
        self,
        params: Dict,
        features: Dict[str, jax.Array],
        graphs: List,
        node_ids: jax.Array,
        *,
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
                               kernel_backend=kernel_backend)
        return self._head(params, h, node_ids)

    def execute_loss(self, params, features, graphs, labels: jax.Array,
                     mask: Optional[jax.Array] = None, *,
                     kernel_backend: Optional[str] = None) -> jax.Array:
        """Masked cross-entropy over ``cfg.target_type`` vertices
        (semi-supervised node classification).  Differentiable on both
        batch types: ``jax.grad`` of this loss over banded batches
        matches its gradients over edge-list batches to float tolerance."""
        logits = self.execute(params, features, graphs,
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
