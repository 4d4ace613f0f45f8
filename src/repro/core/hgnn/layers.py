"""GFP sub-stage primitives: Feature Projection, Neighbor Aggregation,
Semantic Fusion.

Two NA families live here:

  * the pure-jnp primitives (``na_mean`` / ``na_attention``) take global
    (src, dst) edge index arrays and run ``jax.ops.segment_*`` — the
    layout-agnostic oracle path.  The Graph Restructurer only *reorders*
    those arrays; the math is unchanged, so original and restructured
    layouts agree to floating-point reassociation.  Per-destination
    softmax uses segment max/sum over global dst ids and therefore stays
    exact across the three subgraphs even though a backbone destination's
    edges span two of them.
  * the banded primitives (``na_mean_banded`` / ``na_attention_banded``)
    consume the restructurer's cached ``PackedEdges`` blocks and run the
    Pallas NA kernels (kernels/seg_sum.py, kernels/edge_softmax.py) over
    features permuted into the renumbered banded layout — the executed
    form of the paper's GFP stage.

The banded attention forward does only per-vertex work in jnp (the two
score columns); the logits, the softmax stats and alpha are made inside
the two kernels.  Both families are differentiable end to end: the jnp
primitives by construction, the banded ones through the custom VJPs the
kernels carry (backward is a jnp gather/segment-add over the packing's
cached edge map — see kernels/seg_sum.py and kernels/ops.py), so
``jax.grad`` of a model loss agrees between executors to float
tolerance.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import use_interpret
from repro.kernels.ops import na_attention_packed
from repro.kernels.seg_sum import PackedEdges, seg_sum_na


def feature_projection(w: jax.Array, b: jax.Array, x: jax.Array) -> jax.Array:
    """FP sub-stage: per-type dense projection (the MLP of §2.2)."""
    return x @ w + b


def na_mean(
    h_src: jax.Array,  # (N_src, D) projected source features
    src: jax.Array,  # (E,) int32
    dst: jax.Array,  # (E,) int32
    num_dst: int,
) -> jax.Array:
    """RGCN-style NA: degree-normalized sum of neighbour features."""
    gathered = h_src[src]  # (E, D)
    summed = jax.ops.segment_sum(gathered, dst, num_segments=num_dst)
    deg = jax.ops.segment_sum(jnp.ones_like(dst, jnp.float32), dst, num_segments=num_dst)
    return summed / jnp.maximum(deg, 1.0)[:, None]


def edge_softmax_weights(
    logits: jax.Array,  # (E,) unnormalized attention logits
    dst: jax.Array,  # (E,)
    num_dst: int,
) -> jax.Array:
    """Numerically-stable softmax over each destination's in-edges."""
    m = jax.ops.segment_max(logits, dst, num_segments=num_dst)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    ex = jnp.exp(logits - m[dst])
    s = jax.ops.segment_sum(ex, dst, num_segments=num_dst)
    return ex / jnp.maximum(s[dst], 1e-9)


def na_attention(
    h_src: jax.Array,  # (N_src, D)
    h_dst: jax.Array,  # (N_dst, D) destination-side features for logits
    src: jax.Array,
    dst: jax.Array,
    num_dst: int,
    a_src: jax.Array,  # (D,) attention vector, source side
    a_dst: jax.Array,  # (D,) attention vector, destination side
    edge_bias: Optional[jax.Array] = None,  # scalar or (E,) edge-type term (Simple-HGN)
    leaky_slope: float = 0.2,
) -> jax.Array:
    """GAT-style NA (RGAT / Simple-HGN): weighted sum with edge softmax."""
    e_s = h_src @ a_src  # (N_src,)
    e_d = h_dst @ a_dst  # (N_dst,)
    logits = e_s[src] + e_d[dst]
    if edge_bias is not None:
        logits = logits + edge_bias
    logits = jax.nn.leaky_relu(logits, leaky_slope)
    alpha = edge_softmax_weights(logits, dst, num_dst)
    weighted = h_src[src] * alpha[:, None]
    return jax.ops.segment_sum(weighted, dst, num_segments=num_dst)


def na_mean_banded(
    packed: PackedEdges,
    h_src: jax.Array,  # (N_src, D) features in the packing's banded numbering
    deg: jax.Array,  # (N_dst,) in-degrees in the packing's dst numbering
    backend: Optional[str] = None,
) -> jax.Array:
    """RGCN-style NA on the banded Pallas kernel (dst rows banded too)."""
    summed = seg_sum_na(packed, h_src, interpret=use_interpret(backend))
    return summed / jnp.maximum(deg, 1.0)[:, None]


def na_attention_banded(
    packed: PackedEdges,
    h_src: jax.Array,  # (N_src, D) banded-numbered source features
    h_dst: jax.Array,  # (N_dst, D) banded-numbered destination features
    a_src: jax.Array,
    a_dst: jax.Array,
    edge_bias: Optional[jax.Array] = None,  # scalar edge-type term (Simple-HGN)
    leaky_slope: float = 0.2,
    backend: Optional[str] = None,
) -> jax.Array:
    """GAT-style NA on the attention kernels (``kernels.ops.na_attention_packed``).

    Same math as ``na_attention``, with only per-vertex work outside the
    kernels: the two score columns ``h_src @ a_src`` and ``h_dst @ a_dst``,
    the scalar bias folded into the destination's.  The logits, the
    online (m, s) stats and alpha are made per edge slot inside the
    kernels, from the packing's blocks.
    """
    e_d = h_dst @ a_dst
    if edge_bias is not None:
        e_d = e_d + edge_bias
    return na_attention_packed(packed, h_src @ a_src, e_d, h_src, leaky_slope,
                               backend=backend)


def semantic_fusion_beta(
    z_stack: jax.Array,  # (P, N, D) NA outputs per semantic graph
    w: jax.Array,  # (D, D_att)
    b: jax.Array,  # (D_att,)
    q: jax.Array,  # (D_att,)
) -> jax.Array:
    """The (P,) semantic-attention weights of :func:`semantic_fusion`.

    beta_p = softmax_p( mean_v q . tanh(W z_p,v + b) ).  The mean runs
    over *all* rows of the type, which makes beta a graph-level statistic
    (no per-row dependence) — the dependency-subset executor exploits
    exactly this by freezing betas from one full calibration forward
    (``HGNN.fusion_betas``) instead of re-deriving them from a partial
    row set.
    """
    s = jnp.tanh(z_stack @ w + b) @ q  # (P, N)
    return jax.nn.softmax(jnp.mean(s, axis=1))  # (P,)


def semantic_fusion(
    z_stack: jax.Array,  # (P, N, D) NA outputs per semantic graph
    w: jax.Array,  # (D, D_att)
    b: jax.Array,  # (D_att,)
    q: jax.Array,  # (D_att,)
) -> jax.Array:
    """SF sub-stage (HAN-style semantic attention, §2.2).

    beta_p = softmax_p( mean_v q . tanh(W z_p,v + b) ); out = sum_p beta_p z_p.
    """
    beta = semantic_fusion_beta(z_stack, w, b, q)
    return jnp.einsum("p,pnd->nd", beta, z_stack)
