"""RGAT as the program runs it: single-head attention over each
destination's metapath neighbours, logit ``LeakyReLU(a_src . hs_j +
a_dst . h_i)`` on the ``w_rel``-projected sources ``hs``."""
from __future__ import annotations

from chipbench.params import dense, small
from chipbench.reference import edge_softmax
from chipbench.work import F32, IDX

LEAKY_SLOPE = 0.2


def program_config(cfg):
    return {}


def draw_na(keys, cfg, h):
    return {"w_rel": dense(next(keys), h, h), "a_src": small(next(keys), h),
            "a_dst": small(next(keys), h)}


def draw_layer(keys, cfg, mps):
    return {}


def logits(dot, na_p, hs, h_dst, src, dst):
    """Each edge's attention logit before the LeakyReLU."""
    e_s = dot(hs, na_p["a_src"][:, None])[:, 0]
    e_d = dot(h_dst, na_p["a_dst"][:, None])[:, 0]
    return e_s[src] + e_d[dst]


def attend(logit, hs, src, dst, n_dst):
    """The sources' rows summed into each destination, weighted by the
    softmax of ``LeakyReLU(logit)`` over its edges."""
    import jax
    import jax.numpy as jnp

    logit = jnp.where(logit >= 0, logit, LEAKY_SLOPE * logit)
    alpha = edge_softmax(logit, dst, n_dst)
    return jax.ops.segment_sum(hs[src] * alpha[:, None], dst, num_segments=n_dst)


def na(dot, lp, i, mp, hs, h_dst, src, dst, n_dst):
    return attend(logits(dot, lp["na"][mp], hs, h_dst, src, dst), hs, src, dst, n_dst)


def na_flops(n_src, n_dst, e, h):
    # relation projection, source and destination logit dots, per-edge
    # logit add/bias/leaky (3), softmax max/subtract/exp/sum/divide (5),
    # weighted sum (2 h)
    return 2 * n_src * h * h + 2 * n_src * h + 2 * n_dst * h + 8 * e + 2 * e * h


def na_kernel_calls(e, u_src, u_dst, h):
    # the aggregation reads a weight per edge besides its two indices; the
    # stats kernel: max, subtract, exp, add per edge, m and s per
    # destination
    return {"na_seg_sum": (2 * e * h, F32 * h * (u_src + u_dst) + (2 * IDX + F32) * e),
            "na_softmax_stats": (4 * e, (F32 + IDX) * e + 2 * F32 * u_dst)}
