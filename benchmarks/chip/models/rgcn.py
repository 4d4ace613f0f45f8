"""R-GCN (Schlichtkrull et al. 2018, arXiv:1703.06103) as metapath NA:
the mean of the ``w_rel``-projected source rows of each destination."""
from __future__ import annotations

from chipbench.params import dense
from chipbench.work import F32, IDX


def program_config(cfg):
    return {}


def draw_na(keys, cfg, h):
    return {"w_rel": dense(next(keys), h, h)}


def draw_layer(keys, cfg, mps):
    return {}


def na(dot, lp, i, mp, hs, h_dst, src, dst, n_dst):
    import jax
    import jax.numpy as jnp

    summed = jax.ops.segment_sum(hs[src], dst, num_segments=n_dst)
    deg = jax.ops.segment_sum(jnp.ones(dst.shape, jnp.float32), dst, num_segments=n_dst)
    return summed / jnp.maximum(deg, 1.0)[:, None]


def na_flops(n_src, n_dst, e, h):
    # relation projection, then one add per edge and hidden unit
    return 2 * n_src * h * h + e * h


def na_kernel_calls(e, u_src, u_dst, h):
    return {"na_seg_sum": (2 * e * h, F32 * h * (u_src + u_dst) + 2 * IDX * e)}
