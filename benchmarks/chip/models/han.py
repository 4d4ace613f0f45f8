"""HAN (Wang et al. 2019, arXiv:1903.07293) as the program runs it: K-head
node-level attention over each destination's metapath neighbours.  Head k
scores ``LeakyReLU(a_src[k] . hs_j[k] + a_dst[k] . hd_i[k])``, where ``hs``
and ``hd`` are the source and destination rows projected by ``w_rel`` and
``x[k]`` is the k-th block of ``hidden / K`` columns of a row; the softmax of
the scores over i's neighbours weighs head k's block of the source rows,
and the heads' sums, concatenated, pass through an ELU.  The weighted rows
are summed a chunk of edges at a time, so that no ``(edges, hidden)`` array
of a whole semantic graph is held at once."""
from __future__ import annotations

from chipbench.params import dense, small
from chipbench.reference import edge_softmax
from chipbench.work import F32, IDX

HEADS = 8  # HAN's K (Section 5.2); the work counts assume it
LEAKY_SLOPE = 0.2
CHUNK = 1 << 20  # edges per partial sum


def heads(cfg):
    return int(cfg.get("num_heads", HEADS))


def program_config(cfg):
    return {"num_heads": heads(cfg)}


def draw_na(keys, cfg, h):
    k = heads(cfg)
    return {"w_rel": dense(next(keys), h, h), "a_src": small(next(keys), k, h // k),
            "a_dst": small(next(keys), k, h // k)}


def draw_layer(keys, cfg, mps):
    return {}


def scores(dot, x, a):
    """``(rows, K)``: head k's score of each row of ``x``, ``x[k] . a[k]``."""
    import jax.numpy as jnp

    k, dh = a.shape
    return jnp.concatenate([dot(x[:, j * dh:(j + 1) * dh], a[j][:, None])
                            for j in range(k)], axis=1)


def na(dot, lp, i, mp, hs, h_dst, src, dst, n_dst):
    import jax
    import jax.numpy as jnp

    na_p = lp["na"][mp]
    k, dh = na_p["a_src"].shape
    hd = dot(h_dst, na_p["w_rel"])
    logit = scores(dot, hs, na_p["a_src"])[src] + scores(dot, hd, na_p["a_dst"])[dst]
    logit = jnp.where(logit >= 0, logit, LEAKY_SLOPE * logit)
    alpha = edge_softmax(logit, dst, n_dst)  # (E, K)
    out = jnp.zeros((n_dst, k * dh), jnp.float32)
    for c in range(0, src.shape[0], CHUNK):
        s, d, a = src[c:c + CHUNK], dst[c:c + CHUNK], alpha[c:c + CHUNK]
        rows = hs[s].reshape(-1, k, dh) * a[:, :, None]
        out = out + jax.ops.segment_sum(rows.reshape(-1, k * dh), d, num_segments=n_dst)
    return jax.nn.elu(out)


def na_flops(n_src, n_dst, e, h):
    # relation projection (a metapath starts and ends at one type, so the
    # destination's projected rows are the sources'), the heads' source and
    # destination score dots (2 h per row over all heads), per edge and
    # head the logit add/scale/leaky (3) and softmax max/subtract/exp/sum/
    # divide (5), weighted sum (2 h)
    return 2 * n_src * h * h + 2 * n_src * h + 2 * n_dst * h + 8 * e * HEADS + 2 * e * h


def na_kernel_calls(e, u_src, u_dst, h):
    # per edge both kernels read two indices and the K logits (the stats
    # kernel writes them); the stats kernel reads the K-wide score columns
    # of every source and destination and writes (m, s) per destination,
    # which the aggregation reads with each source row and writes each
    # destination row
    per_edge = (2 * IDX + F32 * HEADS) * e
    stats = 2 * F32 * HEADS * u_dst
    return {"na_seg_sum": (2 * e * h, F32 * h * (u_src + u_dst) + per_edge + stats),
            "na_softmax_stats": (8 * e * HEADS,
                                 per_edge + F32 * HEADS * (u_src + u_dst) + stats)}
