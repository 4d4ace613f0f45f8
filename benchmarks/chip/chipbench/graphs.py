"""The benchmark's graphs: synthetic heterogeneous graphs built from a config.

The topology follows the program's synthetic dataset generator
(power-law out-degrees, planted communities, Zipf hubs), kept here so
that no later change to the program can change the graph a cell
measures.  Unlike the program's, it draws until each relation holds the
number of distinct edges the configuration states: the published
dataset's count.  A configuration file states the dataset's vertex
counts, feature widths and per-relation edge counts, and a fixed
``graph_seed``: the graph is the same in every run, as a real dataset
is, so packed shapes and compiled programs repeat.

Features are drawn on the device in one jitted call from the same seed.

``semantic_graph`` composes a metapath from the one-hop relations with a
sparse boolean product: the reference's own semantic-graph build, which
shares no code with the program's.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import numpy as np

IDX = np.int32


def _powerlaw_degrees(rng: np.random.Generator, n: int, total: int, cap: int,
                      alpha: float = 2.1) -> np.ndarray:
    """Power-law out-degrees of ``n`` sources, none over ``cap``, summing
    to ``total``: the draw scaled so that, clipped at ``cap``, it sums to
    ``total``, then rounded so that the sum is exact."""
    raw = rng.pareto(alpha - 1.0, size=n) + 1.0
    lo, hi = 0.0, total / raw.min()
    for _ in range(200):
        c = (lo + hi) / 2
        lo, hi = (c, hi) if np.minimum(c * raw, cap).sum() < total else (lo, c)
    deg = np.minimum(hi * raw, cap)
    out = np.floor(deg).astype(np.int64)
    extra = np.argsort(out - deg, kind="stable")[: total - int(out.sum())]
    out[extra] += 1
    return out


def _bipartite_edges(rng: np.random.Generator, num_src: int, num_dst: int,
                     num_edges: int, p_in: float = 0.75
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Exactly ``num_edges`` distinct ``(src, dst)`` pairs, canonical.

    Each source gets a power-law out-degree, at most ten times the mean
    and at least a community's size (48), and draws that many distinct
    destinations: with probability ``p_in`` one of its planted
    community's, else one by Zipf popularity over all of them.  Pairs
    drawn twice are drawn again."""
    n_comm = max(2, num_dst // 48)
    cap = min(num_dst, max(48, int(np.ceil(10 * num_edges / num_src))))
    if num_edges > num_src * cap:
        raise ValueError(f"{num_edges} edges over {num_src} x {num_dst} vertices")
    deg = _powerlaw_degrees(rng, num_src, num_edges, cap)
    comm_src = rng.integers(0, n_comm, size=num_src)
    comm_dst = rng.integers(0, n_comm, size=num_dst)
    order = np.argsort(comm_dst, kind="stable")
    sorted_comm = comm_dst[order]
    starts = np.searchsorted(sorted_comm, np.arange(n_comm))
    ends = np.searchsorted(sorted_comm, np.arange(n_comm), side="right")
    w = 1.0 / (np.arange(1, num_dst + 1) ** 0.8)
    w = rng.permutation(w)
    w /= w.sum()

    keys = np.empty(0, np.int64)
    for _ in range(10_000):
        short = deg - np.bincount(keys // num_dst, minlength=num_src)
        if not short.any():
            break
        src = np.repeat(np.arange(num_src), short)
        lo, hi = starts[comm_src[src]], ends[comm_src[src]]
        in_comm = (rng.random(src.size) < p_in) & (hi > lo)
        pos = lo + (rng.random(src.size) * (hi - lo)).astype(np.int64)
        dst_in = order[np.minimum(pos, np.maximum(lo, hi - 1))]
        dst_glob = rng.choice(num_dst, size=src.size, p=w)
        keys = np.unique(np.concatenate(
            [keys, src * num_dst + np.where(in_comm, dst_in, dst_glob)]))
    else:
        raise RuntimeError(f"could not draw {num_edges} distinct edges")
    return (keys // num_dst).astype(IDX), (keys % num_dst).astype(IDX)


def _canonical(num_dst: int, src: np.ndarray, dst: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Sorted by (src, dst), duplicates removed."""
    key = np.unique(src.astype(np.int64) * num_dst + dst.astype(np.int64))
    return (key // num_dst).astype(IDX), (key % num_dst).astype(IDX)


def topology(cfg: Dict) -> Tuple[Dict[str, int], Dict[str, Tuple[np.ndarray, np.ndarray]]]:
    """``(num_vertices, relations)`` of a config's graph; ``relations``
    maps ``"AP"`` to canonical ``(src, dst)`` arrays, every relation with
    its reverse (a self-relation is merged with its reverse)."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [zlib.crc32(cfg["dataset"].encode()), int(cfg["graph_seed"])]))
    scale = float(cfg["scale"])
    nv = {t: max(2, int(round(c * scale))) for t, c in cfg["vertices"].items()}
    rels: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
    for s, d, count in cfg["relation_edges"]:
        # a cut in scale keeps each relation's density
        n = max(1, int(round(int(count) * scale * scale)))
        src, dst = _bipartite_edges(rng, nv[s], nv[d], n)
        if s != d:
            rels[s + d] = (src, dst)
            rels[d + s] = _canonical(nv[s], dst, src)
        else:
            rels[s + d] = _canonical(nv[d], np.concatenate([src, dst]),
                                     np.concatenate([dst, src]))
    return nv, rels


def semantic_graph(nv: Dict[str, int], rels: Dict, metapath: str
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Canonical ``(src, dst)`` of a metapath: the boolean product of its
    one-hop relations (an edge wherever some path of that type joins the
    two end vertices)."""
    import scipy.sparse as sp

    def adj(a: str, b: str):
        s, d = rels[a + b]
        return sp.csr_matrix((np.ones(s.size, np.float32), (s, d)),
                             shape=(nv[a], nv[b]))

    m = adj(metapath[0], metapath[1])
    for a, b in zip(metapath[1:-1], metapath[2:]):
        m = (m @ adj(a, b)).tocsr()
        m.data[:] = 1.0
    coo = m.tocoo()
    return _canonical(nv[metapath[-1]], coo.row.astype(IDX), coo.col.astype(IDX))


def semantic_graphs(nv: Dict[str, int], rels: Dict, metapaths: List[str]
                    ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    return {mp: semantic_graph(nv, rels, mp) for mp in metapaths}


def make_features(cfg: Dict, nv: Dict[str, int]):
    """Raw features of every featured type, drawn on the device from the
    graph seed in one jitted call (N(0, 0.1^2), float32)."""
    import jax
    import jax.numpy as jnp

    shapes = {t: (nv[t], int(d)) for t, d in sorted(cfg["features"].items()) if int(d) > 0}

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(shapes))
        return {t: jax.random.normal(k, shp, jnp.float32) * 0.1
                for k, (t, shp) in zip(keys, shapes.items())}

    return draw(jax.random.key(int(cfg["graph_seed"])))
