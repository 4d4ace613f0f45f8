"""One benchmark per paper table/figure (see DESIGN.md §6 for the index).

Each function returns CSV rows ``name,us_per_call,derived``; ``derived``
carries the figure's headline quantity (speedup / reduction / rate).
"""
from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.common import (BUFFER_BYTES, CYCLE_MODEL, FEATURE_DIM,
                               gfp_cycles, na_streams, row, timed)
from repro.core.buffersim import simulate_na
from repro.core.sgb import execute_plan, plan_ctt, plan_ctt_dp, plan_naive
from repro.hetero import make_dataset

DATASETS = ("ACM", "DBLP", "IMDB")
SGB_SCALE = 0.25  # sub-sampled graphs keep long-metapath sweeps tractable
MAX_TARGETS = 8


def _targets(g, hops: int) -> List[str]:
    return [m for m in g.enumerate_metapaths(hops) if len(m) == hops + 1][:MAX_TARGETS]


# Fig. 2 — #semantic graphs + SGB time vs metapath length -------------------
def bench_sgb_scaling() -> List[str]:
    g = make_dataset("ACM", scale=SGB_SCALE)
    out = []
    base = None
    for hops in (2, 3, 4, 5):
        targets = _targets(g, hops)
        if not targets:
            continue
        res, us = timed(lambda: execute_plan(g, plan_naive(g, targets)))
        base = base or us
        n_graphs = len(g.enumerate_metapaths(hops))
        out.append(row(f"fig2/sgb_scaling/hops{hops}", us,
                       f"graphs={n_graphs};norm_time={us / base:.2f}"))
    return out


# Fig. 14 — SGB speedup with/without the Semantic Graph Builder -------------
def bench_ctt_speedup() -> List[str]:
    out = []
    for ds in DATASETS:
        g = make_dataset(ds, scale=SGB_SCALE)
        for hops in (3, 5, 6):
            targets = _targets(g, hops)
            if not targets:
                continue
            rn, us_n = timed(lambda: execute_plan(g, plan_naive(g, targets)))
            rc, us_c = timed(lambda: execute_plan(g, plan_ctt(g, targets)))
            out.append(row(
                f"fig14/ctt_speedup/{ds}/hops{hops}", us_c,
                f"time_speedup={us_n / max(us_c, 1e-9):.2f}x;"
                f"mac_speedup={rn.cost.macs / max(rc.cost.macs, 1):.2f}x"))
    return out


# Fig. 15 — computation + memory-access reduction from the CTT --------------
def bench_ctt_redundancy() -> List[str]:
    out = []
    for ds in DATASETS:
        g = make_dataset(ds, scale=SGB_SCALE)
        for hops in (3, 5, 6):
            targets = _targets(g, hops)
            if not targets:
                continue
            rn = execute_plan(g, plan_naive(g, targets))
            rc = execute_plan(g, plan_ctt(g, targets))
            rd = execute_plan(g, plan_ctt_dp(g, targets))
            comp_red = 1 - rc.cost.macs / max(rn.cost.macs, 1)
            mem_red = 1 - rc.cost.total_bytes / max(rn.cost.total_bytes, 1)
            dp_red = 1 - rd.cost.macs / max(rn.cost.macs, 1)
            out.append(row(
                f"fig15/ctt_redundancy/{ds}/hops{hops}", 0.0,
                f"compute_reduction={comp_red:.3f};memory_reduction={mem_red:.3f};"
                f"dp_compute_reduction={dp_red:.3f}"))
    return out


# Fig. 3 — NA buffer hit rate (original layout) ------------------------------
def bench_buffer_hitrate() -> List[str]:
    out = []
    for ds in DATASETS:
        g = make_dataset(ds)
        rel = max(g.relations.values(), key=lambda r: r.num_edges)
        (so, do), (sr, dr), _ = na_streams(rel)
        a = simulate_na(so, FEATURE_DIM, BUFFER_BYTES, num_rows=rel.num_src)
        b = simulate_na(sr, FEATURE_DIM, BUFFER_BYTES, num_rows=rel.num_src)
        out.append(row(f"fig3/hitrate/{ds}/{rel.name}", 0.0,
                       f"orig_hit={a.hit_rate:.3f};restructured_hit={b.hit_rate:.3f}"))
    return out


# Fig. 4 — replacement-count histogram ---------------------------------------
def bench_thrashing() -> List[str]:
    out = []
    for ds in DATASETS:
        g = make_dataset(ds)
        rel = max(g.relations.values(), key=lambda r: r.num_edges)
        (so, _), (sr, _), _ = na_streams(rel)
        for tag, stream in (("orig", so), ("restructured", sr)):
            st = simulate_na(stream, FEATURE_DIM, BUFFER_BYTES,
                             num_rows=rel.num_src)
            h = st.replacement_histogram(max_bucket=4)
            v = ";".join(f"v{i}={x:.3f}" for i, x in enumerate(h["vertex_ratio"]))
            a = ";".join(f"a{i}={x:.3f}" for i, x in enumerate(h["access_ratio"]))
            out.append(row(f"fig4/thrashing/{ds}/{tag}", 0.0, v + ";" + a))
    return out


# Fig. 16 — GFP speedup with the Graph Restructurer --------------------------
def bench_gfp_speedup() -> List[str]:
    out = []
    speedups = []
    for ds in DATASETS:
        g = make_dataset(ds)
        # paper §6.2.2 isolates one-hop relations
        for rel in sorted(g.relations.values(), key=lambda r: -r.num_edges)[:3]:
            (so, _), (sr, _), _ = na_streams(rel)
            a = gfp_cycles(rel, so)
            b = gfp_cycles(rel, sr)
            sp = a["cycles"] / max(b["cycles"], 1e-9)
            speedups.append(sp)
            out.append(row(f"fig16/gfp_speedup/{ds}/{rel.name}", 0.0,
                           f"speedup={sp:.2f}x;orig_cycles={a['cycles']:.0f};"
                           f"rest_cycles={b['cycles']:.0f}"))
    geo = float(np.exp(np.mean(np.log(speedups))))
    out.append(row("fig16/gfp_speedup/GEOMEAN", 0.0, f"speedup={geo:.2f}x"))
    return out


# Fig. 17 — normalized DRAM access --------------------------------------------
def bench_dram_access() -> List[str]:
    from repro.kernels.seg_sum import pack_edge_blocks, stream_tile_loads

    out = []
    for ds in DATASETS:
        g = make_dataset(ds)
        rel = max(g.relations.values(), key=lambda r: r.num_edges)
        (so, do), (sr, dr), rg = na_streams(rel)
        a = simulate_na(so, FEATURE_DIM, BUFFER_BYTES, num_rows=rel.num_src)
        b = simulate_na(sr, FEATURE_DIM, BUFFER_BYTES, num_rows=rel.num_src)
        # kernel-level meter: (BAND, D) feature tiles a walk in stream
        # order loads (kernels/seg_sum.py geometry); the restructured
        # LAYOUT (renumbered vertices, permuted feature rows) is what the
        # paper's "semantic graph layout" maps to on TPU.  The packer
        # groups each dst-tile run by band, so its block count is near
        # 1.0x between the two and is printed beside it.
        s2, d2 = rg.scheduled_edges(renumbered=True)
        la, lb = stream_tile_loads(so, do), stream_tile_loads(s2, d2)
        pa = pack_edge_blocks(so, do, rel.num_src, rel.num_dst)
        pb = pack_edge_blocks(s2, d2, rel.num_src, rel.num_dst)
        out.append(row(
            f"fig17/dram/{ds}/{rel.name}", 0.0,
            f"lru_dram_ratio={b.dram_bytes / max(a.dram_bytes, 1):.3f};"
            # one fp32 (BAND, D) tile per load, so the ratio of loads is
            # also the ratio of feature bytes streamed
            f"kernel_tiles_ratio={lb / max(la, 1):.3f};"
            f"kernel_blocks_ratio={pb.num_blocks / max(pa.num_blocks, 1):.3f}"))
    return out


# Fig. 18 — DRAM bandwidth utilization ---------------------------------------
def bench_bandwidth_util() -> List[str]:
    out = []
    for ds in DATASETS:
        g = make_dataset(ds)
        rel = max(g.relations.values(), key=lambda r: r.num_edges)
        (so, _), (sr, _), _ = na_streams(rel)
        for tag, stream in (("orig", so), ("restructured", sr)):
            c = gfp_cycles(rel, stream)
            util = c["dram"] / max(c["cycles"], 1e-9) / CYCLE_MODEL.bytes_per_cycle
            out.append(row(f"fig18/bandwidth/{ds}/{tag}", 0.0,
                           f"util={util:.3f};bytes_per_cycle={c['dram'] / max(c['cycles'], 1e-9):.1f}"))
    return out


# Fig. 12 — overall speedup (SGB + GFP, modeled cycles) ----------------------
def bench_overall_speedup() -> List[str]:
    """Backend alone vs backend + SiHGNN frontend.

    Modeled end-to-end cycles = SGB MAC-cycles + GFP cycles summed over the
    paper's 3/4-hop semantic-graph workload; the frontend applies the CTT
    (SGB) and the Graph Restructurer (GFP).  The SGB datapath is credited
    with the same MAC rate as the backend systolic array.
    """
    out = []
    speedups = []
    for ds in DATASETS:
        g = make_dataset(ds, scale=SGB_SCALE)
        targets = (_targets(g, 3) + _targets(g, 4))[:8]
        rn = execute_plan(g, plan_naive(g, targets))
        rc = execute_plan(g, plan_ctt(g, targets))
        sgb_base = rn.cost.macs / CYCLE_MODEL.macs_per_cycle + \
            rn.cost.total_bytes / CYCLE_MODEL.bytes_per_cycle
        sgb_sih = rc.cost.macs / CYCLE_MODEL.macs_per_cycle + \
            rc.cost.total_bytes / CYCLE_MODEL.bytes_per_cycle
        gfp_base = gfp_sih = 0.0
        for t in targets:
            rel = rn.graphs[t]
            if rel.num_edges == 0:
                continue
            (so, _), (sr, _), _ = na_streams(rel)
            gfp_base += gfp_cycles(rel, so)["cycles"]
            gfp_sih += gfp_cycles(rel, sr)["cycles"]
        sp = (sgb_base + gfp_base) / max(sgb_sih + gfp_sih, 1e-9)
        speedups.append(sp)
        out.append(row(f"fig12/overall/{ds}", 0.0,
                       f"speedup={sp:.2f}x;sgb={sgb_base / max(sgb_sih, 1e-9):.2f}x;"
                       f"gfp={gfp_base / max(gfp_sih, 1e-9):.2f}x"))
    geo = float(np.exp(np.mean(np.log(speedups))))
    out.append(row("fig12/overall/GEOMEAN", 0.0, f"speedup={geo:.2f}x"))
    return out
