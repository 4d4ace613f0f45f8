"""The work the algorithm needs, counted from the graph's shapes.

These counts are the yardstick for every utilisation and roofline share.
They count useful work only: the matrix products of FP, NA, SF and the
head, and the per-edge work of NA (``edges x hidden x 2`` for the
aggregation, plus the attention logits and softmax).  They never count
the one-hot products a kernel may use, the padding slots of its edge
blocks, or vertex types whose states cannot reach the classified type
(the compiled forward drops them as dead code).  Element-wise work over
vertex rows (biases, activations) is left out: it is under 1% of the
products here.

A kernel's least bytes: each source row it reads once, each destination
row it writes once, and the per-edge indices (and weights) once.
"""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

from chipbench.spec import load_model

F32 = 4
IDX = 4


def live_types(cfg: Dict) -> List[Set[str]]:
    """Per layer, the vertex types whose FP output the classified type's
    final state depends on."""
    mps = cfg["metapaths"]
    need = {cfg["target_type"]}
    out: List[Set[str]] = []
    for _ in range(int(cfg["num_layers"])):
        hp = set(need) | {mp[0] for mp in mps if mp[-1] in need}
        out.append(hp)
        need = hp
    return out[::-1]


def forward_flops(cfg: Dict, nv: Dict[str, int], edges: Dict[str, int]) -> int:
    """Useful FLOPs of one full-graph forward."""
    h, c, att = int(cfg["hidden"]), int(cfg["num_classes"]), int(cfg["sf_att_dim"])
    model = load_model(cfg["model"])
    total = 0
    for layer, live in enumerate(live_types(cfg)):
        for t in live:
            d_in = (int(cfg["features"][t]) or 1) if layer == 0 else h
            total += 2 * nv[t] * d_in * h
        # the types whose SF output the next layer (or the head) reads
        nxt = (live_types(cfg) + [{cfg["target_type"]}])[layer + 1]
        for t in nxt:
            mps = [mp for mp in cfg["metapaths"] if mp[-1] == t]
            for mp in mps:
                total += model.na_flops(nv[mp[0]], nv[t], edges[mp], h)
            total += 2 * nv[t] * h * h  # self path
            if mps:
                p1 = len(mps) + 1
                total += p1 * nv[t] * (2 * h * att + 2 * att)  # fusion scores
                total += 2 * p1 * nv[t] * h  # beta-weighted sum
    total += 2 * nv[cfg["target_type"]] * h * c
    return total


def na_kernel_work(cfg: Dict, nv: Dict[str, int],
                   shapes: Dict[str, Tuple[int, int, int]]) -> Dict[str, List[Tuple[int, int]]]:
    """``{kernel: [(flops, bytes), ...]}``, one entry per call in one
    forward.  ``shapes[mp] = (edges, distinct sources, distinct
    destinations)``; the model's NA kernels (``na_kernel_calls`` of its
    file) run for every metapath in every layer."""
    h = int(cfg["hidden"])
    model = load_model(cfg["model"])
    calls: Dict[str, List[Tuple[int, int]]] = {}
    for _ in range(int(cfg["num_layers"])):
        for mp in sorted(cfg["metapaths"]):
            for kernel, call in model.na_kernel_calls(*shapes[mp], h).items():
                calls.setdefault(kernel, []).append(call)
    return calls


def least_seconds(flops: int, nbytes: int, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / float(peak["flops_per_s"]), nbytes / float(peak["hbm_bytes_per_s"]))
