"""What the compiled forward runs around the NA kernels, on the CPU at
scale 0.1.

The attention forward builds its logits, softmax stats and alpha inside
the two kernels, so nothing under a ``layer<i>/na/<metapath>`` scope
outside them may scatter, or gather one value per edge or per edge slot:
that per-edge glue was 81% of the IMDB Simple-HGN forward on the chip.
The mean model's forward holds no such op either, and runs one
``na_seg_sum`` call per layer and metapath and no stats kernel.  In
interpret mode each kernel is one ``while`` loop whose ``op_name`` holds
the kernel's name; the ops inside it belong to the kernel, not to the
glue.
"""
import re

import numpy as np
import pytest

from repro.api import ExecutorSpec, Session, device_features
from repro.core.hgnn import HGNNConfig
from repro.hetero import make_dataset

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NA = re.compile(r"/(layer\d+)/na/(\w+)/")
_KERNEL = re.compile(r"/(na_seg_sum|na_softmax_stats)/")

SHAPES = {
    "IMDB": (["MAM", "MDM", "MKM"],
             dict(model="shgn", hidden=8, num_layers=2, num_classes=3,
                  target_type="M", edge_emb_dim=4, sf_att_dim=8)),
    "DBLP": (["APA", "APTPA"],
             dict(model="rgcn", hidden=8, num_layers=2, num_classes=4,
                  target_type="A")),
}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def forward(request):
    """(compiled model, the CPU HLO of its banded forward)."""
    metapaths, cfg = SHAPES[request.param]
    graph = make_dataset(request.param, scale=0.1)
    c = Session(ExecutorSpec(na_executor="banded")).compile(graph, metapaths,
                                                            HGNNConfig(**cfg))
    c.forward(c.init(0), device_features(graph)).block_until_ready()
    return c, c.forward_executable().as_text()


def _na_ops(text):
    """(metapath, opcode, result shape, kernel name or None) of every
    instruction under a ``layer<i>/na/<metapath>`` scope."""
    for line in text.splitlines():
        m, op = _INSTR.match(line), _OP_NAME.search(line)
        if not (m and op):
            continue
        na = _NA.search(op.group(1))
        if na:
            k = _KERNEL.search(op.group(1))
            yield na.group(2), m.group(3), m.group(2), k and k.group(1)


def _elements(shape):
    """Elements of an HLO result shape such as ``f32[87,256]{1,0}``."""
    dims = re.findall(r"\[([\d,]*)\]", shape)
    return [int(np.prod([int(d) for d in ds.split(",") if d])) for ds in dims]


def test_na_glue_holds_no_per_edge_op(forward):
    c, text = forward
    per_edge = {g.metapath: {g.packed.num_edges, g.packed.num_slots}
                for g in c.graphs}
    seen = set()
    for mp, opcode, shape, kernel in _na_ops(text):
        seen.add(mp)
        if kernel:
            continue
        assert opcode != "scatter", (mp, shape)
        if opcode == "gather":
            assert not set(_elements(shape)) & per_edge[mp], (mp, shape)
    assert seen == set(per_edge)


def test_kernel_calls_per_layer_and_metapath(forward):
    """One ``while`` loop per kernel call: both kernels once per layer and
    metapath for attention; for the mean model ``na_seg_sum`` alone, as
    many calls as before the attention kernels changed."""
    c, text = forward
    calls = {}
    for mp, opcode, _, kernel in _na_ops(text):
        if kernel and opcode == "while":
            calls[kernel] = calls.get(kernel, 0) + 1
    pairs = c.cfg.num_layers * len(c.graphs)
    if c.cfg.model == "shgn":
        assert calls == {"na_seg_sum": pairs, "na_softmax_stats": pairs}
    else:
        assert calls == {"na_seg_sum": pairs}
