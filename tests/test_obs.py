"""Scopes, the op -> scope map and the counters the benchmark reads
(``repro.obs``), on the CPU at tiny sizes.

Every op that the HGNN layer loops trace must lie under one scope of the
grammar ``layer<i>/{fp,na,sf}/<name>`` or ``head``, and each NA kernel
under its own ``layer<i>/na/<metapath>``; the scopes must change nothing
but HLO metadata; the packing's block fill and the frontend's stage clock
must count what they say.
"""
import contextlib
import gc
import re
import weakref

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import ExecutorSpec, Session, device_features
from repro.core.hgnn import HGNNConfig
from repro.hetero import make_dataset
from repro.kernels.seg_sum import pack_edge_blocks

METAPATHS = ["MAM", "MDM", "MKM"]
GRAMMAR = re.compile(r"^(layer\d+/(fp|sf)/\w+|layer\d+/na/[\w+]+|head)$")
KERNEL = re.compile(r"/(na_seg_sum|na_softmax_stats)/")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(?:\(.*?\)|\S+)\s+([\w\-]+)\((.*?)\)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _cfg(model):
    return HGNNConfig(model=model, hidden=8, num_layers=2, num_classes=3,
                      target_type="M", edge_emb_dim=4, sf_att_dim=8)


@pytest.fixture(scope="module")
def graph():
    return make_dataset("IMDB", scale=0.05)


@pytest.fixture(scope="module")
def compiled(graph):
    """A tiny banded model of each attention kind, forward run once."""
    sess = Session(ExecutorSpec(na_executor="banded"))
    out = {}
    for model in ("rgat", "shgn"):
        c = sess.compile(graph, METAPATHS, _cfg(model))
        params = c.init(0)
        c.forward(params, device_features(graph)).block_until_ready()
        out[model] = (c, params)
    return out


def _instructions(text):
    """(name, opcode, op_name or None) of every instruction in the module."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            yield m.group(1), m.group(2), op.group(1) if op else None


def _assert_scoped(text, metapaths):
    """Every traced op maps to the grammar; each kernel op sits under an
    NA scope, and every (layer, metapath) runs both kernels."""
    scope_of = obs.scopes_of_hlo(text)
    assert scope_of and all(GRAMMAR.match(s) for s in scope_of.values())
    kernels = set()
    for name, opcode, op_name in _instructions(text):
        if opcode == "parameter" or not (op_name or "").startswith("jit("):
            continue
        assert name in scope_of, (name, op_name)
        assert scope_of[name] in op_name
        k = KERNEL.search(op_name)
        if k:
            assert "/na/" in scope_of[name], (name, op_name)
            kernels.add((scope_of[name], k.group(1)))
    assert kernels == {(obs.na_scope(li, mp), k) for li in range(2) for mp in metapaths
                       for k in ("na_seg_sum", "na_softmax_stats")}
    return scope_of


@pytest.mark.parametrize("model", ["rgat", "shgn"])
def test_banded_forward_ops_map_to_scopes(compiled, model):
    c, _ = compiled[model]
    scope_of = obs.forward_scopes(c)
    assert scope_of == _assert_scoped(c.forward_executable().as_text(), METAPATHS)
    wanted = {obs.fp_scope(li, "M") for li in range(2)} | {
        obs.sf_scope(li, "M") for li in range(2)} | {obs.HEAD}
    assert wanted <= set(scope_of.values())


def test_dependency_subset_forward_ops_map_to_scopes(compiled, graph):
    c, params = compiled["shgn"]
    feats = device_features(graph)
    sub = c.dependency_subset(np.array([0, 3, 5]))
    betas = c.model.fusion_betas(params, feats, c.graphs)

    def dep(p, f, b, arrays):
        return c.model.execute_dependency_subset(p, f, c.graphs, arrays, b)

    text = jax.jit(dep).lower(params, feats, betas, sub.arrays).compile().as_text()
    scope_of = obs.scopes_of_hlo(text)
    assert obs.HEAD in scope_of.values()
    for name, opcode, op_name in _instructions(text):
        if opcode != "parameter" and (op_name or "").startswith("jit("):
            assert GRAMMAR.match(scope_of[name]), (name, op_name)
            if KERNEL.search(op_name):
                assert "/na/" in scope_of[name]


def test_sharded_forward_ops_map_to_scopes(graph):
    sess = Session(ExecutorSpec(na_executor="banded", shard="relation"))
    c = sess.compile(graph, METAPATHS, _cfg("shgn"))
    c.forward(c.init(0), device_features(graph)).block_until_ready()
    scope_of = obs.scopes_of_hlo(c.forward_executable().as_text())
    merged = "+".join(sorted(METAPATHS))
    assert {obs.na_scope(li, merged) for li in range(2)} <= set(scope_of.values())
    assert {obs.na_scope(li, mp) for li in range(2) for mp in METAPATHS} <= set(
        scope_of.values())
    assert all(GRAMMAR.match(s) for s in scope_of.values())


def test_scopes_change_only_metadata(compiled, graph, monkeypatch):
    """The compiled forward holds the same instructions with and without
    its scopes: a scope is HLO metadata, free when nobody traces."""
    c, params = compiled["shgn"]
    feats = device_features(graph)

    def hlo():
        def fwd(p, f):  # a new function each time: no trace is reused
            return c.model.execute(p, f, c.graphs)
        text = jax.jit(fwd).lower(params, feats).compile().as_text()
        body = text[text.index("\n%"):]  # past the stack-frame tables
        return re.sub(r",? metadata=\{[^}]*\}", "", body), text

    with_scopes, raw = hlo()
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    without, raw_without = hlo()
    assert "layer0/na/MAM" in raw and "layer0/na/MAM" not in raw_without
    assert with_scopes == without


def test_scopes_of_hlo_by_hand():
    text = "\n".join([
        "%fused_computation (param_0: f32[4]) -> f32[4] {",
        "  %param_0 = f32[4]{0} parameter(0)",
        '  ROOT %mul.1 = f32[4]{0} multiply(%param_0, %param_0), '
        'metadata={op_name="jit(f)/layer1/sf/M/mul"}',
        "}",
        "",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        '  %x = f32[4]{0} parameter(0), metadata={op_name="x"}',
        '  %dot.2 = f32[4]{0} dot(%x, %x), metadata={op_name="jit(f)/layer0/na/MAM/dot_general"}',
        "  %fusion.3 = f32[4]{0} fusion(%dot.2), kind=kLoop, calls=%fused_computation",
        "  %copy.4 = f32[4]{0} copy(%fusion.3)",
        "  %constant.5 = f32[] constant(0)",
        "  %copy.6 = f32[] copy(%constant.5)",
        '  ROOT %add.7 = f32[4]{0} add(%copy.4, %copy.4), '
        'metadata={op_name="jit(f)/head/add"}',
        "}",
    ])
    assert obs.scopes_of_hlo(text) == {
        "mul.1": "layer1/sf/M", "dot.2": "layer0/na/MAM", "fusion.3": "layer1/sf/M",
        "copy.4": "layer1/sf/M", "add.7": "head"}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(fwd)/layer0/na/MAM/jit(_seg_sum_call)/na_seg_sum", "layer0/na/MAM"),
    ("jit(fwd)/layer12/fp/K/dot_general", "layer12/fp/K"),
    ("jit(f)/head/add", "head"),
    ("jit(step)/transpose(jvp(layer1/sf/M))/mul", "layer1/sf/M"),
    ("jit(f)/layer0/na/MAM+MDM/psum", "layer0/na/MAM+MDM"),
    ("jit(f)/header/add", None),
    ("reduce_sum", None),
])
def test_scope_of_op_name(op_name, scope):
    assert obs.scope_of_op_name(op_name) == scope


def test_registry_is_weak(graph):
    sess = Session(ExecutorSpec(na_executor="banded"))
    c = sess.compile(graph, ["MAM"], _cfg("rgat"))
    assert c in obs.live_models() and not c.forward_built
    assert c.forward_executable() is None and obs.forward_scopes(c) is None
    ref = weakref.ref(c)
    del c, sess
    gc.collect()
    assert ref() is None


def test_block_fill_by_hand():
    # dst tiles of 2 rows, bands of 4 sources, blocks of 4 edges: the
    # stream cuts into blocks of 4, 1 (tile change), 2 (band change)
    src = np.array([0, 1, 2, 3, 1, 4, 5])
    dst = np.array([0, 0, 1, 1, 1, 2, 3])
    pk = pack_edge_blocks(src, dst, 8, 4, edge_block=4, src_band=4, dst_tile=2)
    assert pk.count.tolist() == [4, 1, 2]
    assert (pk.num_edges, pk.num_blocks, pk.num_slots) == (7, 3, 12)
    assert pk.fill == pytest.approx(7 / 12)
    empty = pack_edge_blocks(np.zeros(0), np.zeros(0), 8, 4)
    assert (empty.num_slots, empty.fill) == (0, 0.0)


def test_packing_counts_per_metapath(compiled):
    c, _ = compiled["rgat"]
    counts = c.packing_counts()
    assert sorted(counts) == METAPATHS
    for g in c.graphs:
        n = counts[g.metapath]
        assert n["edges"] == g.packed.num_edges == g.packed.edge_block_id.shape[0]
        assert n["slots"] == n["blocks"] * g.packed.edge_block
        assert n["fill"] == pytest.approx(n["edges"] / n["slots"])


def test_frontend_clock_covers_the_banded_build(graph):
    sess = Session(ExecutorSpec(na_executor="banded"))
    c = sess.compile(graph, METAPATHS, _cfg("rgat"))
    t = c.frontend.timings
    assert {"sgb", "restructure", "pack", "banded", "total"} <= set(t)
    assert t["total"] == pytest.approx(t["sgb"] + t["restructure"] + t["pack"] + t["banded"])
    again = sess.compile(graph, METAPATHS, _cfg("shgn"))  # banded batches reused
    assert again.frontend.timings == t
    assert Session(ExecutorSpec()).compile(graph, METAPATHS, _cfg("rgat")).packing_counts() == {}
