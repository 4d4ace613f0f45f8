"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode accepts block shapes and layouts that the TPU's compiler
(Mosaic) refuses, so these tests lower the kernels the main path runs —
the banded seg-sum, the edge-softmax stats, the attention path's stats
and aggregation kernels and the SGB ``spgemm_bsr`` —
for a ``v5e:2x2`` topology that is described, not attached, at the
paper's full-scale shapes, and assert that each compiled program holds a
``tpu_custom_call``.  A small scoped Simple-HGN forward is compiled the
same way, to check that the chip's compiled program keeps the scopes the
profiler trace is read by.  Nothing runs; a pass says the chip's compiler takes
the kernel, not that its results are right.

The topology is described inside a module fixture (never at import
time), because only one process at a time may load the TPU library: the
test workers all collect this file, and only the one that runs it loads
the library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.edge_softmax import _stats_call, attention_stats_call
from repro.kernels.seg_sum import (DST_TILE, EDGE_BLOCK, SRC_BAND, _dense_call,
                                   _seg_sum_call, attention_seg_sum_call)
from repro.kernels.spgemm_bsr import TILE, spgemm_bsr

# The largest packing of each paper graph at scale=1.0 (restructured,
# renumbered; see docs/ARCHITECTURE.md): (edge blocks, src bands, dst tiles)
# for ACM PAP, DBLP APTPA and IMDB MKM.  Features are hidden=64 wide.
PACKINGS = {
    "ACM-PAP": (7520, 6, 24),
    "DBLP-APTPA": (4566, 4, 32),
    "IMDB-MKM": (25177, 10, 39),
}
HIDDEN = 64


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    written for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("graph", sorted(PACKINGS))
def test_seg_sum_kernel_compiles_for_v5e(one_chip, graph):
    nb, bands, tiles = PACKINGS[graph]
    blocks = [_shape(one_chip, (nb,), jnp.int32)] * 3
    per_edge = [_shape(one_chip, (nb, EDGE_BLOCK), jnp.int16)] * 2
    weight = _shape(one_chip, (nb, EDGE_BLOCK), jnp.float32)
    h = _shape(one_chip, (bands * SRC_BAND, HIDDEN), jnp.float32)
    _assert_kernel(
        lambda b, t, f, s, d, w, x: _seg_sum_call(
            b, t, f, s, d, w, x, tiles, SRC_BAND, DST_TILE, False),
        *blocks, *per_edge, weight, h)


def test_dense_seg_sum_kernel_compiles_for_v5e(one_chip):
    """The dense-tile format at DBLP's APVPA at full scale: every one of
    its 32 dst tiles x 8 bands holds an edge."""
    bands, tiles = 8, 32
    pairs = [_shape(one_chip, (bands * tiles,), jnp.int32)] * 3
    a = _shape(one_chip, (bands * tiles, DST_TILE, SRC_BAND), jnp.float32)
    h = _shape(one_chip, (bands * SRC_BAND, HIDDEN), jnp.float32)
    _assert_kernel(lambda b, t, f, x, y: _dense_call(b, t, f, x, y, tiles, False),
                   *pairs, a, h)


@pytest.mark.parametrize("graph", sorted(PACKINGS))
def test_edge_softmax_stats_kernel_compiles_for_v5e(one_chip, graph):
    nb, _, tiles = PACKINGS[graph]
    blocks = [_shape(one_chip, (nb,), jnp.int32)] * 2
    logits = _shape(one_chip, (nb, EDGE_BLOCK), jnp.float32)
    dst_local = _shape(one_chip, (nb, EDGE_BLOCK), jnp.int16)
    valid = _shape(one_chip, (nb, EDGE_BLOCK), jnp.float32)
    _assert_kernel(
        lambda t, f, lg, d, v: _stats_call(t, f, lg, d, v, tiles, DST_TILE, False),
        *blocks, logits, dst_local, valid)


def _attention_operands(one_chip, graph):
    """The attention kernels' shared operands at ``graph``'s packing, as
    the model passes them: three (nb,) block arrays, then the (nb, 1, EB)
    src_local, dst_local (int32) and valid rows."""
    nb, _, _ = PACKINGS[graph]
    blocks = [_shape(one_chip, (nb,), jnp.int32)] * 3
    rows = [_shape(one_chip, (nb, 1, EDGE_BLOCK), jnp.int32)] * 2
    return blocks + rows + [_shape(one_chip, (nb, 1, EDGE_BLOCK), jnp.float32)]


@pytest.mark.parametrize("graph", sorted(PACKINGS))
def test_attention_stats_kernel_compiles_for_v5e(one_chip, graph):
    """The stats kernel that builds each slot's logit from the (rows, 1)
    score columns of its band and tile and writes the blocked logits."""
    _, bands, tiles = PACKINGS[graph]
    e_s = _shape(one_chip, (bands * SRC_BAND, 1), jnp.float32)
    e_d = _shape(one_chip, (tiles * DST_TILE, 1), jnp.float32)
    _assert_kernel(
        lambda *a: attention_stats_call(*a, tiles, SRC_BAND, DST_TILE, 0.2, False),
        *_attention_operands(one_chip, graph), e_s, e_d)


@pytest.mark.parametrize("graph", sorted(PACKINGS))
def test_attention_seg_sum_kernel_compiles_for_v5e(one_chip, graph):
    """The aggregation kernel that forms alpha per slot from the blocked
    logits and the (TD, 1) stats columns of its tile."""
    nb, bands, tiles = PACKINGS[graph]
    logits = _shape(one_chip, (nb, 1, EDGE_BLOCK), jnp.float32)
    stats = [_shape(one_chip, (tiles * DST_TILE, 1), jnp.float32)] * 2
    h = _shape(one_chip, (bands * SRC_BAND, HIDDEN), jnp.float32)
    _assert_kernel(
        lambda *a: attention_seg_sum_call(*a, tiles, SRC_BAND, DST_TILE, False),
        *_attention_operands(one_chip, graph), logits, *stats, h)


def test_spgemm_bsr_compiles_for_v5e(one_chip):
    # ACM's P-A composition step, tile-padded: (3025 x 5959) @ (5959 x 3025)
    m, k = 3072, 6016
    a = _shape(one_chip, (m, k), jnp.float32)
    b = _shape(one_chip, (k, m), jnp.float32)
    a_occ = _shape(one_chip, ((m // TILE) * (k // TILE),), jnp.int32)
    b_occ = _shape(one_chip, ((k // TILE) * (m // TILE),), jnp.int32)
    _assert_kernel(lambda x, y, xo, yo: spgemm_bsr(x, y, xo, yo, interpret=False),
                   a, b, a_occ, b_occ)


def test_scoped_forward_compiles_for_v5e(one_chip, monkeypatch):
    """A small banded Simple-HGN forward with compiled Pallas kernels:
    both NA kernels of each layer and metapath, and the fusions around
    them, carry their ``layer<i>/na/<metapath>`` scope in the program the
    chip would run."""
    from repro import obs
    from repro.api import ExecutorSpec, Session
    from repro.core.hgnn import HGNNConfig
    from repro.hetero import make_dataset

    mps = ["MAM", "MDM", "MKM"]
    graph = make_dataset("IMDB", scale=0.05)
    cfg = HGNNConfig(model="shgn", hidden=HIDDEN, num_layers=2, num_classes=3,
                     target_type="M", edge_emb_dim=16, sf_att_dim=HIDDEN)
    c = Session(ExecutorSpec(na_executor="banded")).compile(graph, mps, cfg)
    params = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype),
                          jax.eval_shape(lambda: c.init(0)))
    feats = {t: _shape(one_chip, x.shape, jnp.float32) for t, x in graph.features.items()}
    # the kernel backend follows the platform: steer it to compiled Pallas
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(lambda p, f: c.model.execute(p, f, c.graphs)).lower(
        params, feats).compile().as_text()
    scope_of = obs.scopes_of_hlo(text)
    na = {obs.na_scope(li, mp) for li in range(2) for mp in mps}
    kernels, fusions = set(), set()
    entry = text[text.index("\nENTRY"):]  # the ops the device runs one by one
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([^\s=]+) = (?:\S+|\(.*?\)) ([\w-]+)\(", line)
        if not m:
            continue
        name, opcode = m.groups()
        if "tpu_custom_call" in line:
            kernels.add((scope_of.get(name), name.split(".")[0]))
        elif opcode == "fusion":
            fusions.add(scope_of.get(name))
    assert kernels == {(s, k) for s in na for k in ("na_seg_sum", "na_softmax_stats")}
    assert na <= fusions and None not in fusions
