"""The compiled forward takes the graph as an argument, on the CPU at small
sizes.

``CompiledHGNN.forward`` passes every device array of its bound batches
(packed blocks, masks, permutations, banded edges, degrees) to one jitted
function as a pytree and binds it to the batches inside the trace, so
the compiled program holds none of the graph as a constant.  The banded
mean (RGCN) forward over DBLP's 4-hop metapaths must match the jnp
executor and a plain float64 reference; the forward must trace once; and
an attention forward must give the same logits as one built with the
graph closed over.
"""
import re

import jax
import numpy as np
import pytest

from repro.api import ExecutorSpec, Session, device_features
from repro.core.hgnn import HGNNConfig
from repro.core.hgnn.models import bind_graphs, graph_arrays
from repro.kernels.seg_sum import pack_edge_blocks

DBLP_METAPATHS = ["APA", "APTPA", "APVPA"]
IMDB_METAPATHS = ["MAM", "MDM", "MKM"]
_HEX_CONSTANT = re.compile(r'dense<"0x([0-9A-Fa-f]*)">')
MB = 1 << 20


def _cfg(model, target, classes):
    return HGNNConfig(model=model, hidden=64, num_layers=2, num_classes=classes,
                      target_type=target)


@pytest.fixture(scope="module")
def dblp(dblp_small):
    """The banded and jnp RGCN models over DBLP at scale 0.1, and the
    banded forward's first result."""
    feats = device_features(dblp_small)
    banded = Session(ExecutorSpec(na_executor="banded")).compile(
        dblp_small, DBLP_METAPATHS, _cfg("rgcn", "A", 4))
    jnp_model = Session(ExecutorSpec(na_executor="jnp")).compile(
        dblp_small, DBLP_METAPATHS, _cfg("rgcn", "A", 4))
    params = banded.init(0)
    traces = []
    execute = banded.model.execute

    def counted(*args, **kw):  # called once per trace of the forward
        traces.append(1)
        return execute(*args, **kw)

    banded.model.execute = counted
    logits = np.asarray(banded.forward(params, feats))
    return {"graph": dblp_small, "banded": banded, "jnp": jnp_model, "params": params,
            "feats": feats, "logits": logits, "traces": traces,
            "first_traces": len(traces)}


def _constant_bytes(text):
    """Bytes of each dense literal constant in a StableHLO module's text."""
    return [len(h) // 2 for h in _HEX_CONSTANT.findall(text)]


def _reference_logits(compiled, graph, params):
    """RGCN over the frontend's semantic graphs in float64 numpy: FP, the
    degree mean of each metapath, semantic attention with the self path,
    the head."""
    p = jax.tree.map(lambda x: np.asarray(x, np.float64), params)
    nv = graph.num_vertices
    h = {t: np.asarray(graph.features[t], np.float64) if graph.feature_dims[t] > 0
         else np.ones((n, 1)) for t, n in nv.items()}
    for lp in p["layers"]:
        hp = {t: np.maximum(x @ lp["fp"][t]["w"] + lp["fp"][t]["b"], 0) for t, x in h.items()}
        incoming = {}
        for mp in sorted(DBLP_METAPATHS):
            rel = compiled.semantic[mp]
            hs = hp[mp[0]] @ lp["na"][mp]["w_rel"]
            summed = np.zeros((nv[mp[-1]], hs.shape[1]))
            np.add.at(summed, rel.dst, hs[rel.src])
            deg = np.bincount(rel.dst, minlength=nv[mp[-1]])
            incoming.setdefault(mp[-1], []).append(summed / np.maximum(deg, 1)[:, None])
        nxt = {}
        for t, x in hp.items():
            sf = lp["sf"][t]
            self_z = x @ sf["w_self"]
            if t not in incoming:
                nxt[t] = np.maximum(self_z, 0)
                continue
            stack = np.stack(incoming[t] + [self_z])
            score = (np.tanh(stack @ sf["w"] + sf["b"]) @ sf["q"]).mean(axis=1)
            beta = np.exp(score - score.max())
            beta /= beta.sum()
            nxt[t] = np.maximum(np.einsum("p,pnd->nd", beta, stack), 0)
        h = nxt
    return h["A"] @ p["head"]["w"] + p["head"]["b"]


def test_banded_rgcn_forward_matches_jnp_and_reference(dblp):
    got = dblp["logits"]
    want = np.asarray(dblp["jnp"].forward(dblp["params"], dblp["feats"]))
    ref = _reference_logits(dblp["banded"], dblp["graph"], dblp["params"])
    assert got.shape == (dblp["graph"].num_vertices["A"], 4)
    scale = np.abs(ref).max()
    assert np.abs(got - want).max() <= 1e-5 * scale
    assert np.abs(got - ref).max() <= 1e-5 * scale


@pytest.fixture(scope="module")
def imdb(imdb_small):
    """The banded Simple-HGN model over IMDB at scale 0.2, its forward run."""
    c = Session(ExecutorSpec(na_executor="banded")).compile(
        imdb_small, IMDB_METAPATHS, _cfg("shgn", "M", 3))
    params, feats = c.init(1), device_features(imdb_small)
    logits = np.asarray(c.forward(params, feats))
    return {"banded": c, "params": params, "feats": feats, "logits": logits}


@pytest.mark.parametrize("name", ["dblp", "imdb"])
def test_lowered_forward_holds_no_graph_constant(name, request):
    """The graph's arrays are parameters of the program: no literal over
    1 MB, and all of them together a few KiB, where the same forward
    with the graph closed over holds as literals every array its NA
    kernels read: the dense tiles of the mean forward's packings in the
    dense format (DBLP's APTPA and APVPA at this scale), the packed
    blocks of the others."""
    m = request.getfixturevalue(name)
    c = m["banded"]
    sizes = _constant_bytes(c.forward_lowered().as_text())
    assert max(sizes, default=0) < MB and sum(sizes) < 64 * 1024
    closed = jax.jit(lambda p, f: c.model.execute(p, f, c.graphs))
    dense = [g.packed.dense_format and c.cfg.model == "rgcn" for g in c.graphs]
    assert any(dense) is (name == "dblp")
    read = sum(g.packed.dense_tiles()[3].nbytes if d else g.packed.src_local.nbytes
               for g, d in zip(c.graphs, dense))
    assert sum(_constant_bytes(closed.lower(m["params"], m["feats"]).as_text())) > read


def test_packing_counts_report_the_dense_format(dblp, imdb):
    """Per metapath, ``edges``, ``blocks``, ``slots`` and ``fill`` of the
    edge blocks as before, and the ``dense_tiles`` and ``dense_edges`` the
    forward aggregates as dense tiles: DBLP's APTPA and APVPA under the
    mean model, none of the attention model's packings."""
    for m in (dblp, imdb):
        c = m["banded"]
        counts = c.packing_counts()
        for g in c.graphs:
            pk, n = g.packed, counts[g.metapath]
            assert (n["edges"], n["blocks"], n["slots"]) == (
                pk.num_edges, pk.num_blocks, pk.num_blocks * pk.edge_block)
            assert n["fill"] == pytest.approx(n["edges"] / n["slots"])
            dense = (pk.num_dense_tiles, pk.num_edges) if c.cfg.model == "rgcn" \
                and pk.dense_format else (0, 0)
            assert (n["dense_tiles"], n["dense_edges"]) == dense
    dblp_counts = dblp["banded"].packing_counts()
    assert [dblp_counts[mp]["dense_edges"] == dblp_counts[mp]["edges"]
            for mp in DBLP_METAPATHS] == [False, True, True]
    assert not any(n["dense_edges"] for n in imdb["banded"].packing_counts().values())


def test_forward_traces_once(dblp):
    c = dblp["banded"]
    first, traces = c.timings["forward_compile"], len(dblp["traces"])
    for _ in range(2):
        again = np.asarray(c.forward(dblp["params"], dblp["feats"]))
        assert np.array_equal(again, dblp["logits"])
    assert dblp["first_traces"] == 1 and len(dblp["traces"]) == traces
    assert c.timings["forward_compile"] == first > 0


def test_attention_forward_equals_the_closed_over_one(imdb):
    """Same ops in the same order: an argument in place of a constant
    changes no logit of the banded Simple-HGN forward."""
    c = imdb["banded"]
    closed = jax.jit(lambda p, f: c.model.execute(p, f, c.graphs))
    assert np.array_equal(imdb["logits"], np.asarray(closed(imdb["params"], imdb["feats"])))


def test_bound_batches_read_only_the_given_arrays(imdb_small):
    """``graph_arrays`` leaves the edge maps out of the graph (no forward,
    mean or attention, reads them) and holds the dense tiles of a packing
    in the dense format; ``bind_graphs`` gives batches whose every device
    array is the given one, and whose packings share the host arrays but
    no cache: a backward pass uploads the flat edges on first use."""
    c = Session(ExecutorSpec(na_executor="banded")).compile(
        imdb_small, IMDB_METAPATHS, _cfg("shgn", "M", 3))
    arrays = graph_arrays(c.graphs)
    dense = {"dense"} if c.graphs[0].packed.dense_format else set()
    assert set(arrays[0]["packed"]) == {"blocked", "valid"} | dense
    assert set(arrays[0]) == {"packed", "src_gather", "dst_gather",
                              "dst_scatter", "deg"}
    marked = jax.tree.map(lambda x: x + 0, arrays)
    bound = bind_graphs(c.graphs, marked)
    for g, b, a in zip(c.graphs, bound, marked):
        assert b.src_gather is a["src_gather"] and b.deg is a["deg"]
        assert b.packed.device_blocked() is a["packed"]["blocked"]
        assert b.packed.device_weight() is a["packed"]["valid"]
        for x, y in zip(b.packed.device_flat_edges(), g.packed.flat_global_edges()):
            assert np.array_equal(np.asarray(x), y)
        if g.packed.dense_format:
            assert b.packed.device_dense() is a["packed"]["dense"]
        assert b.packed.src_local is g.packed.src_local
        assert b.packed.device_valid() is not g.packed.device_valid()


def test_weighted_packing_passes_its_weights():
    src, dst = np.array([0, 1, 2, 2]), np.array([0, 0, 1, 3])
    w = np.array([0.5, 1.0, 2.0, 0.0], np.float32)
    packed = pack_edge_blocks(src, dst, 3, 4, weight=w)
    arrays = packed.device_arrays()
    assert set(arrays) == {"blocked", "valid", "weight"}
    assert np.array_equal(np.asarray(arrays["weight"])[:, 0], packed.weight)
    assert np.asarray(arrays["valid"]).sum() == 4
    assert packed.bind(arrays).device_weight() is arrays["weight"]
