"""Tests of the model files (``benchmarks/chip/models/<model>.py``), on the
CPU at small sizes.

    python3 -m pytest -q tests/chipbench/test_models.py

Both configurations draw the same parameters, get the same reference
logits and count the same work as the harness did before the models moved
into files (``data/model_pins.json``); every model file draws the
program's parameter layout; no module of ``chipbench`` names a model; a
model in no cell (RGAT) runs a whole forward cell through its file alone,
correct when sound and not with each fault; and ``load_model`` refuses a
bad name and a missing file.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import math
import re
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import drivers, faults, params, runner, spec  # noqa: E402

PINS = json.loads((Path(__file__).resolve().parent / "data" / "model_pins.json").read_text())
MODELS = sorted(p.stem for p in (HERE / "models").glob("*.py"))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _sums(x):
    v = np.asarray(x, np.float64).ravel().tolist()
    return [math.fsum(v), math.fsum(abs(a) for a in v)]


# ------------------------------------------------- pinned from the past --
@pytest.fixture(scope="module", params=sorted(PINS["configs"]))
def pinned(request):
    """A configuration at the pins' scale: its context, its parameters
    drawn at the pins' seed, and what was pinned for it."""
    import jax

    name = request.param
    cfg = dict(spec.load_json("configs", name), scale=PINS["scale"])
    ctx = drivers.Context(cfg)
    p = jax.device_get(params.init_params(cfg, params.seed_key(PINS["seed"], 1)))
    return ctx, p, PINS["configs"][name]


def test_work_counts_are_pinned(pinned):
    ctx, _, pin = pinned
    w = runner.work_counts(ctx)
    assert w["forward_flops"] == pin["forward_flops"]
    assert {k: [list(c) for c in v] for k, v in w["na_calls"].items()} == pin["na_calls"]


def test_parameters_are_pinned(pinned):
    import jax

    _, p, pin = pinned
    got = {jax.tree_util.keystr(k): _sums(v) for k, v in jax.tree_util.tree_flatten_with_path(p)[0]}
    assert got == pin["params"]


def test_reference_logits_are_pinned(pinned):
    import jax

    ctx, p, pin = pinned
    got = {}
    for prec in ("highest", "bf16x3"):
        for reordered in (False, True):
            fn = ctx.reference(reordered).logits_fn(prec)
            got[prec + ("/reordered" if reordered else "")] = _sums(
                fn(jax.device_put(p), ctx.features))
    assert got == pin["logits"]


# ------------------------------------------------------- every model --
def _config(model: str):
    return dict(spec.load_json("configs", "imdb-shgn"), model=model, scale=0.05)


@pytest.mark.parametrize("model", MODELS)
def test_parameters_have_the_program_layout(model):
    """The harness's pytree has the keys and shapes of the program's own
    ``HGNN.init`` for the same configuration."""
    import jax

    from repro.core.hgnn import HGNN, HGNNConfig

    cfg = _config(model)
    hcfg = HGNNConfig(model=model, hidden=int(cfg["hidden"]), num_layers=int(cfg["num_layers"]),
                      num_classes=int(cfg["num_classes"]), target_type=cfg["target_type"],
                      edge_emb_dim=int(cfg["edge_emb_dim"]), sf_att_dim=int(cfg["sf_att_dim"]),
                      **spec.load_model(model).program_config(cfg))
    program = HGNN(hcfg, {t: int(d) for t, d in cfg["features"].items()}, cfg["vertices"],
                   cfg["metapaths"])
    key = jax.random.key(0)
    want = jax.eval_shape(program.init, key)
    got = jax.eval_shape(lambda k: params.init_params(cfg, k), key)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    assert [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(got)] == \
        [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(want)]


def _words(source: str) -> set:
    """The words of a module's string literals (docstrings and f-string
    parts included) and of its identifiers: runs of letters and digits, so
    ``"han"``, ``f"{x}_han"`` and ``han_flops`` give ``han``, and ``than``
    or ``change`` do not."""
    words = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            text = node.value
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            text = node.name
        else:
            text = next((getattr(node, f) for f in ("id", "attr", "arg")
                         if isinstance(getattr(node, f, None), str)), "")
        words.update(re.findall(r"[A-Za-z0-9]+", text))
    return words


@pytest.mark.parametrize("path", sorted((HERE / "chipbench").glob("*.py")), ids=lambda p: p.name)
def test_chipbench_names_no_model(path):
    assert MODELS and not _words(path.read_text()) & set(MODELS)


@pytest.mark.parametrize("source, named", [
    ('"""Longer than the change it hands on."""\nthan = change = 1', False),
    ("x = HAN_like", False),
    ('m = cfg["model"] == "han"', True),
    ('k = f"{x}_han"', True),
    ("def han_flops(): pass", True),
    ("y = mods.han", True),
])
def test_a_model_is_named_only_as_a_word(source, named):
    """A short name such as ``han`` is found where code names it, and not
    inside ``than``, ``change`` or ``hands``."""
    assert ("han" in _words(source)) is named


@pytest.mark.parametrize("fault", [None] + faults.FAULTS_BY_ENTRY["forward"])
def test_model_in_no_cell_runs_a_cell(fault):
    """The IMDB cell with RGAT, which no cell runs, at a twentieth of its
    scale: correct when sound, not with each fault planted."""
    assert "rgat" not in {spec.load_cell(w["name"]).config["model"]
                          for w in spec.load_benchmark()["workloads"]}
    c = spec.load_cell("imdb-shgn.forward")
    c = dataclasses.replace(c, config=dict(c.config, model="rgat", scale=0.05))
    res, checks = runner.run_cell(c, 2**31 + 13, 0.5, None, t_start=time.perf_counter(),
                                  device=CPU, fault=faults.FAULTS.get(fault))
    assert res["attempted"] > 0 and {"forward_ms", "setup_s"} == set(res["metrics"])
    assert res["correct"] is (fault is None), checks


@pytest.mark.parametrize("name", ["../params", "a b", "", "x" * 65])
def test_load_model_refuses_a_bad_name(name):
    with pytest.raises(ValueError):
        spec.load_model(name)


def test_load_model_refuses_a_missing_file():
    with pytest.raises(FileNotFoundError):
        spec.load_model("no_such_model")
