"""Find everything a cell needs by name.

``BENCHMARK.json`` at the checkout's root is the index: a cell names its
configuration and its traffic mix, and each metric lists the cells it is
reported in.  Everything else is one file per thing under this
directory, found by name:

* ``configs/<config>.json``  -- the configuration as it is run;
* ``traffic/<traffic>.json`` -- the traffic mix's parameters;
* ``workloads/<cell>.json``  -- the cell's correctness limits, with the
  readings each was set from;
* ``metrics/<metric>.py``    -- a per-layer metric's reader;
* ``models/<model>.py``      -- what one HGNN model (a configuration's
  ``model``) changes in the harness: the program's extra ``HGNNConfig``
  arguments (``program_config``), its NA parameters drawn from the
  seed's one key stream (``draw_na``, ``draw_layer``), its reference NA
  of one metapath (``na``), and its NA work counts (``na_flops``,
  ``na_kernel_calls``).

A cell, mix, configuration, metric or model is added by adding files and
``BENCHMARK.json`` entries; no file here names one.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(kind: str, name: str) -> Dict:
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    with open(HERE / kind / f"{name}.json") as f:
        return json.load(f)


def _load_module(kind: str, name: str) -> ModuleType:
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = HERE / f"{kind}s" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str) -> ModuleType:
    """A per-layer metric's reader: a module with ``UNIT``, ``LAYER``,
    ``MOVES`` and ``read(run) -> float | None``."""
    return _load_module("metric", name)


@functools.lru_cache(maxsize=None)
def load_model(name: str) -> ModuleType:
    """An HGNN model's file: a module with ``program_config(cfg)``,
    ``draw_na(keys, cfg, h)``, ``draw_layer(keys, cfg, mps)``,
    ``na(dot, lp, i, mp, hs, h_dst, src, dst, n_dst)``,
    ``na_flops(n_src, n_dst, e, h)`` and
    ``na_kernel_calls(e, u_src, u_dst, h)``.  Loaded once per process, so
    every caller, and a model file that builds on another, shares one
    module."""
    return _load_module("model", name)


def _in_cell(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def kind(self) -> str:
        return self.traffic["entry"]


def load_cell(name: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _in_cell(m, name) and m["moves"] in e2e_names]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=load_json("traffic", w["traffic"]),
                limits=load_json("workloads", name)["limits"],
                end_to_end=e2e, per_layer=per_layer)
