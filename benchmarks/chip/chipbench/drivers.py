"""What a cell drives: the program's full-graph forward, behind the
shape every driver has.

Each driver builds the program's objects once (``__init__``), takes a
seed's weights and data (``prepare``), warms every shape its traffic uses
(``warm``), measures (``window``), and hands what the timed path produced
to ``check`` (``produced``).  ``control`` gives the same things from the
reference computed in the precision below the configuration's, put in the
program's place.

``fault`` wraps each program entry before use: ``fault(kind, obj)``
returns the object to call.  Runs pass the identity; the fault tests
pass wrappers that break the timed path.
"""
from __future__ import annotations

import collections
import contextlib
import math
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import check, graphs, params as seeded
from chipbench.reference import Reference
from chipbench.spec import load_model

Fault = Callable[[str, object], object]


def no_fault(kind: str, obj):
    return obj


def _span(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


class Context:
    """The graph, the program's session and compiled model for a config."""

    def __init__(self, cfg: Dict):
        import jax

        from repro.api import ExecutorSpec, Session
        from repro.core.hgnn import HGNNConfig
        from repro.hetero.graph import HetGraph, Relation

        jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
        self.cfg = cfg
        self.nv, self.rels = graphs.topology(cfg)
        relations = {}
        for name, (s, d) in self.rels.items():
            relations[name] = Relation(name[0], name[1], self.nv[name[0]], self.nv[name[1]], s, d)
        self.graph = HetGraph(name=cfg["dataset"], num_vertices=dict(self.nv),
                              feature_dims={t: int(v) for t, v in cfg["features"].items()},
                              relations=relations)
        self.features = graphs.make_features(cfg, self.nv)
        self.session = Session(ExecutorSpec(na_executor=cfg["executor"]))
        self.hcfg = HGNNConfig(model=cfg["model"], hidden=int(cfg["hidden"]),
                               num_layers=int(cfg["num_layers"]),
                               num_classes=int(cfg["num_classes"]),
                               target_type=cfg["target_type"],
                               edge_emb_dim=int(cfg["edge_emb_dim"]),
                               sf_att_dim=int(cfg["sf_att_dim"]),
                               **load_model(cfg["model"]).program_config(cfg))
        self.compiled = self.session.compile(self.graph, cfg["metapaths"], self.hcfg)
        self.num_target = self.nv[cfg["target_type"]]
        self._semantic = None
        self._references = {}

    def semantic(self):
        """The reference's own semantic graphs (built on first use)."""
        if self._semantic is None:
            self._semantic = graphs.semantic_graphs(self.nv, self.rels, self.cfg["metapaths"])
        return self._semantic

    def reference(self, reordered: bool = False) -> Reference:
        """The reference over the semantic graphs; ``reordered`` lists
        every graph's edges in another fixed order, so that its sums
        round differently: the float32 noise of the reference itself."""
        if reordered not in self._references:
            sem = self.semantic()
            if reordered:
                rng = np.random.default_rng(0)
                shuffled = {}
                for mp, (s, d) in sem.items():
                    order = rng.permutation(s.size)
                    shuffled[mp] = (s[order], d[order])
                sem = shuffled
            self._references[reordered] = Reference(self.cfg, self.nv, sem)
        return self._references[reordered]

    def na_shapes(self) -> Dict:
        """``{metapath: (edges, distinct sources, distinct destinations)}``."""
        return {mp: (int(s.size), int(np.unique(s).size), int(np.unique(d).size))
                for mp, (s, d) in self.semantic().items()}


def _report_calls(what: str, t0: float, ends: List[float]) -> None:
    """The spread of single calls in the window, on stderr: a slow window
    shows whether every call was slower or a few stalled."""
    ms = np.diff(np.concatenate([[t0], ends])) * 1e3
    print(f"window: {ms.size} {what} calls, ms min {ms.min():.3f} median "
          f"{np.median(ms):.3f} max {ms.max():.3f}, over 1.5x median: "
          f"{int(np.sum(ms > 1.5 * np.median(ms)))}", file=sys.stderr)


def _ref_logits(driver, precision: str, reordered: bool = False) -> np.ndarray:
    """The reference's logits for the driver's seed (memoized per seed)."""
    import jax

    key = (precision, reordered)
    if key not in driver._ref:
        fn = driver.ctx.reference(reordered).logits_fn(precision)
        driver._ref[key] = np.asarray(fn(jax.device_put(driver.host_params),
                                         driver.ctx.features))
    return driver._ref[key]


class ForwardDriver:
    """One caller, repeated full-graph forwards, keeping the traffic's
    ``ahead_s`` seconds of them in flight."""

    def __init__(self, ctx: Context, traffic: Dict, fault: Fault = no_fault):
        self.ctx, self.traffic = ctx, traffic
        self.forward = fault("forward", ctx.compiled.forward)

    def prepare(self, seed: int) -> None:
        import jax

        self.params = seeded.init_params(self.ctx.cfg, seeded.seed_key(seed, 1))
        self.host_params = jax.device_get(self.params)
        self._ref = {}

    def warm(self, seconds: float) -> None:
        """Two forwards; the second's time sets how many the window keeps
        in flight."""
        for _ in range(2):
            t = time.perf_counter()
            self.forward(self.params, self.ctx.features).block_until_ready()
        per_call = time.perf_counter() - t
        self.depth = max(1, math.ceil(float(self.traffic["ahead_s"]) / per_call))

    def window(self, seconds: float, trace: bool) -> Dict:
        """Forwards sent ``depth`` ahead of the one waited for, so that a
        stall of the host shorter than ``ahead_s`` leaves the chip busy.
        When the time is up nothing more is sent and every forward sent is
        waited for: all of them count, over the time until the last ends."""
        span = _span(trace)
        f, p = self.ctx.features, self.params
        pending = collections.deque()
        ends = []
        t0 = time.perf_counter()
        with span("bench.window"):
            while not ends or ends[-1] - t0 < seconds:
                while len(pending) < self.depth:
                    with span("bench.submit"):
                        pending.append(self.forward(p, f))
                with span("bench.forward"):
                    out = pending.popleft()
                    out.block_until_ready()
                ends.append(time.perf_counter())
            while pending:
                with span("bench.forward"):
                    out = pending.popleft()
                    out.block_until_ready()
                ends.append(time.perf_counter())
        elapsed = ends[-1] - t0
        n = len(ends)
        _report_calls("forward", t0, ends)
        self.out = np.asarray(out)
        return {"window_s": elapsed, "attempted": n, "failed": 0, "forwards": n,
                "e2e": {"forward_ms": elapsed / n * 1e3}}

    def produced(self):
        return self.out

    def release(self) -> None:
        self.params = None

    def check(self, produced) -> Dict[str, float]:
        want = _ref_logits(self, "highest")
        reordered = _ref_logits(self, "highest", reordered=True)
        return {"logit_gap_over_noise": check.gap_over_noise(produced, want, reordered)}

    def control(self):
        return _ref_logits(self, "bf16x3")


DRIVERS = {"forward": ForwardDriver}


def make_driver(ctx: Context, traffic: Dict, fault: Optional[Fault] = None):
    return DRIVERS[traffic["entry"]](ctx, traffic, fault or no_fault)
