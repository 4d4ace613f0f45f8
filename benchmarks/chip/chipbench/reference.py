"""The plain reference: the configuration's HGNN in straightforward jnp.

Written from the model's description (FP -> NA -> SF per layer, then the
classifier head), over the benchmark's own semantic graphs as global
``(src, dst)`` edge lists, with ``jax.ops.segment_*`` for aggregation and
the per-destination softmax.  FP, SF and the head are shared; the NA of
one metapath is the model's own, ``na`` of ``models/<model>.py``.  It
imports nothing of the program.

Every matrix product goes through one ``dot``: ``"highest"`` is float32
(``Precision.HIGHEST``), the precision the configurations state.
``"bf16x3"`` is the control: each float32 operand split into a bfloat16
high part and a bfloat16 remainder, and the three leading products summed
in float32 -- the three-pass ``"high"`` precision, the nearest below
``"highest"``, written out so that it computes the same on any backend.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

from chipbench.spec import load_model


def edge_softmax(logit, dst, n_dst):
    """Softmax of the per-edge ``logit`` over each destination's edges
    (over the leading axis, so a trailing head axis is kept)."""
    import jax
    import jax.numpy as jnp

    m = jax.ops.segment_max(logit, dst, num_segments=n_dst)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    ex = jnp.exp(logit - m[dst])
    den = jax.ops.segment_sum(ex, dst, num_segments=n_dst)
    return ex / den[dst]


def make_dot(precision: str) -> Callable:
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return lambda a, b: jnp.matmul(a, b, precision=hi)
    if precision != "bf16x3":
        raise ValueError(f"unknown reference precision {precision!r}")

    def bf16(x):
        # rounds to bfloat16's 8-bit mantissa but stays float32, so no
        # compiler may drop the rounding as excess precision (a
        # float32 -> bfloat16 -> float32 round trip may be dropped)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def dot(a, b):
        a_hi, b_hi = bf16(a), bf16(b)
        a_lo, b_lo = bf16(a - a_hi), bf16(b - b_hi)
        # products of bfloat16 values are exact in float32
        return (jnp.matmul(a_hi, b_hi, precision=hi)
                + (jnp.matmul(a_hi, b_lo, precision=hi) + jnp.matmul(a_lo, b_hi, precision=hi)))

    return dot


class Reference:
    """The reference model of one configuration over its semantic graphs.

    ``logits_fn`` returns a jitted function, built once per precision; the
    edge lists are its arguments, not constants."""

    def __init__(self, cfg: Dict, nv: Dict[str, int],
                 semantic: Dict[str, Tuple[np.ndarray, np.ndarray]]):
        import jax.numpy as jnp

        self.cfg = cfg
        self.model = load_model(cfg["model"])
        self.nv = dict(nv)
        self.mps = sorted(cfg["metapaths"])
        self.edges = {mp: (jnp.asarray(semantic[mp][0]), jnp.asarray(semantic[mp][1]))
                      for mp in self.mps}
        self._fns: Dict = {}

    def logits_fn(self, precision: str) -> Callable:
        """``(params, feats) -> logits``."""
        key = ("logits", precision)
        if key not in self._fns:
            import jax

            dot = make_dot(precision)
            fn = jax.jit(lambda p, f, e: self.logits(p, f, dot, e))
            self._fns[key] = lambda p, f: fn(p, f, self.edges)
        return self._fns[key]

    def hidden(self, params, feats, dot, edges):
        import jax
        import jax.numpy as jnp

        cfg, nv, model = self.cfg, self.nv, self.model
        h = {t: feats[t] if int(cfg["features"][t]) > 0 else jnp.ones((n, 1), jnp.float32)
             for t, n in nv.items()}
        for lp in params["layers"]:
            hp = {t: jax.nn.relu(dot(x, lp["fp"][t]["w"]) + lp["fp"][t]["b"])
                  for t, x in h.items()}
            incoming: Dict[str, List] = {}
            for i, mp in enumerate(self.mps):
                src, dst = edges[mp]
                s_t, d_t = mp[0], mp[-1]
                hs = dot(hp[s_t], lp["na"][mp]["w_rel"])
                z = model.na(dot, lp, i, mp, hs, hp[d_t], src, dst, nv[d_t])
                incoming.setdefault(d_t, []).append(z)
            nxt = {}
            for t, x in hp.items():
                sf = lp["sf"][t]
                self_z = dot(x, sf["w_self"])
                if t not in incoming:
                    nxt[t] = self_z
                    continue
                stack = jnp.stack(incoming[t] + [self_z])  # (P+1, N, D)
                p1, n, d = stack.shape
                score = dot(jnp.tanh(dot(stack.reshape(p1 * n, d), sf["w"]) + sf["b"]),
                            sf["q"][:, None]).reshape(p1, n)
                beta = jax.nn.softmax(jnp.mean(score, axis=1))
                nxt[t] = jnp.sum(beta[:, None, None] * stack, axis=0)
            h = {t: jax.nn.relu(v) for t, v in nxt.items()}
        return h

    def logits(self, params, feats, dot, edges):
        h = self.hidden(params, feats, dot, edges)[self.cfg["target_type"]]
        return dot(h, params["head"]["w"]) + params["head"]["b"]
