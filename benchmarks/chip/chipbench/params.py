"""Weights drawn from ``--seed``, on the device.

The benchmark makes the weights itself, so that the program and the
reference get the same numbers and the reference takes nothing the
program made.  The pytree has the program's parameter layout (the
interface ``HGNN.execute`` reads); the values are this module's own
draw.  Each is one jitted call.  What a model adds to the layout (its
NA parameters) comes from its file, ``models/<model>.py``, drawn from
the same key stream in the same order.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench.spec import load_model


def seed_key(seed: int, stream: int):
    """A JAX key for one use of ``seed`` (any non-negative integer; seeds
    past 32 bits are folded by ``SeedSequence``)."""
    import jax

    state = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return jax.random.key(int(state[0]) >> 1)


def dense(k, d_in, d_out):
    """A ``(d_in, d_out)`` float32 weight, He-scaled."""
    import jax
    import jax.numpy as jnp

    return jax.random.normal(k, (d_in, d_out), jnp.float32) * (2.0 / d_in) ** 0.5


def small(k, *shape):
    """A float32 array of ``shape`` at a tenth of unit scale."""
    import jax
    import jax.numpy as jnp

    return jax.random.normal(k, shape, jnp.float32) * 0.1


def init_params(cfg: Dict, key):
    """The program's parameter pytree for ``cfg``, drawn from ``key``."""
    import jax

    types = sorted(cfg["vertices"])
    mps = sorted(cfg["metapaths"])
    h, c = int(cfg["hidden"]), int(cfg["num_classes"])
    att = int(cfg["sf_att_dim"])
    model = load_model(cfg["model"])

    @jax.jit
    def draw(key):
        ks = iter(jax.random.split(key, 4096))
        layers = []
        for layer in range(int(cfg["num_layers"])):
            lp = {"fp": {}, "na": {}, "sf": {}}
            for t in types:
                d_in = (int(cfg["features"][t]) or 1) if layer == 0 else h
                lp["fp"][t] = {"w": dense(next(ks), d_in, h), "b": small(next(ks), h)}
            for mp in mps:
                lp["na"][mp] = model.draw_na(ks, cfg, h)
            lp.update(model.draw_layer(ks, cfg, mps))
            for t in types:
                lp["sf"][t] = {"w": dense(next(ks), h, att), "b": small(next(ks), att),
                               "q": small(next(ks), att), "w_self": dense(next(ks), h, h)}
            layers.append(lp)
        return {"layers": layers,
                "head": {"w": dense(next(ks), h, c), "b": small(next(ks), c)}}

    return draw(key)
