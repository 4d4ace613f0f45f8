"""Mesh construction sized from the devices that actually exist.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state — the dry-run must set XLA_FLAGS
before the first jax initialization.

``make_mesh_for`` is the one constructor: it sizes axes from
``jax.devices()`` (or an explicit device subset — the serving engine's
pinned tenant groups) instead of assuming a 16x16 pod.
``make_production_mesh`` survives as a thin wrapper that picks the
production axis names.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np


def _balanced_shape(n: int, k: int) -> Tuple[int, ...]:
    """Factor ``n`` devices into ``k`` near-equal axis sizes.

    Prime factors of ``n`` are dealt largest-first onto the currently
    smallest axis, so 256 over 2 axes is (16, 16) and 512 over 3 is
    (8, 8, 8).  Deterministic; the product is always exactly ``n``.
    """
    if n < 1 or k < 1:
        raise ValueError(f"need n >= 1 devices and k >= 1 axes, got ({n}, {k})")
    factors = []
    m = n
    p = 2
    while p * p <= m:
        while m % p == 0:
            factors.append(p)
            m //= p
        p += 1
    if m > 1:
        factors.append(m)
    shape = [1] * k
    for f in sorted(factors, reverse=True):
        shape[int(np.argmin(shape))] *= f
    return tuple(sorted(shape, reverse=True))


def make_mesh_for(devices: Optional[Sequence] = None,
                  shard_axes: Sequence[str] = ("dev",),
                  shape: Optional[Tuple[int, ...]] = None):
    """Mesh over the devices that actually exist (or a pinned subset).

    ``devices=None`` uses ``jax.devices()``; the serving engine passes an
    explicit subset to pin a tenant to a device group.  ``shape=None``
    sizes the axes from the device count (``_balanced_shape``); an
    explicit shape must multiply out to the device count.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    axes = tuple(shard_axes)
    if not axes:
        raise ValueError("shard_axes must name at least one mesh axis")
    if shape is None:
        shape = _balanced_shape(len(devs), len(axes))
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axes) or math.prod(shape) != len(devs):
        raise ValueError(
            f"mesh shape {shape} does not cover {len(devs)} devices over "
            f"axes {axes}")
    arr = np.empty(len(devs), dtype=object)
    arr[:] = devs
    return jax.sharding.Mesh(arr.reshape(shape), axes)


def make_production_mesh(*, multi_pod: bool = False):
    """Production axis names over however many chips the fleet has.

    Axes: 'data' carries FSDP + batch, 'model' carries TP/EP; the 'pod'
    axis is pure data parallelism whose gradient all-reduce crosses the
    inter-pod (DCN) boundary once per step.  A 256-chip pod resolves to
    the historical 16x16; smaller fleets size down instead of failing.
    """
    if multi_pod:
        n = len(jax.devices())
        if n % 2:
            raise ValueError(f"multi_pod needs an even device count, got {n}")
        return make_mesh_for(
            shard_axes=("pod", "data", "model"),
            shape=(2,) + _balanced_shape(n // 2, 2))
    return make_mesh_for(shard_axes=("data", "model"))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny ``(data, model)`` mesh over the host's first devices.

    Built through :func:`make_mesh_for`, so its axes are Auto-typed and
    ``with_sharding_constraint`` accepts them (``jax.make_mesh`` builds
    Explicit axes by default).
    """
    return make_mesh_for(jax.devices()[: data * model], ("data", "model"),
                         shape=(data, model))
