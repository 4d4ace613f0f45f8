"""The Pallas kernel backend, chosen from the platform in one place.

Two backends run the same kernel bodies:

  * ``"pallas"``    — compiled through Mosaic for the TPU (``interpret=False``);
  * ``"interpret"`` — the Pallas interpreter, which is how the CPU test suite
                      checks the kernels.

``None`` (the default everywhere a backend can be named) means "whatever
this platform runs": compiled Pallas when JAX's default backend is a TPU,
the interpreter otherwise.  Naming ``"pallas"`` on a host with no TPU is an
error, never a fallback.
"""
from __future__ import annotations

from typing import Optional

import jax

KERNEL_BACKENDS = ("pallas", "interpret")


def platform_backend() -> str:
    """``"pallas"`` when JAX runs on a TPU, else ``"interpret"``."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def resolve(backend: Optional[str] = None) -> str:
    """Concrete kernel backend for ``backend`` (``None`` = the platform's)."""
    if backend is None:
        return platform_backend()
    if backend not in KERNEL_BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r} (expected one of "
            f"{KERNEL_BACKENDS} or None for the platform's)")
    if backend == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            "kernel backend 'pallas' needs a TPU, but JAX's default backend "
            f"is {jax.default_backend()!r}; pass None (or 'interpret') to run "
            "the kernels in interpret mode")
    return backend


def use_interpret(backend: Optional[str] = None) -> bool:
    """The ``interpret=`` flag of ``pallas_call`` for ``backend``."""
    return resolve(backend) == "interpret"
