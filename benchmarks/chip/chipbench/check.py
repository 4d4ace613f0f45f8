"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the reference computes from the same weights and data.

* ``gap_over_noise``: ``rms_gap``, the root-mean-square logit
  difference over every row as a share of the reference logits' root
  mean square, taken as a multiple of the same gap between the
  reference and itself with every edge list in another order: the
  reference's own float32 rounding noise for this seed.  A seed whose
  weights make the logits sensitive raises both alike, so the multiple
  stays steady from seed to seed where the bare gap does not.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def widest_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def rms_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def gap_over_noise(got, want, reordered) -> float:
    noise = rms_gap(reordered, want)
    return rms_gap(got, want) / noise if noise > 0 else float("inf")


def verdict(readings: Dict[str, float], limits: Dict[str, Dict]) -> Tuple[bool, Dict]:
    """``(correct, {name: {"value", "limit"}})``: correct when every
    reading is finite and at most its limit."""
    out, ok = {}, True
    for name, value in readings.items():
        limit = float(limits[name]["limit"])
        out[name] = {"value": value, "limit": limit}
        ok = ok and np.isfinite(value) and value <= limit
    return bool(ok), out
