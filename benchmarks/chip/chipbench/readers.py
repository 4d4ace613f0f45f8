"""Shared arithmetic of the per-layer metric readers (``metrics/*.py``).

A reader gets the run record: ``kind`` (the cell's entry), ``window``
(what the driver counted), ``trace`` (the ``tracing.Reduction``), ``peak``
(the device's row of ``peaks.PEAKS``) and ``work`` (``work`` counts).  It
returns ``None`` when the run has nothing for it to read.
"""
from __future__ import annotations

from typing import Dict, Optional

from chipbench import work


def mfu_pct(run: Dict, kind: str, flops_key: str, count_key: str) -> Optional[float]:
    """Useful FLOPs per call x calls in the window / window / peak."""
    if run["kind"] != kind:
        return None
    w = run["window"]
    return (100.0 * run["work"][flops_key] * w[count_key] / w["window_s"]
            / run["peak"]["flops_per_s"])


def roofline_pct(run: Dict, kind: str, kernel: str) -> Optional[float]:
    """Least time over device time of one kernel's events in the window.

    The trace does not say which call an event was, so the least time is
    the mean over one forward's calls times the number of events."""
    if run["kind"] != kind or run["trace"] is None:
        return None
    calls = run["work"]["na_calls"].get(kernel)
    secs, count = run["trace"].kernel(kernel)
    if not calls or count == 0 or secs <= 0:
        return None
    least = sum(work.least_seconds(f, b, run["peak"]) for f, b in calls) / len(calls)
    return 100.0 * least * count / secs


def idle_pct(run: Dict, kind: str) -> Optional[float]:
    if run["kind"] != kind or run["trace"] is None:
        return None
    return 100.0 * run["trace"].idle_share
