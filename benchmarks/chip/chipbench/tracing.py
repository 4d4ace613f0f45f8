"""From a profiler trace to the numbers the per-layer metrics read.

The traced run wraps its window in a ``bench.window`` host span and each
call into the program in a ``bench.*`` span (``bench.forward``).
The reduction reads the ``.xplane.pb`` the JAX
profiler writes:

* device operations: the events of each TPU plane's ``XLA Ops`` line,
  named by their HLO instruction (``na_seg_sum.9``, ``fusion.30``);
* host spans: events named ``bench.*`` on any host plane.

It then takes, inside the window: the union of each device's operation
intervals (busy time), the gaps between them (idle), the time per
operation name, and the kernels' time by name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]  # plane name -> ops
    spans: List[Event]  # host bench.* spans


def find_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, found {files}")
    return files[0]


def op_name(event_name: str) -> str:
    """A TPU op event is named by its HLO text (``"%na_seg_sum.9 = f32[...]
    custom-call(...)"``); keep the instruction's name, ``na_seg_sum.9``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX) and plane.name[
            len(DEVICE_PLANE_PREFIX):].isdigit()
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                device_ops.setdefault(plane.name, []).extend(
                    (op_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif not is_device:
                spans.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops=device_ops, spans=spans)


def window_of(trace: Trace) -> Tuple[int, int]:
    ws = [s for s in trace.spans if s[0] == "bench.window"]
    if len(ws) != 1:
        raise RuntimeError(f"expected one bench.window span, found {len(ws)}")
    return ws[0][1], ws[0][2]


def _clip(events: List[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def busy_intervals(events: List[Event]) -> List[Tuple[int, int]]:
    """The union of the events' intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over devices of the busy union inside the window
    op_seconds: Dict[str, float]  # summed device time per op name (all devices)
    op_counts: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]  # longest first: (host span, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, prefix: str) -> Tuple[float, int]:
        """Device seconds and event count of ops whose name starts with
        ``prefix``."""
        secs = sum(v for k, v in self.op_seconds.items() if k.startswith(prefix))
        count = sum(v for k, v in self.op_counts.items() if k.startswith(prefix))
        return secs, count


def _host_activity(spans: List[Event], t: int) -> str:
    """The innermost bench.* span (other than the window) covering ``t``."""
    best: Optional[Event] = None
    for sp in spans:
        if sp[0] != "bench.window" and sp[1] <= t < sp[2]:
            if best is None or sp[2] - sp[1] < best[2] - best[1]:
                best = sp
    return best[0] if best else "no bench span"


def reduce(trace: Trace, top: int = 10) -> Reduction:
    lo, hi = window_of(trace)
    if not trace.device_ops:
        raise RuntimeError("the trace holds no device plane")
    busy, ops, counts, gaps = [], {}, {}, []
    for plane, events in sorted(trace.device_ops.items()):
        inside = _clip(events, lo, hi)
        for name, s, e in inside:
            ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
            counts[name] = counts.get(name, 0) + 1
        ivs = busy_intervals(inside)
        busy.append(sum(e - s for s, e in ivs))
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s))
    gaps.sort(reverse=True)
    idle = [(_host_activity(trace.spans, s + (d // 2)), d / 1e9) for d, s in gaps[:top]]
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
                     op_seconds=ops, op_counts=counts, idle_gaps=idle)


def base_name(op: str) -> str:
    """``na_seg_sum.9`` -> ``na_seg_sum``: one entry per kind of op."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def breakdown(red: Reduction, top: int = 10) -> Dict:
    """The device time per kind of op (instructions summed under their
    base name), and the longest idle gaps by the host span around them."""
    kinds: Dict[str, float] = {}
    for op, secs in red.op_seconds.items():
        kinds[base_name(op)] = kinds.get(base_name(op), 0.0) + secs
    ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps[:top]]}
