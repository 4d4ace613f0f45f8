"""Device time per program scope: the traced window's time per HLO
instruction joined with the program's map from instruction to scope.

The program names each sub-stage of its forward with a ``named_scope``
(``layer<i>/fp/<type>``, ``layer<i>/na/<metapath>``, ``layer<i>/sf/<type>``,
``head``; see ``repro.obs``), and ``repro.obs.forward_scopes`` maps every
instruction of the compiled forward to its scope.  A TPU trace names its
device ops by the same instruction names (``tracing.op_name``), so the
join is exact: no op is matched by a guess at its kind.

The join itself (:func:`seconds_by_scope`, :func:`stage_seconds`) is pure
and is tested on recorded data; :func:`forward_model` finds the program's
one compiled model through its registry, and returns None where the
program has none (a checkout older than the registry included).
"""
from __future__ import annotations

from typing import Dict, Optional

KERNELS = ("na_seg_sum", "na_softmax_stats")
UNSCOPED = ""
STAGES = ("fp", "na_kernels", "na_glue", "sf", "head", "unscoped")


def seconds_by_scope(op_seconds: Dict[str, float],
                     scope_of: Dict[str, str]) -> Dict[str, float]:
    """Device seconds per scope; ops the map does not hold go under
    ``UNSCOPED``."""
    out: Dict[str, float] = {}
    for op, secs in op_seconds.items():
        key = scope_of.get(op, UNSCOPED)
        out[key] = out.get(key, 0.0) + secs
    return out


def is_kernel(op: str) -> bool:
    """Whether an instruction is one of the two NA kernels (``na_seg_sum.9``)."""
    return op.split(".", 1)[0] in KERNELS


def stage_of(op: str, scope: Optional[str]) -> str:
    """The stage an op's time counts under (one of ``STAGES``)."""
    if not scope:
        return "unscoped"
    if scope == "head":
        return "head"
    kind = scope.split("/")[1]
    if kind == "na":
        return "na_kernels" if is_kernel(op) else "na_glue"
    return kind


def stage_seconds(op_seconds: Dict[str, float],
                  scope_of: Dict[str, str]) -> Dict[str, float]:
    """Device seconds per stage: FP, the NA kernels, the rest of NA (the
    glue: projection, gathers, logits, scatters, alpha), SF, the head, and
    what lies under no scope.  The stages add up to the ops' total."""
    out = dict.fromkeys(STAGES, 0.0)
    for op, secs in op_seconds.items():
        out[stage_of(op, scope_of.get(op))] += secs
    return out


def scoped_share(op_seconds: Dict[str, float], scope_of: Dict[str, str]) -> float:
    """The share of the ops' device time that lies under a scope."""
    total = sum(op_seconds.values())
    scoped = sum(s for op, s in op_seconds.items() if op in scope_of)
    return scoped / total if total > 0 else 0.0


def forward_model(run: Dict):
    """The program's one live compiled model with a built forward, in a
    forward cell's run; else None."""
    if run.get("kind") != "forward":
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    models = [m for m in obs.live_models() if m.forward_built]
    return models[0] if len(models) == 1 else None


def ms_per_forward(run: Dict, stages) -> Optional[float]:
    """Device ms per forward of the ops in ``stages``, in a traced forward
    run whose program maps its ops to scopes; else None."""
    model = forward_model(run)
    if model is None or run.get("trace") is None or not run["window"].get("forwards"):
        return None
    from repro import obs

    scope_of = obs.forward_scopes(model)
    if not scope_of:
        return None
    secs = stage_seconds(run["trace"].op_seconds, scope_of)
    return 1e3 * sum(secs[s] for s in stages) / run["window"]["forwards"]
