"""What the compiled HGNN tells an observer: scope names, live models, and
the map from a compiled forward's device ops to those scopes.

The one HGNN layer loop (``HGNN._layers``, which the full-graph,
dependency-subset and sharded paths all run) names its sub-stages with
``jax.named_scope`` in one grammar, built only by the helpers here:

  * ``layer<i>/fp/<vertex type>`` — feature projection and its ReLU;
  * ``layer<i>/na/<metapath>``    — everything neighbour aggregation does
    for one semantic graph: the ``w_rel`` projection, the banded gathers,
    the attention logits, the blocked scatters, both NA kernels, alpha and
    the scatter back to global order (the sharded executor's merged kernel
    pair serves several metapaths and is named ``MAM+MDM+...``);
  * ``layer<i>/sf/<vertex type>`` — the self path, the semantic-attention
    beta, the weighted sum and the ReLU;
  * ``head``                      — the classifier.

A scope is HLO metadata only (``metadata={op_name="jit(fwd)/layer0/na/MAM/
..."}``): the compiled instructions are the same with or without it, so it
costs nothing when nobody traces.

``CompiledHGNN`` objects register themselves here when they are built
(a weak registry: it keeps nothing alive).  :func:`forward_scopes` maps
each HLO instruction of a model's compiled forward — the names a TPU
profiler trace gives its device ops — to its scope.  It compiles the
forward again at the argument shapes of its first call (the persistent
compile cache serves that), so it is for after a measured window, never
on the forward's path.
"""
from __future__ import annotations

import re
import weakref
from typing import Dict, List, Optional

import jax

HEAD = "head"

# a scope inside an op_name path ("jit(fwd)/layer0/na/MAM/dot_general");
# "(" and ")" also delimit, as in the name stacks of transformed code
SCOPE_RE = re.compile(
    r"(?:^|[/(])(layer\d+/(?:fp|na|sf)/[^/()]+|head)(?=[/)]|$)")


def fp_scope(layer: int, vertex_type: str) -> str:
    """``layer<i>/fp/<type>``."""
    return f"layer{layer}/fp/{vertex_type}"


def na_scope(layer: int, metapath: str) -> str:
    """``layer<i>/na/<metapath>``."""
    return f"layer{layer}/na/{metapath}"


def sf_scope(layer: int, vertex_type: str) -> str:
    """``layer<i>/sf/<type>``."""
    return f"layer{layer}/sf/{vertex_type}"


def scope(name: str):
    """The context that puts the ops traced inside it under ``name``."""
    return jax.named_scope(name)


def scope_of_op_name(op_name: str) -> Optional[str]:
    """The scope an HLO ``op_name`` path lies under, or None."""
    m = SCOPE_RE.search(op_name)
    return m.group(1) if m else None


# ------------------------------------------------------------- registry --
_LIVE: "weakref.WeakSet" = weakref.WeakSet()


def register(model) -> None:
    """Record a live compiled model (called by ``CompiledHGNN.__init__``)."""
    _LIVE.add(model)


def live_models() -> List:
    """Every registered model still alive, in no particular order."""
    return list(_LIVE)


# ------------------------------------------------------ op -> scope map --
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%([^\s(=]+)\s*\(.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?%([^\s=]+)\s*=\s*(?:\(.*?\)|\S+)\s+[\w\-]+\((.*?)\)(?:,|$)")
_OPERAND_RE = re.compile(r"%([^\s,()]+)")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%([^\s,}]+)")


def scopes_of_hlo(text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` over an HLO module's text.

    An instruction takes the scope of its own ``op_name``; one without
    (a fusion, or an op a compiler pass made) takes that of its called
    computation's root, else that of its first operand that has one: it
    carries on that operand's work.  Instructions under no scope (the
    parameters, constants and copies of constants) are left out."""
    out: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        c = _COMP_RE.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        root, name, operands = m.groups()
        op = _OP_NAME_RE.search(line)
        sc = scope_of_op_name(op.group(1)) if op else None
        called = _CALLS_RE.search(line)
        if sc is None and called:
            sc = out.get(roots.get(called.group(1), ""))
        if sc is None:
            sc = next((out[o] for o in _OPERAND_RE.findall(operands) if o in out),
                      None)
        if sc is not None:
            out[name] = sc
        if root and comp is not None:
            roots[comp] = name
    return out


def forward_scopes(model) -> Optional[Dict[str, str]]:
    """``{instruction name: scope}`` of ``model``'s compiled forward, or
    None before its first call."""
    exe = model.forward_executable()
    return None if exe is None else scopes_of_hlo(exe.as_text())
