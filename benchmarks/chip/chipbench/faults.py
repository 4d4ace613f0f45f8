"""Faults planted under the timed path, for the checks' own tests and for
reading a fault's number on the chip.  Each is a ``fault(kind, obj)``
hook for the drivers (see ``drivers.no_fault``)."""
from __future__ import annotations

ALTER = 0.1  # added to one logit


def altered_answer(kind, obj):
    """One logit of the full forward's output is altered where it is
    produced."""
    if kind != "forward":
        return obj
    return lambda p, f: obj(p, f).at[0, 0].add(ALTER)


FAULTS = {"altered_answer": altered_answer}
FAULTS_BY_ENTRY = {"forward": ["altered_answer"]}
