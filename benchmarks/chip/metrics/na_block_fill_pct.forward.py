"""Valid edges over edge slots of the program's banded packings (its
``packing_counts``): the share of the NA kernels' per-block work that
aggregates a real edge, forward cells."""
from chipbench import scopes

UNIT = "%"
LAYER = "layout (restructure and pack)"
MOVES = "forward_ms"


def read(run):
    model = scopes.forward_model(run)
    counts = model.packing_counts() if model is not None else {}
    slots = sum(c["slots"] for c in counts.values())
    if not slots:
        return None
    return 100.0 * sum(c["edges"] for c in counts.values()) / slots
