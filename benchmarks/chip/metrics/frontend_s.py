"""Host seconds of the program's frontend for the cell's model, by its own
clock (``FrontendResult.timings``: SGB, restructure, pack and the banded
batches), forward cells."""
from chipbench import scopes

UNIT = "s"
LAYER = "frontend (host)"
MOVES = "setup_s"


def read(run):
    model = scopes.forward_model(run)
    if model is None:
        return None
    return float(model.frontend.timings["total"])
