"""Share of the semantic edges that the forward aggregates as dense
adjacency tiles, not edge blocks (the program's ``packing_counts``
``dense_edges`` over ``edges``), forward cells.  Nothing to read in a
program whose counts hold no ``dense_edges``."""
from chipbench import scopes

UNIT = "%"
LAYER = "kernels"
MOVES = "forward_ms"


def read(run):
    model = scopes.forward_model(run)
    counts = list(model.packing_counts().values()) if model is not None else []
    edges = sum(c["edges"] for c in counts)
    if not edges or any("dense_edges" not in c for c in counts):
        return None
    return 100.0 * sum(c["dense_edges"] for c in counts) / edges
