"""Host seconds of the program's first forward call, by its own clock
(``CompiledHGNN.timings["forward_compile"]``: tracing, compiling or
loading from the persistent compile cache, and dispatch), forward cells."""
from chipbench import scopes

UNIT = "s"
LAYER = "model step"
MOVES = "setup_s"


def read(run):
    model = scopes.forward_model(run)
    value = getattr(model, "timings", {}).get("forward_compile")
    return None if value is None else float(value)
