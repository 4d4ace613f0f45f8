"""Device ms per forward under the program's ``layer<i>/fp/<type>``
scopes (feature projection), forward cells."""
from chipbench import scopes

UNIT = "ms"
LAYER = "FP"
MOVES = "forward_ms"


def read(run):
    return scopes.ms_per_forward(run, ("fp",))
