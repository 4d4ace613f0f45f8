"""Device ms per forward under the program's ``layer<i>/sf/<type>`` scopes
(semantic fusion) and its ``head`` scope, forward cells."""
from chipbench import scopes

UNIT = "ms"
LAYER = "SF and head"
MOVES = "forward_ms"


def read(run):
    return scopes.ms_per_forward(run, ("sf", "head"))
