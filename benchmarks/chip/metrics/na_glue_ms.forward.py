"""Device ms per forward of neighbour aggregation outside its two kernels:
the ops under the program's ``layer<i>/na/<metapath>`` scopes other than
``na_seg_sum`` and ``na_softmax_stats`` (projection, banded gathers,
logits, blocked scatters, alpha, the scatter back), forward cells."""
from chipbench import scopes

UNIT = "ms"
LAYER = "NA"
MOVES = "forward_ms"


def read(run):
    return scopes.ms_per_forward(run, ("na_glue",))
