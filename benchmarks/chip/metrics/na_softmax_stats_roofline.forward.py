"""The na_softmax_stats kernel's share of its roofline in the forward cells."""
from chipbench import readers

UNIT = "%"
LAYER = "kernels"
MOVES = "forward_ms"


def read(run):
    return readers.roofline_pct(run, "forward", "na_softmax_stats")
