"""Useful FLOPs of the full-graph forwards in the window over the chip's bf16 peak."""
from chipbench import readers

UNIT = "%"
LAYER = "model step"
MOVES = "forward_ms"


def read(run):
    return readers.mfu_pct(run, "forward", "forward_flops", "forwards")
