"""Share of the window in which no operation ran on the device, forward cells."""
from chipbench import readers

UNIT = "%"
LAYER = "device"
MOVES = "forward_ms"


def read(run):
    return readers.idle_pct(run, "forward")
