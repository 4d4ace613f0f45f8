"""Device microseconds of the na_softmax_stats kernel per forward over the
edge blocks it steps over per forward (the program's ``packing_counts``
blocks times the model's layers), forward cells: what a grid step of the
stats kernel costs, beside ``na_seg_sum_us_per_block.forward``; between
models with one head and with eight, what the heads cost per step."""
from chipbench import scopes

UNIT = "us"
LAYER = "kernels"
MOVES = "forward_ms"


def read(run):
    model = scopes.forward_model(run)
    forwards = run["window"].get("forwards")
    if model is None or run.get("trace") is None or not forwards:
        return None
    blocks = sum(c["blocks"] for c in model.packing_counts().values())
    secs, count = run["trace"].kernel("na_softmax_stats")
    if not blocks or not count:
        return None
    return 1e6 * secs / forwards / (blocks * model.cfg.num_layers)
