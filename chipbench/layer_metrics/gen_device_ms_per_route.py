"""Device-busy time of the traced window per route the guard completed in
it, ms (the reader of ``device_ms_per_route``; None unless the program
wrote ``gen.*`` steps)."""

from chipbench import cells
from chipbench.layer_metrics import _gen_spans


def read(run):
    if _gen_spans.steps(run) is None:
        return None
    return cells.load_module("layer_metrics", "device_ms_per_route").read(run)
