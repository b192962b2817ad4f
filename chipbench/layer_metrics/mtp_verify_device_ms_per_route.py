"""Device-op time under the ``verify`` named scope (the main model's layers
and head at a step's two positions) in the traced window per route
completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "verify")
