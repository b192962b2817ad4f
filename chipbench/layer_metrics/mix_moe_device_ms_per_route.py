"""Device-op time under the ``moe`` named scope (router, sort, grouped
matmuls, combine, the gated shared expert) in the traced window per route
completed in it, short and long alike, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "moe")
