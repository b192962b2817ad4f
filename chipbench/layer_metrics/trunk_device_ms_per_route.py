"""Device-op time under the ``trunk`` named scope in the traced window per
route completed in it, ms."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.scope_ms_per_route(run, ("trunk",))
