"""Device-op time under the ``conv`` named scope (``in_proj``, ``conv1d``,
``out_proj`` of every short-convolution layer; prefill and decode) in the
traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "conv")
