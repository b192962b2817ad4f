"""Device-op time under the ``mlp`` named scope (every layer's SwiGLU;
prefill and decode) in the traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "mlp")
