"""Device-op time under the ``linear_attn/scan`` named scope (the gated
delta rule of every linear layer: the chunked op of a prefill, the one-token
step of a decode) in the traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "linear_attn/scan")
