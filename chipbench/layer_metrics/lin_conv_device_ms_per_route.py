"""Device-op time under the ``linear_attn/conv1d`` named scope (the three
depthwise causal convolutions, their ``silu`` and the L2 norms of q and k;
prefill and decode) in the traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "linear_attn/conv1d")
