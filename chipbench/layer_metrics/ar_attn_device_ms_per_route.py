"""Device-op time under the ``attn`` named scope (projections, RoPE, the
flash kernel of a prefill, the cache write and the attention over the cache
of a decode) in the traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "attn")
