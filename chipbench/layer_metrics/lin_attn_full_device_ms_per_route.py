"""Device-op time under the ``attn`` named scope (the full layers:
projections, the norms of q and k, the core, the cache writes; prefill and
decode) in the traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "attn")
