"""Device-op time under the ``attn_window`` named scope (a sliding layer's
projections, its core over the window or the ring, and its gate) in the
traced window per route completed in it, short and long alike, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "attn_window")
