"""Median of completion minus send time of ``Router.route()`` over the
routes the token-at-a-time guard answered inside the window, ms (the reader
of ``route_latency_p50_ms``, under this cell's own name)."""

from chipbench import cells


def read(run):
    return cells.load_module("layer_metrics", "route_latency_p50_ms").read(run)
