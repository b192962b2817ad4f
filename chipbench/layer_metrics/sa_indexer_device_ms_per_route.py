"""Device-op time under the ``indexer`` named scope (its projections, the
scores of all heads and the exact top-k threshold: ``indexer/scores`` +
``indexer/select``) in the traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "indexer")
