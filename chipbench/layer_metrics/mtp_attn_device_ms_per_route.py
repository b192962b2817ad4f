"""Device-op time under the ``attn`` named scope (the latent attention's
projections, cache write and core; the drafter's block among them) in the
traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "attn")
