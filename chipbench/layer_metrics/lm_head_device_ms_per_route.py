"""Device-op time under the ``lm_head`` named scope (final norm and the
untied head over the whole vocabulary) in the traced window per route
completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "lm_head")
