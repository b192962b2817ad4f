"""Device-op time under the ``heads``, ``token_heads`` and ``pool`` named
scopes in the traced window per route completed in it, ms."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.scope_ms_per_route(
        run, _program_spans.HEAD_SCOPES)
