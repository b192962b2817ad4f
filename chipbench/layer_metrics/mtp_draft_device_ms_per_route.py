"""Device-op time under the ``mtp`` named scope (the drafter: ``eh_proj``, its
block, its norm and the head; a prefill's run over the prompt among them)
in the traced window per route completed in it, ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.scope_ms_per_route(run, "mtp")
