"""The median ``engine.queue_wait`` of the traced LONG prompts (bucket
8192), ms (``_mix_spans.queue_wait_ms``)."""

from chipbench.layer_metrics import _mix_spans


def read(run):
    return _mix_spans.queue_wait_ms(run, "long")
