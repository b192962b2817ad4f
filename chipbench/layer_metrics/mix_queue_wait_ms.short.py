"""The median ``engine.queue_wait`` of the traced SHORT prompts (bucket
512), ms: what a chat turn waits in the batcher behind whatever group runs
before its own — long prefills among them (``_mix_spans.queue_wait_ms``)."""

from chipbench.layer_metrics import _mix_spans


def read(run):
    return _mix_spans.queue_wait_ms(run, "short")
