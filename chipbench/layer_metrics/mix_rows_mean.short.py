"""Real rows of a traced generation of the SHORT bucket, mean: how many
chat turns ride one generation in lock step."""

from chipbench.layer_metrics import _mix_spans


def read(run):
    return _mix_spans.rows_mean(run, "short")
