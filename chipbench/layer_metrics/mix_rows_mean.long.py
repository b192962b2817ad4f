"""Real rows of a traced generation of the LONG bucket, mean."""

from chipbench.layer_metrics import _mix_spans


def read(run):
    return _mix_spans.rows_mean(run, "long")
