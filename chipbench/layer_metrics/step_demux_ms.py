"""Mean length of ``engine.step.demux`` per step in the traced window, ms:
the per-item softmax and span loop over what came back."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.stage_mean_ms(run, "demux")
