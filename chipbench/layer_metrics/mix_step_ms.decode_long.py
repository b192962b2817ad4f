"""Mean length of the traced ``gen.decode`` steps (a generation's loop of
decode steps, one program) of the LONG bucket, ms: its full layers read a
whole cache of 8,256 columns a step, its sliding layers a ring of 512."""

from chipbench.layer_metrics import _ar_spans, _mix_spans


def read(run):
    return _mix_spans.step_mean_ms(run, _ar_spans.DECODE, "long")
