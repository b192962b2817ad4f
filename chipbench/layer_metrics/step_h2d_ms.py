"""Mean length of ``engine.step.h2d`` per step in the traced window, ms:
handing the batch to the device (``_to_device``, and the packed path's
position and segment planes)."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.stage_mean_ms(run, "h2d")
