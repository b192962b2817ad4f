"""The part of the traced window's device idle share spent
inside an ``engine.step`` once its program has begun: readback tail, demux,
finish.  With the other two it sums to the line's idle share
(1 - busy_s / window_s), %."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.idle_share(run, "step_tail")
