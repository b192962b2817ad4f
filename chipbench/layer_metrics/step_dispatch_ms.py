"""Mean length of ``engine.step.dispatch`` per step in the traced window, ms:
the jitted call returning — the enqueue of a warm program, not its run."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.stage_mean_ms(run, "dispatch")
