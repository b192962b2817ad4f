"""Mean length of the traced ``gen.prefill`` steps of the SHORT bucket,
ms (host clock around stack, H2D, the prefill program and its readback)."""

from chipbench.layer_metrics import _ar_spans, _mix_spans


def read(run):
    return _mix_spans.step_mean_ms(run, _ar_spans.PREFILL, "short")
