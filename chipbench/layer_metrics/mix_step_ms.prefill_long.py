"""Mean length of the traced ``gen.prefill`` steps of the LONG bucket,
ms: what a short turn that arrives meanwhile waits behind."""

from chipbench.layer_metrics import _ar_spans, _mix_spans


def read(run):
    return _mix_spans.step_mean_ms(run, _ar_spans.PREFILL, "long")
