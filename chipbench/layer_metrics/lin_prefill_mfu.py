"""The share of the chip's bfloat16 peak that the traced prefill STEPS
reach with the model's operations for their real tokens, %
(``_lin_spans.prefill_mfu``)."""

from chipbench.layer_metrics import _lin_spans


def read(run):
    return _lin_spans.prefill_mfu(run)
