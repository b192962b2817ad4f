"""The full layers' attention cores' share of their roofline over the
traced LONG prefills, % (``_mix_spans.flash_roofline``; the count is
``opcount/gqa_attention.py``'s)."""

from chipbench.layer_metrics import _mix_spans


def read(run):
    return _mix_spans.flash_roofline(run, "full_attention")
