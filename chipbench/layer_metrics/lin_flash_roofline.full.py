"""The full layers' attention cores' share of their roofline over the
traced prefills, % (``_lin_spans.scope_roofline``; the count is
``opcount/mha_attention.py``'s)."""

from chipbench.layer_metrics import _lin_spans


def read(run):
    return _lin_spans.scope_roofline(run, "mha_attention", "full_attention")
