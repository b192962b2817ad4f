"""The chunked gated delta rule's share of its roofline over the traced
prefills, % (``_lin_spans.scope_roofline``: the scan scope's device time
against ``opcount/gated_delta_rule.py`` at the rows' REAL lengths)."""

from chipbench.layer_metrics import _lin_spans


def read(run):
    return _lin_spans.scope_roofline(run, "gated_delta_rule",
                                     "linear_attention")
