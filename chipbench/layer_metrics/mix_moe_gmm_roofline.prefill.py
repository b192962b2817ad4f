"""The grouped matmuls' share of their roofline over the traced PREFILL
forwards of both buckets, % (``opcount/moe_gmm.py`` as it is, at H 3072, I
1024, 128 experts held; the pairs and experts the forwards really routed)."""

from chipbench.layer_metrics import _ar_spans, _mix_spans


def read(run):
    return _mix_spans.gmm_roofline(run, _ar_spans.PREFILL)
