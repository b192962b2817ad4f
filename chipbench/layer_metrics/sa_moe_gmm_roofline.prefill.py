"""The grouped matmuls' share of their roofline over the traced PREFILL
forwards, % (``opcount/moe_gmm.py`` as it is, at H 5120, I 1536, 32 experts
held; compute-bound: thousands of pairs an expert)."""

from chipbench.layer_metrics import _ar_spans


def read(run):
    return _ar_spans.gmm_roofline(run, _ar_spans.PREFILL)
