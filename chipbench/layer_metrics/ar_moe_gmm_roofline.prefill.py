"""The grouped matmuls' share of their roofline over the traced PREFILL
forwards, % (compute-bound: thousands of pairs an expert)."""

from chipbench.layer_metrics import _ar_spans


def read(run):
    return _ar_spans.gmm_roofline(run, _ar_spans.PREFILL)
