"""The grouped matmuls' share of their roofline over the traced DECODE
forwards, % (memory-bound: a touched expert's matrices for a pair or two)."""

from chipbench.layer_metrics import _ar_spans


def read(run):
    return _ar_spans.gmm_roofline(run, _ar_spans.DECODE)
