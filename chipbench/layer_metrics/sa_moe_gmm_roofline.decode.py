"""The grouped matmuls' share of their roofline over the traced DECODE
forwards, % (``opcount/moe_gmm.py`` as it is, at H 5120, I 1536, 32 experts
held; memory-bound: a touched expert's matrices are read for a handful of
pairs)."""

from chipbench.layer_metrics import _ar_spans


def read(run):
    return _ar_spans.gmm_roofline(run, _ar_spans.DECODE)
