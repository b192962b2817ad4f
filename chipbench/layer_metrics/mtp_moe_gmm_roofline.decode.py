"""The grouped matmuls' share of their roofline over the traced DECODE
steps, % (``opcount/moe_gmm.py`` as it is, at H 2048, I 768, 128 experts
held; the pairs and the experts touched are those the two-position steps
really routed, a rejected second position of the drafter's block routes
none; memory-bound: a touched expert's matrices are read for a handful of
pairs)."""

from chipbench.layer_metrics import _ar_spans


def read(run):
    return _ar_spans.gmm_roofline(run, _ar_spans.DECODE)
