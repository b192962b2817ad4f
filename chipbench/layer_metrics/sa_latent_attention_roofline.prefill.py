"""The attention cores' share of their roofline over the traced PREFILL
forwards, %: the need is the SELECTED keys only, the kernel is dense under a
mask (``_sa_spans.latent_attention_roofline``)."""

from chipbench.layer_metrics import _sa_spans


def read(run):
    return _sa_spans.latent_attention_roofline(run)
