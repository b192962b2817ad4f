"""Mean length of the traced ``engine.step`` spans of flavour
``gen.prefill`` (host clock around stack, H2D, the prefill program — rows
mapped inside it — and the readback of its report), ms."""

from chipbench.layer_metrics import _ar_spans, _gen_spans


def read(run):
    return _gen_spans.step_mean_ms(run, (_ar_spans.PREFILL,))
