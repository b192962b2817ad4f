"""Mean length of the traced ``engine.step`` spans of flavour
``gen.decode`` (host clock around one LOOP of decode steps: a generation's
31, one device program), ms."""

from chipbench.layer_metrics import _ar_spans, _gen_spans


def read(run):
    return _gen_spans.step_mean_ms(run, (_ar_spans.DECODE,))
