"""Mean length of the traced ``engine.step`` spans of flavour
``gen.decode`` (dispatch, the decode program over all rows, the readback
of its small report, the trajectory's bookkeeping), ms."""

from chipbench.layer_metrics import _ar_spans, _gen_spans


def read(run):
    return _gen_spans.step_mean_ms(run, (_ar_spans.DECODE,))
