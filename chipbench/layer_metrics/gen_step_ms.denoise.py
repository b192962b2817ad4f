"""Mean length of the traced ``engine.step`` spans of one block forward
(flavours ``gen.denoise`` and ``gen.commit``: dispatch, the program, the
readback of its report, the trajectory's bookkeeping), ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.step_mean_ms(run, ("gen.denoise", "gen.commit"))
