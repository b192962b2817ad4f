"""Mean length of the traced ``engine.step`` spans of flavour
``gen.prefill`` (host clock around stack, H2D, the prefill program and its
readback), ms."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    return _gen_spans.step_mean_ms(run, ("gen.prefill",))
