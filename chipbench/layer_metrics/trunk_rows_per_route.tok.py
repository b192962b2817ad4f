"""``trunk_rows_per_route`` of the window, the part that ``tok`` steps ran:
by the share of the traced window's ``engine.step`` rows whose ``flavour`` is
``tok``.  With the other three flavours it sums to ``trunk_rows_per_route``."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.rows_per_route(run, "tok")
