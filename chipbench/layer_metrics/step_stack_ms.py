"""Mean length of ``engine.step.stack`` per step in the traced window, ms:
padding the items' token ids into one [padded_rows, bucket] host batch
and counting overflows."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.stage_mean_ms(run, "stack")
