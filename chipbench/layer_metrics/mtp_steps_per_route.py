"""Decode steps a route was live for: (live row, step) pairs of the traced
``gen.decode`` steps (their ``drafted``) over the rows of the traced
``gen.prefill`` steps.  31 where no draft is accepted, 16 where all are."""

from chipbench.layer_metrics import _ar_spans, _mtp_spans


def read(run):
    rows = sum(int(st.facts.get("rows", 0))
               for st, _ in _ar_spans.forwards(run, _ar_spans.PREFILL))
    pairs = sum(int(m["drafted"]) for m in _mtp_spans.steps(run))
    return pairs / rows if rows and pairs else None
