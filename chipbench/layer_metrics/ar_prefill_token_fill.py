"""Real over padded positions of the traced prefill steps: ``tokens_real``
over ``padded_rows`` x ``bucket`` (facts of the ``engine.step`` spans of
flavour ``gen.prefill``), summed over the steps."""

from chipbench.layer_metrics import _ar_spans, _gen_spans


def read(run):
    mine = [st.facts for st in _gen_spans.steps(run) or ()
            if st.facts.get("flavour") == _ar_spans.PREFILL]
    padded = sum(int(f["padded_rows"]) * int(f["bucket"]) for f in mine)
    return sum(int(f["tokens_real"]) for f in mine) / padded \
        if padded else None
