"""Real rows of a generative step (``rows`` of the traced ``engine.step``
spans whose flavour is ``gen.*``), mean: how many callers ride one
generation in lock step."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    steps = _gen_spans.steps(run)
    if not steps:
        return None
    return sum(int(st.facts.get("rows", 0)) for st in steps) / len(steps)
