"""Experts that got at least one routed pair, per expert layer and decode
forward (``experts_touched`` over ``layers`` of the ``engine.gen.forward``
markers of flavour ``gen.decode``), mean: what a decode forward streams."""

from chipbench.layer_metrics import _ar_spans


def read(run):
    per = [int(m["experts_touched"]) / int(m["layers"])
           for _, m in _ar_spans.forwards(run, _ar_spans.DECODE)
           if int(m.get("layers", 0))]
    return sum(per) / len(per) if per else None
