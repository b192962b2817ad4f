"""The busiest expert's routed pairs over the mean, per expert layer and
forward, averaged over the traced forwards (computed on the device, read
with each forward's report, written as ``load_milli`` of the
``engine.gen.forward`` marker); prefill forwards count like the others."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    loads = [int(m["load_milli"]) / 1000.0
             for _, m in _gen_spans.forwards(run) if "load_milli" in m]
    return sum(loads) / len(loads) if loads else None
