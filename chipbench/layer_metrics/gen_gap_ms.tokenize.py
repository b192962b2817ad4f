"""Mean over the traced gaps between two generations, ms: the
``tokenize`` piece of the device's gap between one generation's last program
and the next one's prefill (``_generation_host`` has the cut)."""

from chipbench.layer_metrics import _generation_host


def read(run):
    return _generation_host.gap_ms(run, "tokenize")
