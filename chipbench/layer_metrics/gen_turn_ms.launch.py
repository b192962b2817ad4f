"""Mean over the traced pairs of consecutive forwards of one generation,
ms: the ``launch`` piece of the device's gap between their two programs
(``_generation_host`` has the cut)."""

from chipbench.layer_metrics import _generation_host


def read(run):
    return _generation_host.turn_ms(run, "launch")
