"""Of the device's idle seconds between the session's first and last op,
the share that lies inside the gaps the ``gen_turn_ms.*`` and
``gen_gap_ms.*`` readers cut into named pieces, %."""

from chipbench.layer_metrics import _generation_host


def read(run):
    return _generation_host.accounted_share(run)
