"""The device's idle share of the traced window, % (the reader of
``gen_device_idle_share``)."""

from chipbench import cells


def read(run):
    return cells.load_module("layer_metrics",
                             "gen_device_idle_share").read(run)
