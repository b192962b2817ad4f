"""Device-busy time of the traced window per route the guard completed in
it, ms (the reader of ``gen_device_ms_per_route``)."""

from chipbench import cells


def read(run):
    return cells.load_module("layer_metrics",
                             "gen_device_ms_per_route").read(run)
