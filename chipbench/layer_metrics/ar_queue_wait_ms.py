"""Per route completed in the traced window, the time its guard call
waited in the batcher for the generation before it to end: the reader of
``route_queue_wait_ms``, median, ms."""

from chipbench import cells


def read(run):
    return cells.load_module("layer_metrics", "route_queue_wait_ms").read(run)
