"""Real rows of a generative step, mean (the reader of ``gen_rows_mean``):
how many callers ride one generation in lock step."""

from chipbench import cells


def read(run):
    return cells.load_module("layer_metrics", "gen_rows_mean").read(run)
