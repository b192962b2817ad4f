"""Experts that got at least one routed pair, per expert layer (the five
held and the drafter's block) and decode step: what a step of two
positions a row streams (``ar_experts_touched_per_decode``'s reader; here
of the 128 held)."""

from chipbench import cells


def read(run):
    return cells.load_module(
        "layer_metrics", "ar_experts_touched_per_decode").read(run)
