"""The reader of ``ar_experts_touched_per_decode`` under this cell's name (here: of the 32 held): an
accepted entry's ``workloads`` list cannot be edited by the PR that adds a
cell (PERF.md section 7 (a3) merges them)."""

from chipbench import cells


def read(run):
    return cells.load_module("layer_metrics", "ar_experts_touched_per_decode").read(run)
