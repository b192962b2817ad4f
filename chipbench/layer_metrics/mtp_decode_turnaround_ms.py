"""The reader of ``ar_decode_turnaround_ms`` under this cell's name: an
accepted entry's ``workloads`` list cannot be edited by the PR that adds a
cell (PERF.md section 7 (a3) merges them)."""

from chipbench import cells


def read(run):
    return cells.load_module("layer_metrics", "ar_decode_turnaround_ms").read(run)
