"""Real rows per device step, all programs, over the window
(``runtimestats.record_step``)."""

from chipbench.layer_metrics._window import step_delta


def read(run):
    d = step_delta(run["steps"])
    return d["rows_real"] / d["executes"] if d["executes"] else None
