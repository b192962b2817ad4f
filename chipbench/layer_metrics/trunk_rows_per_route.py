"""Row-forwards the engine ran per completed route (every program's real
rows over the window / routes): what deduplication, cascades and caches
lower."""

from chipbench.layer_metrics._window import step_delta


def read(run):
    n = len(run["completed"])
    return step_delta(run["steps"])["rows_real"] / n if n else None
