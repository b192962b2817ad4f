"""Mean host-clock seconds of one embedding-task step, ms."""

from chipbench.layer_metrics._window import is_embedding, step_delta


def read(run):
    d = step_delta(run["steps"], is_embedding)
    return d["execute_s"] / d["executes"] * 1e3 if d["executes"] else None
