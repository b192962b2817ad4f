"""Mean host-clock seconds of one fused trunk-group step (stack, H2D,
program, readback as ONE number — what ``record_step`` times), ms."""

from chipbench.layer_metrics._window import is_fused, step_delta


def read(run):
    d = step_delta(run["steps"], is_fused)
    return d["execute_s"] / d["executes"] * 1e3 if d["executes"] else None
