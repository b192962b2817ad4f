"""The part of the traced window's device idle share spent
under no ``engine.step`` (picker, an empty queue, the router's host work),
and what the session's edges leave of the harness's window.  With the other two it sums to the line's idle share
(1 - busy_s / window_s), %."""

from chipbench.layer_metrics import _program_spans


def read(run):
    return _program_spans.idle_share(run, "between_steps")
