"""The flash kernel's share of its roofline in the traced window, %: the
least time the chip could take for the attention the window's routes
needed (operations and bytes from ``opcount/flash_attention.py`` over
``peaks.json``) over the kernel's measured device time.

The window's need: every route completed inside it, at its real token
count, times the row-forwards the engine ran per route in that window
(the program's step counters)."""

from chipbench import cells, reduce_trace
from chipbench.layer_metrics._window import step_delta


def read(run):
    tr = run["trace"]
    if not tr or not tr["completed"] or not tr["peaks"]:
        return None
    oc = cells.load_module("opcount", "flash_attention")
    secs, calls = reduce_trace.seconds_matching(tr, oc.EVENT_PATTERN)
    if not calls or secs <= 0:
        return None
    per_route = step_delta(tr["steps"])["rows_real"] / len(tr["completed"])
    flops = nbytes = 0.0
    for r in tr["completed"]:
        c = oc.forward_cost(r.n_tokens, run["config"]["model"])
        flops += c["flops"] * per_route
        nbytes += c["bytes"] * per_route
    least, bound = oc.least_seconds(flops, nbytes, tr["peaks"])
    print(f"flash roofline: {flops:.3e} operations, {nbytes:.3e} bytes, "
          f"least {least:.4f} s ({bound}-bound), measured {secs:.4f} s in "
          f"{calls} calls", flush=True)
    return least / secs * 100.0
