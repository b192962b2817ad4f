"""The flash kernel's share of its roofline in the traced window, %: the
least time the chip could take for the attention its calls were for
(operations and bytes from ``opcount/flash_attention.py`` over
``peaks.json``) over the kernel's measured device time.

Both sides come from ONE set of steps: the traced ``engine.step`` spans
whose program run is found on the device.  The time is the kernel's calls
inside those runs; the need is those steps' real rows (the ``rows`` fact),
each at its step's mean real length (``tokens_real`` / ``rows``): exact for
a step of one row, and up to a seventh low where a step's rows differ in
length (the operations grow with the square), never high.  Until PR 27 the
rows came from the step counters, read before ``start_trace`` and after
``stop_trace`` returned — seconds more of steps than the window whose
completions they were divided by — and the share read 2.6-3.5x too high
(PERF.md section 6)."""

from chipbench import cells
from chipbench.layer_metrics import _program_spans


def read(run):
    tr = run["trace"]
    if not tr or not tr["peaks"]:
        return None
    ps = _program_spans.load(run)
    if ps is None:
        return None
    oc = cells.load_module("opcount", "flash_attention")
    flops = nbytes = secs = 0.0
    calls = rows = steps = 0
    for step, step_secs, step_calls in _program_spans.kernel_in_steps(
            ps, oc.EVENT_PATTERN):
        n = int(step.facts.get("rows", 0))
        if not n:
            continue
        cost = oc.forward_cost(float(step.facts["tokens_real"]) / n,
                               run["config"]["model"])
        flops += n * cost["flops"]
        nbytes += n * cost["bytes"]
        secs, calls = secs + step_secs, calls + step_calls
        rows, steps = rows + n, steps + 1
    if not calls or secs <= 0:
        return None
    least, bound = oc.least_seconds(flops, nbytes, tr["peaks"])
    print(f"flash roofline: {rows} rows in {steps} steps: {flops:.3e} "
          f"operations, {nbytes:.3e} bytes, least {least:.4f} s "
          f"({bound}-bound), measured {secs:.4f} s in {calls} calls",
          flush=True)
    return least / secs * 100.0
