"""The device's idle share of the traced window, %: 1 - busy / window of
the line's ``device`` block."""

from chipbench.layer_metrics import _gen_spans


def read(run):
    tr = run.get("trace")
    if not tr or _gen_spans.steps(run) is None:
        return None
    window_s = tr["window"][1] - tr["window"][0]
    return (1.0 - tr["busy_s"] / window_s) * 100.0 if window_s > 0 else None
