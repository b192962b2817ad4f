"""What the readers of the sparse-attention guard cell share beyond
``_ar_spans``: the attention cores' share of their roofline over the traced
prefills, and what the decode forwards selected.  On a program without the
annotations (no ``keys_selected`` on the markers, no ``attn_full`` scope)
every function here gives None."""

from __future__ import annotations

from typing import Optional

from chipbench import cells
from chipbench.layer_metrics import _ar_spans, _gen_spans


def selected_share(run, flavour: str) -> Optional[float]:
    """``keys_selected`` over ``keys_visible``, summed over the traced
    forwards of one flavour."""
    chosen = visible = 0
    for _, mark in _ar_spans.forwards(run, flavour):
        if "keys_visible" in mark:
            chosen += int(mark["keys_selected"])
            visible += int(mark["keys_visible"])
    return chosen / visible if visible else None


def latent_attention_roofline(run) -> Optional[float]:
    """The least time for the SELECTED keys of the traced prefills' real
    rows (``opcount/latent_attention.py``; each row at its step's mean real
    length, which is exact: past ``index_topk`` the need is linear in the
    length) over the device time of the ops under the cores' scopes inside
    those same prefills' program runs, %."""
    tr = run.get("trace")
    if not tr or not tr.get("peaks"):
        return None
    oc = cells.load_module("opcount", "latent_attention")
    flops = nbytes = secs = 0.0
    rows = 0
    for step, _ in _ar_spans.forwards(run, _ar_spans.PREFILL):
        n = int(step.facts.get("rows", 0))
        if step.device is None or not n:
            continue
        s = sum(_gen_spans.scope_seconds(run, scope, within=step.device)
                or 0.0 for scope in oc.SCOPES)
        if not s:
            continue
        cost = oc.row_cost(float(step.facts["tokens_real"]) / n,
                           run["config"]["model"])
        flops, nbytes = flops + n * cost["flops"], nbytes + n * cost["bytes"]
        secs, rows = secs + s, rows + n
    if not rows or secs <= 0:
        return None
    least, bound = cells.load_module("opcount", "flash_attention") \
        .least_seconds(flops, nbytes, tr["peaks"])
    print(f"latent attention roofline (prefill): {rows} rows: {flops:.3e} "
          f"operations, {nbytes:.3e} bytes, least {least:.4f} s "
          f"({bound}-bound), measured {secs:.4f} s", flush=True)
    return least / secs * 100.0
