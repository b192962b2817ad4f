"""What the readers of the token-at-a-time guard cell share beyond
``_gen_spans``: a flavour's forwards alone (prefill is compute-bound,
decode memory-bound: one share of a roofline over both says nothing of
either), and a decode step's turn-around.  On a program without the
annotations every function here gives None.
"""

from __future__ import annotations

from typing import Optional

from chipbench import cells
from chipbench.layer_metrics import _gen_spans

PREFILL, DECODE = "gen.prefill", "gen.decode"


def forwards(run, flavour: str):
    return [(st, m) for st, m in _gen_spans.forwards(run)
            if st.facts.get("flavour") == flavour]


def gmm_roofline(run, flavour: str) -> Optional[float]:
    """``moe_gmm_roofline``'s reckoning (``opcount/moe_gmm.py`` unchanged)
    over the traced forwards of one flavour: the least time for their
    routed pairs and touched experts over the device time of the ops under
    ``moe/gmm`` inside those same forwards' program runs, %."""
    tr = run.get("trace")
    if not tr or not tr.get("peaks"):
        return None
    oc = cells.load_module("opcount", "moe_gmm")
    pairs = touched = secs = 0.0
    n = 0
    for step, mark in forwards(run, flavour):
        if step.device is None or "pairs" not in mark:
            continue
        s = _gen_spans.scope_seconds(run, oc.SCOPE, within=step.device)
        if not s:
            continue
        pairs += float(mark["pairs"])
        touched += float(mark["experts_touched"])
        secs, n = secs + s, n + 1
    if not n or secs <= 0:
        return None
    cost = oc.forward_cost(pairs, touched, run["config"]["model"])
    least, bound = cells.load_module("opcount", "flash_attention") \
        .least_seconds(cost["flops"], cost["bytes"], tr["peaks"])
    print(f"moe gmm roofline ({flavour}): {n} forwards, {pairs:.0f} pairs, "
          f"{touched:.0f} experts touched: {cost['flops']:.3e} operations, "
          f"{cost['bytes']:.3e} bytes, least {least:.4f} s ({bound}-bound), "
          f"measured {secs:.4f} s", flush=True)
    return least / secs * 100.0
