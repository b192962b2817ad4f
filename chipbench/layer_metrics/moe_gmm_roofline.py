"""The expert layers' grouped matmuls' share of their roofline in the traced
window, %: the least time the chip could take for the routed pairs and the
experts touched (``opcount/moe_gmm.py`` over ``peaks.json``) over the device
time of the ops under scope ``moe/gmm``.

Both sides come from ONE set of forwards: the traced ``gen.*`` steps whose
program run is found on the device and whose ``engine.gen.forward`` marker
is in the trace.  The time is the scope's ops inside those runs; the need is
those markers' ``pairs`` and ``experts_touched`` (sums over the layers,
counted on the device)."""

from chipbench import cells
from chipbench.layer_metrics import _gen_spans


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("peaks"):
        return None
    oc = cells.load_module("opcount", "moe_gmm")
    pairs = touched = secs = 0.0
    n = 0
    for step, mark in _gen_spans.forwards(run):
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
    print(f"moe gmm roofline: {n} forwards, {pairs:.0f} pairs, {touched:.0f} "
          f"experts touched: {cost['flops']:.3e} operations, "
          f"{cost['bytes']:.3e} bytes, least {least:.4f} s ({bound}-bound), "
          f"measured {secs:.4f} s", flush=True)
    return least / secs * 100.0
