"""What the readers of the linear-attention guard cell share beyond
``_ar_spans`` and ``_mix_spans``: the traced prefills with their rows' REAL
lengths (``_mix_spans._lengths_by_step``: the cell has one bucket, its
``long`` one), a scope's share of its roofline over them, the recurrent
state's share of the cache, and the share of the chip's peak that a whole
prefill step reaches.

The program writes ``cache_bytes_full`` / ``cache_bytes_state`` /
``cache_bytes_conv`` on a prefill's ``engine.gen.forward`` marker and the
scopes ``linear_attn/scan``, ``linear_attn/conv1d``, ``attn/core``, ``mlp``
since the PR that added this cell.  On a program without them — that PR's
parent — every function here gives None.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench import cells
from chipbench.layer_metrics import _ar_spans, _gen_spans, _mix_spans


def _layers(model: Dict[str, Any], kind: str) -> int:
    return list(model["layer_types"]).count(kind)


def scope_roofline(run, opcount: str, kind: str) -> Optional[float]:
    """The least time for what ``opcount/<opcount>.py`` counts over the
    traced prefills' real rows, each at its real length, in the layers of
    ``kind``, over the device time of the ops under the opcount's scope
    inside those same prefills' program runs, %."""
    tr = run.get("trace")
    if not tr or not tr.get("peaks"):
        return None
    oc = cells.load_module("opcount", opcount)
    model = run["config"]["model"]
    layers = _layers(model, kind)
    flops = nbytes = secs = 0.0
    rows = 0
    for st, lengths in _mix_spans._lengths_by_step(run, "long"):
        s = _gen_spans.scope_seconds(run, oc.SCOPE, within=st.device)
        if not s:
            continue
        for n in lengths:
            cost = oc.row_cost(n, model)
            flops += layers * cost["flops"]
            nbytes += layers * cost["bytes"]
        secs, rows = secs + s, rows + len(lengths)
    if not rows or secs <= 0:
        return None
    least, bound = cells.load_module("opcount", "flash_attention") \
        .least_seconds(flops, nbytes, tr["peaks"])
    print(f"{opcount} roofline (prefills): {rows} rows in {layers} layers: "
          f"{flops:.3e} operations, {nbytes:.3e} bytes, least {least:.4f} s "
          f"({bound}-bound), measured {secs:.4f} s", flush=True)
    return least / secs * 100.0


def state_cache_share(run) -> Optional[float]:
    """The matrix states' and conv windows' bytes over all cache bytes of
    the traced prefills, %."""
    state = whole = 0
    for _, mark in _mix_spans.forwards(run, _ar_spans.PREFILL, "long"):
        if "cache_bytes_state" in mark:
            mine = int(mark["cache_bytes_state"]) \
                + int(mark.get("cache_bytes_conv", 0))
            state += mine
            whole += mine + int(mark.get("cache_bytes_full", 0))
    return state / whole * 100.0 if whole else None


def token_flops(model: Dict[str, Any]) -> float:
    """The matrix products a token passes in the layers: 2 operations a
    parameter of every projection and of the SwiGLU."""
    H, W = model["hidden_size"], model["intermediate_size"]
    n, dk, dv = (model["linear_num_key_heads"], model["linear_key_head_dim"],
                 model["linear_value_head_dim"])
    linear = H * (2 * n * dk + 2 * n * dv + 2 * n) + n * dv * H
    full = 4 * H * H
    return 2.0 * (_layers(model, "linear_attention") * linear
                  + _layers(model, "full_attention") * full
                  + len(model["layer_types"]) * 3 * H * W)


def prefill_mfu(run) -> Optional[float]:
    """The model's operations for the REAL tokens of the traced prefill
    steps — the matrix products a token passes, the head once a row, the
    recurrence and the causal attention at each row's real length — over
    those steps' host-clock length times the chip's bfloat16 peak, %: the
    share of a whole step, padding, the host's part of the step and every
    pass the program makes beyond the model's arithmetic all on the debit
    side."""
    tr = run.get("trace")
    if not tr or not tr.get("peaks"):
        return None
    model = run["config"]["model"]
    if "linear_num_key_heads" not in model:
        return None
    scan = cells.load_module("opcount", "gated_delta_rule")
    core = cells.load_module("opcount", "mha_attention")
    per_token = token_flops(model)
    head = 2.0 * model["vocab_size"] * model["hidden_size"]
    flops = secs = 0.0
    rows = 0
    for st, lengths in _mix_spans._lengths_by_step(run, "long"):
        for n in lengths:
            flops += n * per_token + head \
                + _layers(model, "linear_attention") \
                * scan.row_cost(n, model)["flops"] \
                + _layers(model, "full_attention") \
                * core.row_cost(n, model)["flops"]
        secs, rows = secs + (st.end - st.start), rows + len(lengths)
    if not rows or secs <= 0:
        return None
    print(f"prefill mfu: {rows} rows, {flops:.3e} model operations in "
          f"{secs:.4f} s of prefill steps", flush=True)
    return flops / (secs * tr["peaks"]["bf16_flops_per_s"]) * 100.0
