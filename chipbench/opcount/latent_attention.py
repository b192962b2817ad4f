"""What the attention cores of a ``dots3_note`` prefill (``models/dots3_note.py``,
scopes ``attn_full/core`` and ``attn_window/core``: the flash kernel of
``ops/flash_attention.py`` under a per-query selection, or under the causal
window) have to do for one row, from counts alone — the yardstick of
``sa_latent_attention_roofline.prefill``.

It follows the SELECTION, not the kernel: a query of a full layer needs its
``min(t + 1, index_topk)`` selected keys and no others, a query of a sliding
layer its ``min(t + 1, sliding_window_size)`` latest; each (query, key) pair
of each head takes ``2 * d_qk`` operations for the score and ``2 * d_v`` for
the weighted sum.  Bytes: Q, K, V read once and O written once, a head.
Padding positions, future keys and the unselected keys a dense kernel visits
under its mask are the kernel's waste, not its work, so a share computed
from these counts cannot pass 100% — and reads LOW while the kernel is dense
under a mask: that distance is what a kernel that skips unselected blocks
would win.
"""

from __future__ import annotations

from typing import Any, Dict

# device ops of the cores: every op whose ``tf_op`` path goes through one
SCOPES = ("attn_full/core", "attn_window/core")


def pairs(n_tokens: float, keep: int) -> float:
    """``sum over t < n of min(t + 1, keep)``."""
    if n_tokens <= keep:
        return n_tokens * (n_tokens + 1) / 2
    return keep * (keep + 1) / 2 + (n_tokens - keep) * keep


def row_cost(n_tokens: float, model: Dict[str, Any], dtype_bytes: int = 2
             ) -> Dict[str, float]:
    """All attention cores of one prefill over one row of ``n_tokens``."""
    flops = nbytes = 0.0
    for kind in model["layer_types"]:
        p = "swa_" if kind == "sliding_attention" else ""
        heads = model[p + "num_attention_heads"]
        d_qk = model[p + "qk_nope_head_dim"] + model[p + "qk_rope_head_dim"]
        d_v = model[p + "v_head_dim"]
        keep = model["sliding_window_size"] if p else model["index_topk"]
        flops += pairs(n_tokens, keep) * heads * 2 * (d_qk + d_v)
        nbytes += n_tokens * heads * 2 * (d_qk + d_v) * dtype_bytes
    return {"flops": flops, "bytes": nbytes}
