"""What the attention cores of a ``laguna`` prefill (``models/laguna.py``,
scopes ``attn_full/core`` and ``attn_window/core``: the flash kernel of
``ops/flash_attention.py``, causal and whole or causal under the window)
have to do for one row of one layer, from counts alone — the yardstick of
``mix_flash_roofline.full`` and ``mix_flash_roofline.window``.

It follows the MASK, not the kernel: a query of a full layer needs its ``t
+ 1`` causal keys, a query of a sliding layer its ``min(t + 1,
sliding_window)`` latest; each (query, key) pair of each query head takes
``2 * D`` operations for the score and ``2 * D`` for the weighted sum.
Bytes: Q read and O written once a QUERY head, K and V read once a K/V
head (grouped queries share them; the program repeats them to every query
head before the call, and that copy is its waste).  Padding positions, the
upper triangle of a diagonal block and the keys a block visits outside the
band are the kernel's waste, not its work, so a share computed from these
counts cannot pass 100%.  (``opcount/flash_attention.py`` counts an
encoder's symmetric band and is left as it is.)
"""

from __future__ import annotations

from typing import Any, Dict

# device ops of the cores by layer type: every op whose ``tf_op`` path goes
# through the scope
SCOPES = {"full_attention": "attn_full/core",
          "sliding_attention": "attn_window/core"}


def causal_pairs(n_tokens: int, window: int = 0) -> int:
    """``sum over t < n of (t + 1)``, or of ``min(t + 1, window)``."""
    if not window or n_tokens <= window:
        return n_tokens * (n_tokens + 1) // 2
    return window * (window + 1) // 2 + (n_tokens - window) * window


def row_cost(n_tokens: int, kind: str, model: Dict[str, Any],
             dtype_bytes: int = 2) -> Dict[str, float]:
    """ONE layer of ``kind``'s core over one row of ``n_tokens``."""
    heads = model["num_attention_heads_per_layer"][
        model["layer_types"].index(kind)]
    kv, d = model["num_key_value_heads"], model["head_dim"]
    window = model["sliding_window"] if kind == "sliding_attention" else 0
    return {"flops": 4.0 * causal_pairs(n_tokens, window) * d * heads,
            "bytes": 2.0 * n_tokens * (heads + kv) * d * dtype_bytes}
