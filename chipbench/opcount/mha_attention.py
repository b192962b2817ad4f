"""What the full layers' attention core of an ``olmo_hybrid`` prefill
(``models/olmo_hybrid.py``, scope ``attn/core``: the flash kernel of
``ops/flash_attention.py``, causal, as many k/v heads as query heads) has to
do for one row of one layer, from counts alone — the yardstick of
``lin_flash_roofline.full``.

It follows the MASK, not the kernel: a query needs its ``t + 1`` causal keys;
each (query, key) pair of each head takes ``2 D`` operations for the score
and ``2 D`` for the weighted sum.  Bytes: Q, K, V read and O written once a
head.  Padding positions and the upper triangle of a diagonal block are the
kernel's waste, not its work, so a share computed from these counts cannot
pass 100%.  (``opcount/gqa_attention.py`` reads ``laguna``'s per-layer head
list and is left as it is.)
"""

from __future__ import annotations

from typing import Any, Dict

SCOPE = "attn/core"


def row_cost(n_tokens: int, model: Dict[str, Any], dtype_bytes: int = 2
             ) -> Dict[str, float]:
    """ONE full layer's core over one row of ``n_tokens``."""
    heads = model["num_attention_heads"]
    d = model["hidden_size"] // heads
    pairs = n_tokens * (n_tokens + 1) // 2
    return {"flops": 4.0 * pairs * d * heads,
            "bytes": 4.0 * n_tokens * heads * d * dtype_bytes}
