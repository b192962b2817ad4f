"""What the gated delta rule of a linear-attention layer
(``models/olmo_hybrid.py``, scope ``linear_attn/scan``: the op of
``ops/gated_delta_rule.py`` and nothing else) has to do for one row of one
layer, from counts alone — the yardstick of ``lin_delta_rule_roofline``.

It follows the RECURRENCE, whatever implements it: a token of a head decays
the state (``d_k d_v``), reads it along the key (``S^T k``: ``2 d_k d_v``),
writes the rank-one update (``2 d_k d_v``) and reads it along the query
(``S^T q``: ``2 d_k d_v``): ``7 d_k d_v`` operations.  Bytes: q, k, v read
and o written once at the activations' width, ``g`` and ``beta`` read in
float32, the float32 state read once and written once a row (it stays on
the core between tokens).  A chunked form does more operations than this
(the products inside a chunk, the triangular inverse), what it keeps in HBM
between its stages is its own traffic, and padding positions are its waste,
not its work: a share computed from these counts cannot pass 100%.  At the
published widths (30 heads, 96 x 192) the yardstick is memory: 290 MB a
full 8192-token row-layer, 0.35 ms on a v5e, against 31.7 G operations,
0.16 ms.
"""

from __future__ import annotations

from typing import Any, Dict

# device ops of the scan: every op whose ``tf_op`` path goes through it
SCOPE = "linear_attn/scan"


def row_cost(n_tokens: int, model: Dict[str, Any], dtype_bytes: int = 2
             ) -> Dict[str, float]:
    """ONE linear layer's recurrence over one row of ``n_tokens``."""
    heads = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    return {"flops": 7.0 * dk * dv * heads * n_tokens,
            "bytes": 2.0 * (dk + dv) * heads * n_tokens * dtype_bytes
            + 2.0 * heads * n_tokens * 4 + 2.0 * heads * dk * dv * 4}
