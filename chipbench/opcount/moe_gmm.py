"""What the grouped matmuls of a sparse expert layer (``models/sdar_moe.py``
``moe``, scope ``moe/gmm``) have to do, from counts alone — the yardstick of
``moe_gmm_roofline``.

A routed (token, expert) pair takes three matrix-vector products of the
expert's ``[H, I]`` matrices (gate, up, down): ``3 * 2 * H * I`` operations.
Bytes: the matrices of every expert that got at least one pair are read
once (an expert nobody chose is not read), and a pair's activation is read
once (``H``) and its result written once (``H``); the intermediate of width
``I`` could stay on the chip and is not counted.  Padding tokens and the
pairs of experts held elsewhere are not work, so a share computed from
these counts cannot pass 100%.
"""

from __future__ import annotations

from typing import Any, Dict

# device ops of the grouped matmuls and of what stands between them: every
# op whose ``tf_op`` path goes through this scope
SCOPE = "moe/gmm"
# ... and XLA's own grouped-matmul kernels, which the compiler names
# ``ragged-dot-none`` / ``ragged-dot-metadata`` and leaves no path on
# (looked at in the compiled program's text, PR 28): (pattern, their path)
UNSCOPED = (r"^%ragged-dot", "moe/gmm/ragged_dot")


def forward_cost(pairs: float, experts_touched: float, model: Dict[str, Any],
                 dtype_bytes: int = 2) -> Dict[str, float]:
    """``pairs`` routed pairs and ``experts_touched`` experts with at least
    one, both summed over the expert layers of the forwards counted."""
    h, i = model["hidden_size"], model["moe_intermediate_size"]
    return {"flops": pairs * 3 * 2 * h * i,
            "bytes": (experts_touched * 3 * h * i + pairs * 2 * h)
            * dtype_bytes}
