"""What the flash attention kernel (``ops/flash_attention.py``) has to do
for one call, from shapes alone — the yardstick of its roofline share.

It follows the kernel's masking, not its tiling: the operations the
ALGORITHM needs for the real (unpadded) tokens.  A global layer scores
every query against every key: 2*L*L*D for QK^T and as much for PV, per
head.  A windowed layer needs only the band |i - j| <= window/2, whatever
blocks the kernel visits.  Bytes: Q, K, V read once and O written once.
Padding rows and padded positions are the kernel's waste, not its work,
so a share computed from these counts cannot pass 100%.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# how the kernel's executions are named on the trace's "XLA Ops" line: the
# op's HLO text, `%attn.<n> = f32[...] custom-call(...),
# custom_call_target="tpu_custom_call", ...` (looked at by hand, PR 23)
EVENT_PATTERN = r'^%attn[.\d]* = .*custom_call_target="tpu_custom_call"'


def band_pairs(n_tokens: int, window: int) -> int:
    """Query-key pairs with |i - j| <= window // 2 inside [0, n)."""
    h = window // 2
    if n_tokens <= h + 1:
        return n_tokens * n_tokens
    return n_tokens * (2 * h + 1) - h * (h + 1)


def call_cost(n_tokens: int, heads: int, head_dim: int, window: int,
              dtype_bytes: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of one layer's attention over one sequence;
    ``window`` 0 = global."""
    pairs = n_tokens * n_tokens if not window else \
        band_pairs(n_tokens, window)
    flops = 4.0 * pairs * head_dim * heads
    nbytes = 4.0 * n_tokens * heads * head_dim * dtype_bytes
    return flops, nbytes


def forward_cost(n_tokens: int, model: Dict[str, Any],
                 dtype_bytes: int = 4) -> Dict[str, float]:
    """All attention layers of one trunk forward over one sequence."""
    heads = model["num_attention_heads"]
    head_dim = model["hidden_size"] // heads
    n_global = sum(1 for i in range(model["num_hidden_layers"])
                   if i % model["global_attn_every_n_layers"] == 0)
    n_local = model["num_hidden_layers"] - n_global
    gf, gb = call_cost(n_tokens, heads, head_dim, 0, dtype_bytes)
    lf, lb = call_cost(n_tokens, heads, head_dim, model["local_attention"],
                       dtype_bytes)
    return {"flops": n_global * gf + n_local * lf,
            "bytes": n_global * gb + n_local * lb,
            "calls": n_global + n_local}


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, Any]
                  ) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else \
        (t_memory, "memory")
