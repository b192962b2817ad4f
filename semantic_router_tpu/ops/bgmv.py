"""Pallas BGMV: per-item gathered matmul for wide head/LoRA banks.

The long-carried fused-bank follow-on (docs/FUSED_BANK.md → shipped
here, docs/KERNELS.md): the all-heads bank matmul computes EVERY task's
head for EVERY row and demuxes host-side — optimal at classifier task
counts (~18 heads: head FLOPs are ~0.1% of the trunk's), pure waste for
wide banks where each row needs one or two heads of dozens.  BGMV
(batched gather matrix-vector, the S-LoRA / Punica serving shape) flips
the layout: each (row, task) PAIR gathers its own task's weights and
computes only its own head — work scales with pairs, not rows × tasks.

Kernel: grid = (P,) over pairs; the pair's task index arrives via
scalar prefetch (``PrefetchScalarGridSpec``) so the weight BlockSpec's
index_map gathers task ``idx[p]``'s [D, H] slab straight from HBM into
VMEM — no padded [P, D, H] gather ever materializes.

Rows enter and leave as [P, 1, D] / [P, 1, H] so a one-pair block's
last two dims equal the array's (the TPU tiling rule refuses a (1, D)
block of a [P, D] array once P > 1).

``bgmv`` is the public entry: the Pallas kernel on TPU, XLA take+einsum
on CPU — same semantics, parity-gated ≤1e-4 against the padded
all-heads path in tests/test_kernels.py across LoRA'd / packed /
deduped batches.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bgmv_kernel(idx_ref, x_ref, w_ref, o_ref):
    """One pair's program: y[p] = x[p] @ W[idx[p]] (idx applied by the
    BlockSpec index_map — the kernel body sees its own slab only)."""
    del idx_ref
    x = x_ref[0].astype(jnp.float32)             # [1, D]
    w = w_ref[0].astype(jnp.float32)             # [D, H]
    o_ref[0] = jnp.dot(x, w,
                       preferred_element_type=jnp.float32
                       ).astype(o_ref.dtype)


def bgmv_pallas(x: jnp.ndarray, w: jnp.ndarray, idx: jnp.ndarray,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """x [P, D] × w [T, D, H] gathered by idx [P] → [P, H]."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    P, D = x.shape
    T, _, H = w.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P,),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda p, idx_ref: (p, 0, 0)),
            pl.BlockSpec((1, D, H),
                         lambda p, idx_ref: (idx_ref[p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, H), lambda p, idx_ref: (p, 0, 0)),
    )
    return pl.pallas_call(
        _bgmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, 1, H), x.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), x[:, None, :], w)[:, 0, :]


def bgmv_reference(x: jnp.ndarray, w: jnp.ndarray,
                   idx: jnp.ndarray) -> jnp.ndarray:
    """XLA fallback / numerics oracle: gather then batched matvec.
    Still a PER-PAIR gather — the CPU path pays O(pairs · D · H), never
    the padded all-heads O(rows · T · D · H)."""
    return jnp.einsum("pd,pdh->ph", x, jnp.take(w, idx, axis=0))


def bgmv(x: jnp.ndarray, w: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Dispatch: the Pallas gather kernel on a TPU platform; the XLA
    reference on CPU."""
    if jax.default_backend() == "tpu":
        return bgmv_pallas(x, w, idx)
    return bgmv_reference(x, w, idx)
