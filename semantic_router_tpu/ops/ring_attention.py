"""Ring attention: sequence-parallel exact attention over a mesh axis.

The reference scales long sequences by throwing HBM at chunked/flash
kernels on one GPU (chunked_sdpa.rs, ort-ck-flash-attn); the TPU-native
answer to sequences that outgrow ONE chip is to shard the sequence over
the mesh's ``sp`` axis and rotate key/value blocks around the ring with
``lax.ppermute`` while queries stay put — each step computes one
[S_local x S_local] block of the score matrix and folds it into an
online-softmax accumulator (same math as ops.flash_attention /
chunked_sdpa, distributed instead of blocked).  On TPU the ppermute
rides the ICI torus and XLA overlaps the collective with the block
matmul — the canonical ring-attention schedule (Liu et al. 2023,
"Ring Attention with Blockwise Transformers"; the public big-vision /
scaling-book pattern) rebuilt on jax collectives.

Memory per device: O(B * H * S_local * (S_local + D)) — the full [S, S]
score matrix never exists anywhere.  Numerics: softmax statistics
accumulate in float32 regardless of input dtype; results match dense
SDPA to float tolerance (tests/test_ring_attention.py oracles).

Supports the same semantics as the other attention impls so ModernBERT
can select it per-config (``attention_impl="ring"``):

- key padding masks ([B, S] with 1 = real token), sharded and rotated
  with their K/V blocks;
- ModernBERT sliding-window locality (``window`` = full width; blocks
  whose position range cannot intersect the window still participate in
  the rotation — the schedule is static — but contribute -inf scores).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .attention import NEG_INF


def _ring_block(q, k, v, mask, *, axis_name: str, axis_size: int,
                window: int, scale: float):
    """Per-device body (runs inside shard_map).

    q/k/v: [B, H, S_local, D] — this device's sequence block.
    mask:  [B, S_local] key padding for the CURRENT k/v block (rotates).
    """
    B, H, Sl, D = q.shape
    my = lax.axis_index(axis_name)
    qf = q.astype(jnp.float32)
    q_pos = my * Sl + jnp.arange(Sl)
    half_window = window // 2
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def fold(t, kb, vb, mb, out, m, l):
        """Fold one k/v block into the online-softmax accumulators.
        After t forward shifts, the block we hold originated on shard
        (my - t) mod n — that fixes its absolute key positions."""
        src = (my - t) % axis_size
        scores = jnp.einsum("bhqd,bhkd->bhqk", qf,
                            kb.astype(jnp.float32)) * scale
        kbias = (1.0 - mb.astype(jnp.float32)) * NEG_INF
        scores = scores + kbias[:, None, None, :]
        if window > 0:
            k_pos = src * Sl + jnp.arange(Sl)
            dist = jnp.abs(q_pos[:, None] - k_pos[None, :])
            wb = jnp.where(dist <= half_window, 0.0, NEG_INF)
            scores = scores + wb[None, None, :, :]
        m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1, keepdims=True)
        out_new = out * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        return out_new, m_new, l_new

    def step(t, carry):
        kb, vb, mb, out, m, l = carry
        # rotate FIRST (iterations 1..n-1): the ring pays exactly n-1
        # ppermute rounds, not n — the last block is folded without a
        # trailing discarded rotation.  XLA overlaps the ppermute with
        # the previous fold's matmuls.
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        mb = lax.ppermute(mb, axis_name, perm)
        out, m, l = fold(t, kb, vb, mb, out, m, l)
        return kb, vb, mb, out, m, l

    # accumulators derived FROM q (not fresh constants): under the new
    # shard_map type system fresh zeros are axis-unvarying and the loop
    # carry would change type on the first iteration
    out0 = qf * 0.0
    m0 = qf[..., :1] * 0.0 - jnp.inf
    l0 = qf[..., :1] * 0.0
    out, m, l = fold(0, k, v, mask, out0, m0, l0)  # the local block
    _, _, _, out, _, l = lax.fori_loop(
        1, axis_size, step, (k, v, mask, out, m, l))
    # l is never 0: NEG_INF is FINITE (-1e9, ops/attention.py), so even a
    # fully-masked padding row accumulates exp(0)=1 per key and divides
    # cleanly — such rows emit the uniform average of v, exactly the
    # dense sdpa convention.  (If NEG_INF ever became -inf this would
    # need an l==0 guard to stay NaN-free.)
    return (out / l).astype(q.dtype)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh, key_padding_mask: Optional[jnp.ndarray] = None,
                   window: int = 0, scale: Optional[float] = None,
                   seq_axis: str = "sp", batch_axis: str = "dp",
                   head_axis: Optional[str] = "tp") -> jnp.ndarray:
    """Exact attention with the sequence sharded over ``mesh[seq_axis]``.

    q/k/v: [B, H, S, D] global views (S divisible by the seq-axis size,
    B by the batch-axis size).  Heads additionally shard over
    ``head_axis`` when it divides H (no collectives cross it).  Callable
    under jit; safe with n=1 meshes (degenerates to one local block).
    """
    from jax import shard_map

    if scale is None:
        scale = q.shape[-1] ** -0.5
    if key_padding_mask is None:
        key_padding_mask = jnp.ones(
            (q.shape[0], q.shape[2]), jnp.int32)
    n = mesh.shape[seq_axis]
    if q.shape[2] % n:
        raise ValueError(f"seq {q.shape[2]} not divisible by "
                         f"{seq_axis}={n}")
    h_axis = head_axis if (head_axis in mesh.shape
                           and q.shape[1] % mesh.shape[head_axis] == 0
                           and mesh.shape[head_axis] > 1) else None
    qspec = P(batch_axis, h_axis, seq_axis, None)
    mspec = P(batch_axis, seq_axis)
    fn = shard_map(
        partial(_ring_block, axis_name=seq_axis, axis_size=n,
                window=window, scale=scale),
        mesh=mesh, in_specs=(qspec, qspec, qspec, mspec),
        out_specs=qspec)
    return fn(q, k, v, key_padding_mask)
