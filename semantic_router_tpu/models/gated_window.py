"""What the decoders with a sigmoid gate a head and a sliding window's ring
share: ``models/dots3_note.py`` (latent attention: the ring holds latents)
and ``models/laguna.py`` (plain keys and values: the ring holds both).  One
gate, one ring arithmetic.

The gate (the Gated-Attention headwise form): ``g = sigmoid(W_g h)``, one
scalar a head from the layer's normed input, times that head's output in
float32, before ``W_o``.

The ring of a window of ``W`` keys (the token itself among them): position
``p`` lives in slot ``p mod W``.  A prefill writes it from the row's last
``min(length, W)`` real positions (``ring_of``); a decode step at position
``p`` overwrites slot ``p mod W`` (``ring_slot``) and then sees the slots
``<= p`` (``ring_seen``): from ``p = W - 1`` on that is every slot, and
every slot then holds a position in ``(p - W, p]`` — the one just
overwritten held ``p - W``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gate_out(p, h, out, dtype):
    """``h [B, S, H]``, ``out [B, heads, S, v]`` -> ``W_o [g_i * o_i] [B, S,
    H]`` with ``p["gate_proj"] [H, heads]`` and ``p["o_proj"] [heads * v,
    H]``."""
    heads, v = out.shape[1], out.shape[3]
    with jax.named_scope("gate_out"):
        gate = jax.nn.sigmoid(jnp.dot(h, p["gate_proj"],
                                      preferred_element_type=jnp.float32))
        out = (out.astype(jnp.float32)
               * jnp.moveaxis(gate, -1, 1)[..., None]).astype(dtype)
        return jnp.einsum("bhsv,hvo->bso", out,
                          p["o_proj"].reshape(heads, v, -1))


def ring_of(seq, lengths, W: int):
    """A prefill's ring ``[B, W, ...]`` of ``seq [B, S, ...]`` (what each
    position leaves in the cache) for rows of ``lengths [B]``: slot ``j``
    holds the latest position ``p <= length - 1`` with ``p mod W == j``,
    zeros where there is none (and in a padding row, length 0)."""
    S = seq.shape[1]
    last = jnp.maximum(lengths - 1, 0)
    slot = jnp.arange(W, dtype=jnp.int32)[None, :]
    src = last[:, None] - (last[:, None] - slot) % W
    held = (src >= 0) & (lengths > 0)[:, None]
    tail = (slice(None), slice(None)) + (None,) * (seq.ndim - 2)
    ring = jnp.take_along_axis(seq, jnp.clip(src, 0, S - 1)[tail], axis=1)
    return ring * held[tail].astype(ring.dtype)


def ring_slot(positions, W: int):
    """The slot a decode step at ``positions [B]`` overwrites."""
    return positions % W


def ring_seen(positions, W: int):
    """``[B, W]``: the slots a decode step at ``positions [B]`` sees, once
    its own is written."""
    return jnp.arange(W)[None, :] <= positions[:, None]
