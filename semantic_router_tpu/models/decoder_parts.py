"""What the generative decoders' layers share that is neither the expert
half (``models/experts.py``) nor one family's attention
(``models/latent_attention.py``, ``models/gated_window.py``): the RMSNorm,
the mask's fill value, the grouped-query projections of the decoders whose
whole head rotates, and the untied head.

Precision: the norm and RoPE in float32, the head's logits float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.rope import RopeSpec, apply_rotary

NEG_INF = -1e30


def rms_norm(x, w, eps: float, dtype):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(dtype)


def qkv(cfg, p, x, positions, table_len: int):
    """``x [B, S, H]`` -> q ``[B, S, heads, D]``, k and v ``[B, S, kv, D]``,
    q and k normalised per head and rotated at ``positions [B, S]``, all
    below ``table_len``.  ``cfg``: ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``rms_norm_eps``, ``rope_theta``,
    ``dtype``."""
    B, S, _ = x.shape
    nh, nkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = (x @ p["q_proj"]).reshape(B, S, nh, D)
    k = (x @ p["k_proj"]).reshape(B, S, nkv, D)
    v = (x @ p["v_proj"]).reshape(B, S, nkv, D)
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps, cfg.dtype)
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps, cfg.dtype)
    cos_t, sin_t = RopeSpec(D, cfg.rope_theta).tables(table_len)
    cos = jnp.take(cos_t, positions, axis=0)[:, :, None, :]
    sin = jnp.take(sin_t, positions, axis=0)[:, :, None, :]
    q, k = apply_rotary(q, k, cos, sin)  # float32 inside
    return q, k, v


def head(cfg, params, x):
    """``x [B, H]`` -> logits ``[B, V held]`` float32: the final norm and
    the untied head."""
    with jax.named_scope("lm_head"):
        h = rms_norm(x, params["norm"], cfg.rms_norm_eps, cfg.dtype)
        return jnp.einsum("bh,vh->bv", h, params["lm_head"],
                          preferred_element_type=jnp.float32)
