"""Plain reference of a dense Qwen3 causal LM: jnp at ``highest``, HF state
dict in, logits of every position out.  It imports nothing of the program.

RMSNorm before attention and MLP, q/k RMSNorm per head, rotate-half RoPE at
positions 0..T-1, grouped-query attention, SwiGLU, untied or tied head."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, weight, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight


def _rope(x, theta):
    """x [T, heads, D]; frequencies duplicated over both halves."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    ang = np.outer(np.arange(T, dtype=np.float64), inv)
    ang = np.concatenate([ang, ang], -1)[:, None, :]
    cos, sin = jnp.asarray(np.cos(ang), jnp.float32), \
        jnp.asarray(np.sin(ang), jnp.float32)
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def forward(dims: Dict[str, Any], state: Dict[str, Any], ids,
            precision: str = "highest"):
    """Logits ``[T, vocab]`` of the causal LM over the token ids ``[T]``."""
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    prec = None if precision == "bfloat16" else precision
    H, KV = dims["num_attention_heads"], dims["num_key_value_heads"]
    D, eps = dims["head_dim"], dims["rms_norm_eps"]
    T = ids.shape[0]

    def w(name):
        return jnp.asarray(state[name]).astype(dt)

    def lin(x, name):  # torch layout [out, in]
        return jnp.matmul(x.astype(dt), w(name).T, precision=prec
                          ).astype(jnp.float32)

    x = w("model.embed_tokens.weight")[ids].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(dims["num_hidden_layers"]):
        p = f"model.layers.{i}."
        h = _rms(x, w(p + "input_layernorm.weight"), eps)
        q = lin(h, p + "self_attn.q_proj.weight").reshape(T, H, D)
        k = lin(h, p + "self_attn.k_proj.weight").reshape(T, KV, D)
        v = lin(h, p + "self_attn.v_proj.weight").reshape(T, KV, D)
        q = _rope(_rms(q, w(p + "self_attn.q_norm.weight"), eps),
                  dims["rope_theta"])
        k = _rope(_rms(k, w(p + "self_attn.k_norm.weight"), eps),
                  dims["rope_theta"])
        k, v = jnp.repeat(k, H // KV, 1), jnp.repeat(v, H // KV, 1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=prec) / np.sqrt(D)
        a = jax.nn.softmax(jnp.where(causal[None], s, -1e30), -1)
        o = jnp.einsum("hts,shd->thd", a, v, precision=prec).reshape(T, H * D)
        x = x + lin(o, p + "self_attn.o_proj.weight")
        h = _rms(x, w(p + "post_attention_layernorm.weight"), eps)
        x = x + lin(jax.nn.silu(lin(h, p + "mlp.gate_proj.weight"))
                    * lin(h, p + "mlp.up_proj.weight"),
                    p + "mlp.down_proj.weight")
    x = _rms(x, w("model.norm.weight"), eps)
    head = "model.embed_tokens.weight" if dims["tie_word_embeddings"] \
        else "lm_head.weight"
    return lin(x, head)
