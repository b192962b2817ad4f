"""Plain ``jax.numpy`` float32 reference forward for the ModernBERT /
mmBERT-32K classifier family.

The parity oracle for the served programs (ROADMAP R0): the same
parameters through the layer equations written out directly — dense
attention with an explicit [S, S] mask, no Flax module, no kernel, none of
``ops/`` — at ``highest`` matmul precision.  It shares nothing with the
code under test except the parameter tree and the config's numbers, so a
fault in the model code, the attention kernels or the RoPE tables shows as
a difference.  Memory is O(S^2) per head by design (heads run one at a
time); it is for checks, never for serving.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps) * p["scale"]
    return y + p["bias"] if "bias" in p else y


def _dense(x, p):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def _rope_angles(head_dim: int, theta: float, seq_len: int,
                 yarn: Optional[dict]):
    """[S, D/2] rotation angles and the YaRN attention factor (1.0 plain).
    YaRN as published (NTK-by-parts): frequencies whose wavelength fits
    the original context keep extrapolating, long ones interpolate by
    ``factor``, with a linear ramp between the beta_fast/beta_slow
    rotation counts; cos/sin are scaled by 0.1·ln(factor)+1."""
    exponents = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    inv_freq = theta ** -exponents
    scale = 1.0
    if yarn:
        factor = float(yarn["factor"])
        orig = float(yarn.get("original_max_position_embeddings", 8192))

        def dim_at(rotations: float) -> float:
            return head_dim * math.log(orig / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dim_at(float(yarn.get("beta_fast", 32.0)))), 0)
        high = min(math.ceil(dim_at(float(yarn.get("beta_slow", 1.0)))),
                   head_dim - 1)
        ramp = np.clip((np.arange(head_dim // 2) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1.0 else 1.0
    return np.outer(np.arange(seq_len, dtype=np.float64), inv_freq), scale


def _rotate(x, angles, scale):
    """x [H, S, D]: rotate-half convention, float32 tables."""
    cos = jnp.asarray(np.cos(angles) * scale, jnp.float32)
    sin = jnp.asarray(np.sin(angles) * scale, jnp.float32)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def reference_hidden(cfg, trunk: Dict[str, Any], input_ids,
                     attention_mask):
    """Trunk forward → final-norm hidden states [B, S, hidden].  ``cfg``:
    a ModernBertConfig (numbers only are read); ``trunk``: the "model"
    subtree of a served task's params.  Jit-able with ``cfg`` closed
    over."""
    ids = jnp.asarray(input_ids)
    mask = jnp.asarray(attention_mask).astype(bool)
    B, S = ids.shape
    H, D = cfg.num_attention_heads, cfg.hidden_size // cfg.num_attention_heads
    yarn = cfg.rope_scaling if (cfg.rope_scaling or {}).get(
        "rope_type", (cfg.rope_scaling or {}).get("type")) == "yarn" else None
    pos = jnp.arange(S)
    in_window = jnp.abs(pos[:, None] - pos[None, :]) \
        <= cfg.local_attention // 2

    def attend(q, k, v, allowed):
        """[H, S, D] ×3 under one [S, S] mask, a head at a time."""
        def one_head(qkv):
            s = (qkv[0] @ qkv[1].T) / math.sqrt(D)
            s = jnp.where(allowed, s, jnp.finfo(jnp.float32).min)
            return jax.nn.softmax(s, axis=-1) @ qkv[2]

        return jax.lax.map(one_head, (q, k, v))

    with jax.default_matmul_precision("highest"):
        emb = trunk["embeddings"]
        x = jnp.asarray(emb["tok_embeddings"]["embedding"],
                        jnp.float32)[ids]
        x = _layer_norm(x, emb["norm"], cfg.norm_eps)
        for i in range(cfg.num_hidden_layers):
            lp = trunk[f"layers_{i}"]
            is_global = i % cfg.global_attn_every_n_layers == 0
            h = x if i == 0 else _layer_norm(x, lp["attn_norm"],
                                             cfg.norm_eps)
            qkv = _dense(h, lp["attn"]["Wqkv"]).reshape(B, S, 3, H, D)
            theta = cfg.global_rope_theta if is_global or \
                cfg.local_rope_theta is None else cfg.local_rope_theta
            angles, scale = _rope_angles(D, theta, S,
                                         yarn if is_global else None)
            rows = []
            for b in range(B):
                q, k, v = (jnp.moveaxis(qkv[b, :, j], 1, 0)
                           for j in range(3))       # [H, S, D]
                q, k = _rotate(q, angles, scale), _rotate(k, angles, scale)
                allowed = jnp.broadcast_to(mask[b][None, :], (S, S))
                if not is_global:
                    allowed = allowed & in_window
                out = attend(q, k, v, allowed)
                rows.append(jnp.moveaxis(out, 0, 1).reshape(S, H * D))
            x = x + _dense(jnp.stack(rows), lp["attn"]["Wo"])
            h = _layer_norm(x, lp["mlp_norm"], cfg.norm_eps)
            a, gate = jnp.split(_dense(h, lp["mlp"]["Wi"]), 2, axis=-1)
            x = x + _dense(_gelu(a) * gate, lp["mlp"]["Wo"])
        return _layer_norm(x, trunk["final_norm"], cfg.norm_eps)


def reference_head(cfg, params: Dict[str, Any], hidden, attention_mask,
                   kind: str):
    """What a task makes of the trunk's hidden states.  ``kind``:
    "sequence" → logits [B, L]; "token" → logits [B, S, L]; "embedding"
    → mean-pooled, L2-normalised [B, hidden]."""
    p = params.get("params", params)
    m = jnp.asarray(attention_mask)[..., None].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        mean = (hidden * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
        if kind == "embedding":
            return mean / jnp.linalg.norm(mean, axis=-1, keepdims=True)
        x = hidden
        if kind == "sequence":
            x = mean if cfg.classifier_pooling == "mean" else hidden[:, 0]
        h = _gelu(_dense(x, p["head"]["dense"]))
        h = _layer_norm(h, p["head"]["norm"], cfg.norm_eps)
        return _dense(h, p["classifier"])
