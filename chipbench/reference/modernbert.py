"""Plain ``jax.numpy`` reference forward for the ModernBERT / mmBERT-32K
family, the benchmark's own copy (the program's is
``semantic_router_tpu/models/reference.py``).

It imports nothing of the program and takes nothing the program made: the
weights are the HF-layout state dicts (torch ``[out, in]`` matrices) that
``chipbench/checkpoints.py`` generates from the seed, the geometry is the
configuration file's numbers, the token ids come from the benchmark's own
text generator.  Dense attention under an explicit [S, S] mask, one head
at a time; no Flax module, no kernel, no cache, no batching.

``precision``:
- ``"highest"``: float32 everywhere, matmuls at ``highest`` — the reference.
- ``"bfloat16"``: parameters and activations in bfloat16 — the CONTROL: the
  nearest precision below the one the configurations state (float32
  parameters and activations, one-bf16-pass matmuls).  A comparison that
  lets this pass would let a later PR serve bfloat16 unseen.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-5
GLOBAL_ROPE_THETA = 160000.0
LOCAL_ROPE_THETA = 10000.0


def _layer_norm(x, weight, eps=NORM_EPS):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * weight


def _linear(x, weight, bias=None):
    """torch layout: weight [out, in]."""
    y = x @ weight.T
    return y if bias is None else y + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def rope_angles(head_dim: int, theta: float, seq_len: int,
                yarn: Optional[dict]):
    """[S, D/2] rotation angles and the YaRN attention factor (1.0 plain).
    YaRN as published (NTK-by-parts): frequencies whose wavelength fits
    the original context keep extrapolating, long ones interpolate by
    ``factor``, with a linear ramp between the beta_fast/beta_slow
    rotation counts; cos/sin are scaled by 0.1*ln(factor)+1."""
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


def _rotate(x, angles, scale, dtype):
    """x [H, S, D]: rotate-half convention."""
    cos = jnp.asarray(np.cos(angles) * scale, dtype)
    sin = jnp.asarray(np.sin(angles) * scale, dtype)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def forward(dims: Dict[str, Any], trunk: Dict[str, Any],
            heads: Dict[str, Dict[str, Any]], input_ids, attention_mask,
            precision: str = "highest") -> Dict[str, Any]:
    """One sequence [S] through trunk + heads.

    ``dims``: the configuration file's numbers (hidden_size,
    num_attention_heads, num_hidden_layers, local_attention,
    global_attn_every_n_layers, rope_scaling).  ``trunk``: HF state dict of
    the trunk (``model.*`` keys).  ``heads``: ``{task: {"kind":
    "sequence"|"token"|"embedding", "state": {head.* / classifier.*}}}``.
    Returns ``{task: logits [L] | token logits [S, L] | embedding [hidden]}``
    in float32.  Jit-able with ``dims`` and the head kinds closed over.
    """
    if precision not in ("highest", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    dtype = jnp.float32 if precision == "highest" else jnp.bfloat16
    cast = lambda a: jnp.asarray(a, dtype)
    ids = jnp.asarray(input_ids)
    mask = jnp.asarray(attention_mask).astype(bool)
    (S,) = ids.shape
    H = dims["num_attention_heads"]
    D = dims["hidden_size"] // H
    n_layers = dims["num_hidden_layers"]
    every = dims["global_attn_every_n_layers"]
    half_window = dims["local_attention"] // 2
    scaling = dims.get("rope_scaling") or {}
    yarn = scaling if scaling.get("rope_type",
                                  scaling.get("type")) == "yarn" else None
    pos = jnp.arange(S)
    in_window = jnp.abs(pos[:, None] - pos[None, :]) <= half_window
    allowed_global = jnp.broadcast_to(mask[None, :], (S, S))
    allowed_local = allowed_global & in_window
    neg = jnp.finfo(dtype).min

    def attend(q, k, v, allowed):
        """[H, S, D] x3 under one [S, S] mask, a head at a time."""
        def one_head(qkv):
            s = (qkv[0] @ qkv[1].T) / math.sqrt(D)
            s = jnp.where(allowed, s, neg)
            return jax.nn.softmax(s, axis=-1) @ qkv[2]

        return jax.lax.map(one_head, (q, k, v))

    mm = "highest" if precision == "highest" else "default"
    with jax.default_matmul_precision(mm):
        x = cast(trunk["model.embeddings.tok_embeddings.weight"])[ids]
        x = _layer_norm(x, cast(trunk["model.embeddings.norm.weight"]))
        for i in range(n_layers):
            p = f"model.layers.{i}."
            is_global = i % every == 0
            h = x if i == 0 else _layer_norm(
                x, cast(trunk[p + "attn_norm.weight"]))
            qkv = _linear(h, cast(trunk[p + "attn.Wqkv.weight"]))
            qkv = qkv.reshape(S, 3, H, D)
            q, k, v = (jnp.moveaxis(qkv[:, j], 1, 0) for j in range(3))
            theta = GLOBAL_ROPE_THETA if is_global else LOCAL_ROPE_THETA
            angles, scale = rope_angles(D, theta, S,
                                        yarn if is_global else None)
            q = _rotate(q, angles, scale, dtype)
            k = _rotate(k, angles, scale, dtype)
            out = attend(q, k, v,
                         allowed_global if is_global else allowed_local)
            out = jnp.moveaxis(out, 0, 1).reshape(S, H * D)
            x = x + _linear(out, cast(trunk[p + "attn.Wo.weight"]))
            h = _layer_norm(x, cast(trunk[p + "mlp_norm.weight"]))
            a, gate = jnp.split(
                _linear(h, cast(trunk[p + "mlp.Wi.weight"])), 2, axis=-1)
            x = x + _linear(_gelu(a) * gate,
                            cast(trunk[p + "mlp.Wo.weight"]))
        hidden = _layer_norm(x, cast(trunk["model.final_norm.weight"]))

        m = mask[:, None].astype(dtype)
        mean = (hidden * m).sum(0) / jnp.maximum(m.sum(0), 1.0)
        out: Dict[str, Any] = {}
        for task, head in heads.items():
            kind, st = head["kind"], head.get("state") or {}
            if kind == "embedding":
                e = mean.astype(jnp.float32)
                out[task] = e / jnp.linalg.norm(e)
                continue
            pooled = hidden if kind == "token" else hidden[0]
            y = _gelu(_linear(pooled, cast(st["head.dense.weight"])))
            y = _layer_norm(y, cast(st["head.norm.weight"]))
            out[task] = _linear(y, cast(st["classifier.weight"]),
                                cast(st["classifier.bias"])
                                ).astype(jnp.float32)
        return out
