"""Flax Qwen3 decoder family: embeddings, generative classification, guard.

TPU-native equivalent of the reference's Qwen3 stack (N5/N7):
- qwen3_embedding.rs:2,347 — Qwen3-Embedding models (last-token pooling,
  L2-normalised, Matryoshka dim truncation)
- qwen3_multi_lora_classifier.rs:1,226 — generative classification with
  runtime adapter selection (here the LoRA dense-factory seam + a label
  scoring head)
- qwen3_guard.rs:513 — safety generation (served through the same trunk
  with an LM head; host-side regex parse lives in the engine layer)

Architecture contract (validated against transformers' Qwen3 in
tests/test_models_qwen3.py): RMSNorm (pre-norm), GQA with per-head-dim
q/k RMSNorm, RoPE, SwiGLU MLP, causal masking, optional tied LM head.

TPU notes: weights stay bf16; attention uses the shared ops (dense or
chunked); GQA K/V heads broadcast via repeat — XLA fuses the broadcast into
the attention einsum. Tensor-parallel sharding comes from
parallel/sharding.py rules (q/k/v/gate/up column-parallel, o/down row-
parallel under 'tp').
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import NEG_INF, chunked_sdpa, sdpa
from ..ops.rope import RopeSpec, apply_rotary
from .checkpoints import torch_dtype_of


@dataclasses.dataclass(frozen=True)
class Qwen3Config:
    vocab_size: int = 151936
    hidden_size: int = 1024
    intermediate_size: int = 3072
    num_hidden_layers: int = 28
    num_attention_heads: int = 16
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    attention_bias: bool = False
    tie_word_embeddings: bool = True
    rope_scaling: Optional[dict] = None
    attention_impl: str = "dense"  # dense | chunked
    chunk_block_size: int = 512
    causal: bool = True  # False → bidirectional (some embedding variants)
    dtype: Any = jnp.float32

    @classmethod
    def from_hf(cls, hf) -> "Qwen3Config":
        g = lambda k, d=None: getattr(hf, k, d)
        return cls(
            vocab_size=g("vocab_size"),
            hidden_size=g("hidden_size"),
            intermediate_size=g("intermediate_size"),
            num_hidden_layers=g("num_hidden_layers"),
            num_attention_heads=g("num_attention_heads"),
            num_key_value_heads=g("num_key_value_heads"),
            head_dim=g("head_dim") or g("hidden_size") // g("num_attention_heads"),
            rms_norm_eps=g("rms_norm_eps", 1e-6),
            rope_theta=g("rope_theta", 1e6),
            max_position_embeddings=g("max_position_embeddings", 32768),
            attention_bias=g("attention_bias", False),
            tie_word_embeddings=g("tie_word_embeddings", True),
            rope_scaling=g("rope_scaling", None),
            dtype=torch_dtype_of(g("torch_dtype", "float32")),
        )


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + self.eps)
        return (out * scale).astype(self.dtype)


class Qwen3Attention(nn.Module):
    config: Qwen3Config
    layer_id: int

    @nn.compact
    def __call__(self, x: jnp.ndarray, attention_mask: jnp.ndarray
                 ) -> jnp.ndarray:
        cfg = self.config
        B, S, _ = x.shape
        H, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        q = nn.Dense(H * D, use_bias=cfg.attention_bias, name="q_proj",
                     dtype=cfg.dtype)(x).reshape(B, S, H, D)
        k = nn.Dense(KV * D, use_bias=cfg.attention_bias, name="k_proj",
                     dtype=cfg.dtype)(x).reshape(B, S, KV, D)
        v = nn.Dense(KV * D, use_bias=cfg.attention_bias, name="v_proj",
                     dtype=cfg.dtype)(x).reshape(B, S, KV, D)

        # per-head-dim RMSNorm on q/k (the Qwen3 signature detail)
        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)

        q = jnp.moveaxis(q, 2, 1)  # [B, H, S, D]
        k = jnp.moveaxis(k, 2, 1)
        v = jnp.moveaxis(v, 2, 1)

        yarn = None
        rs = cfg.rope_scaling
        if rs and rs.get("rope_type", rs.get("type")) == "yarn":
            yarn = dict(rs)
        spec = RopeSpec(D, cfg.rope_theta, yarn=yarn)
        cos, sin = spec.tables(S)
        q, k = apply_rotary(q, k, cos, sin)

        if KV != H:  # GQA broadcast
            rep = H // KV
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)

        bias = (1.0 - attention_mask.astype(jnp.float32))[:, None, None, :] \
            * NEG_INF
        if cfg.causal:
            causal = jnp.triu(jnp.full((S, S), NEG_INF, jnp.float32), k=1)
            bias = bias + causal[None, None, :, :]
        if cfg.attention_impl == "chunked" and not cfg.causal:
            out = chunked_sdpa(q, k, v, key_padding_mask=attention_mask,
                               block_size=cfg.chunk_block_size)
        else:
            out = sdpa(q, k, v, bias=bias)
        out = jnp.moveaxis(out, 1, 2).reshape(B, S, H * D)
        return nn.Dense(cfg.hidden_size, use_bias=cfg.attention_bias,
                        name="o_proj", dtype=cfg.dtype)(out)


class Qwen3MLP(nn.Module):
    config: Qwen3Config

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        gate = nn.Dense(cfg.intermediate_size, use_bias=False,
                        name="gate_proj", dtype=cfg.dtype)(x)
        up = nn.Dense(cfg.intermediate_size, use_bias=False, name="up_proj",
                      dtype=cfg.dtype)(x)
        return nn.Dense(cfg.hidden_size, use_bias=False, name="down_proj",
                        dtype=cfg.dtype)(jax.nn.silu(gate) * up)


class Qwen3DecoderLayer(nn.Module):
    config: Qwen3Config
    layer_id: int

    @nn.compact
    def __call__(self, x: jnp.ndarray, attention_mask: jnp.ndarray
                 ) -> jnp.ndarray:
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x)
        x = x + Qwen3Attention(cfg, self.layer_id, name="self_attn")(
            h, attention_mask)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="post_attention_layernorm")(x)
        return x + Qwen3MLP(cfg, name="mlp")(h)


class Qwen3Model(nn.Module):
    """Decoder trunk → final-norm hidden states."""

    config: Qwen3Config

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                     dtype=cfg.dtype)(input_ids)
        for i in range(cfg.num_hidden_layers):
            x = Qwen3DecoderLayer(cfg, i, name=f"layers_{i}")(
                x, attention_mask)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


def last_token_pool(hidden: jnp.ndarray,
                    attention_mask: jnp.ndarray) -> jnp.ndarray:
    """Pool at the last real (unpadded) token — the Qwen3-Embedding recipe
    (qwen3_embedding.rs pooling)."""
    idx = jnp.maximum(attention_mask.sum(axis=1) - 1, 0)  # [B]
    return jnp.take_along_axis(
        hidden, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]


class Qwen3EmbeddingModel(nn.Module):
    """Qwen3 embedding: trunk → last-token pool → L2 normalize. Matryoshka
    dim truncation happens post-hoc (ops.matryoshka) so one forward serves
    every output dim."""

    config: Qwen3Config

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        hidden = Qwen3Model(self.config, name="model")(
            input_ids, attention_mask)
        pooled = last_token_pool(hidden, attention_mask)
        norm = jnp.linalg.norm(pooled.astype(jnp.float32), axis=-1,
                               keepdims=True)
        return (pooled.astype(jnp.float32) / jnp.maximum(norm, 1e-9)
                ).astype(self.config.dtype)


class Qwen3ForCausalLM(nn.Module):
    """Trunk + LM head — the generative-classifier/guard serving shape
    (qwen3_guard.rs; greedy short-generation + host-side parse)."""

    config: Qwen3Config

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.config
        hidden = Qwen3Model(cfg, name="model")(input_ids, attention_mask)
        if cfg.tie_word_embeddings:
            embed = self.variables["params"]["model"]["embed_tokens"]["embedding"]
            return hidden @ embed.T.astype(cfg.dtype)
        return nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head",
                        dtype=cfg.dtype)(hidden)


def qwen3_params_from_state_dict(state, wrap: str | None = None):
    """Torch Qwen3 state dict → Flax params (name remap + kernel transpose).

    ``wrap``: "model" when loading into Qwen3EmbeddingModel/Qwen3ForCausalLM
    (whose trunk lives under name="model"); None for a bare Qwen3Model."""
    import numpy as np

    tree: dict = {}

    def put(path, arr, transpose=False):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = arr.T if transpose else arr

    trunk = [wrap] if wrap else []
    for key, w in state.items():
        w = np.asarray(w)
        parts = key.split(".")
        if parts[0] == "model":
            parts = parts[1:]
        if parts[0] == "embed_tokens":
            put(trunk + ["embed_tokens", "embedding"], w)
        elif parts[0] == "norm":
            put(trunk + ["norm", "weight"], w)
        elif parts[0] == "lm_head":
            put(["lm_head", "kernel"], w, transpose=True)
        elif parts[0] == "layers":
            i = parts[1]
            rest = parts[2:]
            base = trunk + [f"layers_{i}"]
            if rest[-1] == "weight" and rest[-2] in (
                    "q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                    "up_proj", "down_proj"):
                parent = "self_attn" if rest[0] == "self_attn" else "mlp"
                put(base + [parent, rest[-2], "kernel"], w, transpose=True)
            elif rest[-1] == "bias":
                parent = "self_attn" if rest[0] == "self_attn" else "mlp"
                put(base + [parent, rest[-2], "bias"], w)
            elif rest[-2] in ("q_norm", "k_norm"):
                put(base + ["self_attn", rest[-2], "weight"], w)
            elif rest[0] in ("input_layernorm", "post_attention_layernorm"):
                put(base + [rest[0], "weight"], w)
    return {"params": tree}
