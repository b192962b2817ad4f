"""Flax ModernBERT / mmBERT encoder family.

TPU-native re-implementation of the reference's workhorse classifier
encoder (candle-binding/src/model_architectures/traditional/modernbert.rs,
1,575 LoC — seq & token classification; mmBERT and mmBERT-32K YaRN variants
initialised via candle-binding/semantic-router.go:58-64). Architecture
contract (validated bit-for-bit against the public HF implementation in
tests/test_models_modernbert.py):

- token embeddings + LayerNorm (no learned positions; RoPE in attention)
- pre-LN layers; layer 0's attention norm is identity (embedding norm serves)
- fused Wqkv; alternating attention: every ``global_attn_every_n_layers``-th
  layer attends globally (theta=global_rope_theta), the rest use
  sliding-window local attention (width ``local_attention``,
  theta=local_rope_theta)
- GeGLU MLP: Wi → split(input, gate) → act(input) * gate → Wo
- final LayerNorm; classification heads: dense+act+norm then linear

mmBERT-32K: same module with ``rope_scaling={"rope_type": "yarn", ...}`` on
the global layers (SURVEY.md §5 long-context item 1).

Long-context memory: ``attention_impl="chunked"`` streams query blocks
(ops.chunked_sdpa — N8 parity); "dense" is the small-sequence fast path.
The head-side Matryoshka early-exit (``exit_layer``) taps intermediate
layers for 2D-Matryoshka embeddings (onnx-binding/README.md:38-62).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import (
    block_diagonal_bias,
    chunked_sdpa,
    cls_pool,
    mean_pool,
    packed_window_bias,
    padding_bias,
    sdpa,
    sliding_window_bias,
)
from ..ops.epilogue import gelu_exact
from ..ops.rope import RopeSpec, apply_rotary


@dataclasses.dataclass(frozen=True)
class ModernBertConfig:
    vocab_size: int = 50368
    hidden_size: int = 768
    intermediate_size: int = 1152
    num_hidden_layers: int = 22
    num_attention_heads: int = 12
    max_position_embeddings: int = 8192
    norm_eps: float = 1e-5
    norm_bias: bool = False
    pad_token_id: int = 50283
    global_rope_theta: float = 160000.0
    local_rope_theta: Optional[float] = 10000.0
    global_attn_every_n_layers: int = 3
    local_attention: int = 128  # full window width
    attention_bias: bool = False
    mlp_bias: bool = False
    hidden_activation: str = "gelu"
    classifier_pooling: str = "cls"  # cls | mean
    classifier_bias: bool = False
    classifier_activation: str = "gelu"
    num_labels: int = 2
    rope_scaling: Optional[Dict[str, Any]] = None  # {"rope_type": "yarn", ...}
    # dense | chunked | flash (pallas on TPU) | ring (sequence-parallel
    # exact attention over mesh[ring_seq_axis] — ops.ring_attention)
    attention_impl: str = "dense"
    chunk_block_size: int = 512
    # required for attention_impl="ring"; for "flash" the serving mesh
    # (engine.mesh) whose shards each run the kernel
    mesh: Any = None
    ring_seq_axis: str = "sp"
    ring_batch_axis: str = "dp"
    ring_head_axis: Optional[str] = "tp"
    dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_global_layer(self, layer_id: int) -> bool:
        return layer_id % self.global_attn_every_n_layers == 0

    @classmethod
    def from_hf(cls, hf_config) -> "ModernBertConfig":
        """Build from a transformers ModernBertConfig (duck-typed)."""
        g = lambda k, d=None: getattr(hf_config, k, d)
        return cls(
            vocab_size=g("vocab_size"),
            hidden_size=g("hidden_size"),
            intermediate_size=g("intermediate_size"),
            num_hidden_layers=g("num_hidden_layers"),
            num_attention_heads=g("num_attention_heads"),
            max_position_embeddings=g("max_position_embeddings"),
            norm_eps=g("norm_eps", 1e-5),
            norm_bias=g("norm_bias", False),
            pad_token_id=g("pad_token_id", 0),
            global_rope_theta=g("global_rope_theta", 160000.0),
            local_rope_theta=g("local_rope_theta", 10000.0),
            global_attn_every_n_layers=g("global_attn_every_n_layers", 3),
            local_attention=g("local_attention", 128),
            attention_bias=g("attention_bias", False),
            mlp_bias=g("mlp_bias", False),
            hidden_activation=g("hidden_activation", "gelu"),
            classifier_pooling=g("classifier_pooling", "cls"),
            classifier_bias=g("classifier_bias", False),
            classifier_activation=g("classifier_activation", "gelu"),
            num_labels=len(g("id2label") or {}) or 2,
            rope_scaling=g("rope_scaling", None),
        )


def _act(name: str):
    if name in ("gelu", "gelu_python"):
        return gelu_exact
    if name in ("gelu_new", "gelu_pytorch_tanh"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu
    if name in ("silu", "swish"):
        return jax.nn.silu
    raise ValueError(f"unknown activation {name!r}")


def activation(name: str):
    """The classifier-activation resolver, public: the fused head bank
    (models.lora.apply_head_bank) reruns the head math outside a Flax
    module and must apply the exact same nonlinearity."""
    return _act(name)


class ModernBertEmbeddings(nn.Module):
    config: ModernBertConfig

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="tok_embeddings",
                     dtype=cfg.dtype)(input_ids)
        return nn.LayerNorm(epsilon=cfg.norm_eps, use_bias=cfg.norm_bias,
                            name="norm", dtype=cfg.dtype)(x)


class ModernBertMLP(nn.Module):
    """GeGLU MLP. ``dense_factory`` (shared with attention) lets the LoRA
    path swap every projection for a task-adapted dense without duplicating
    the trunk (see models/lora.py)."""

    config: ModernBertConfig
    dense_factory: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 task_index: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.config
        dense = _make_dense(self, cfg, task_index)
        wi = dense(cfg.intermediate_size * 2, cfg.mlp_bias, "Wi")(x)
        inp, gate = jnp.split(wi, 2, axis=-1)
        h = _act(cfg.hidden_activation)(inp) * gate
        return dense(cfg.hidden_size, cfg.mlp_bias, "Wo")(h)


def _make_dense(module, cfg: ModernBertConfig,
                task_index: Optional[jnp.ndarray]):
    """Returns make(features, use_bias, name) → callable(x).

    Default: plain nn.Dense. With a ``dense_factory`` on the module (the
    LoRA path), the factory's module is called with the task index so the
    adapter pair is selected per call (a gather — no recompile on swap).
    The int8 quantized serving mode rides the same seam
    (models.quant.build_quant_trunk): its factory-made QuantDense layers
    accept and ignore the task index — quantized trunks carry no
    per-task adapters (docs/KERNELS.md)."""
    factory = getattr(module, "dense_factory", None)

    def make(features: int, use_bias: bool, name: str):
        if factory is None:
            layer = nn.Dense(features, use_bias=use_bias, name=name,
                             dtype=cfg.dtype)
            return layer
        layer = factory(features, use_bias, name)
        idx = task_index if task_index is not None else 0
        return lambda x: layer(x, jnp.asarray(idx))

    return make


class ModernBertAttention(nn.Module):
    config: ModernBertConfig
    layer_id: int
    dense_factory: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, attention_mask: jnp.ndarray,
                 task_index: Optional[jnp.ndarray] = None,
                 position_ids: Optional[jnp.ndarray] = None,
                 segment_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.config
        dense = _make_dense(self, cfg, task_index)
        B, S, _ = x.shape
        H, D = cfg.num_attention_heads, cfg.head_dim
        qkv = dense(3 * cfg.hidden_size, cfg.attention_bias, "Wqkv")(x)
        qkv = qkv.reshape(B, S, 3, H, D)
        q, k, v = [jnp.moveaxis(t.squeeze(2), 2, 1)
                   for t in jnp.split(qkv, 3, axis=2)]  # [B, H, S, D]

        is_global = cfg.is_global_layer(self.layer_id)
        if is_global:
            spec = RopeSpec(D, cfg.global_rope_theta, yarn=_yarn_dict(cfg))
            window = 0
        else:
            theta = (cfg.local_rope_theta if cfg.local_rope_theta is not None
                     else cfg.global_rope_theta)
            spec = RopeSpec(D, theta, yarn=None)
            window = cfg.local_attention
        cos, sin = spec.tables(S)
        if position_ids is not None:
            # sequence packing: RoPE by SEGMENT-LOCAL position, not row
            # index — gather the same float32 tables by position id so a
            # packed segment rotates bit-identically to itself unpacked
            cos = jnp.asarray(cos)[position_ids][:, None]  # [B, 1, S, D]
            sin = jnp.asarray(sin)[position_ids][:, None]
        q, k = apply_rotary(q, k, cos, sin)

        if segment_ids is not None:
            # packed rows: block-diagonal attention (each segment attends
            # only to itself) + window on segment-local positions — only
            # the dense path carries packing (the engine gates on it)
            if cfg.attention_impl != "dense":
                raise ValueError(
                    f"sequence packing requires attention_impl='dense' "
                    f"(got {cfg.attention_impl!r})")
            bias = block_diagonal_bias(segment_ids)
            if window > 0:
                bias = bias + packed_window_bias(position_ids, window)
            out = sdpa(q, k, v, bias=bias)
            out = jnp.moveaxis(out, 1, 2).reshape(B, S, cfg.hidden_size)
            return dense(cfg.hidden_size, cfg.attention_bias, "Wo")(out)

        if cfg.attention_impl == "flash":
            from ..ops.flash_attention import flash_attention

            # cfg.mesh is set when the engine serves this trunk under
            # a dp×tp mesh: the kernel then runs per shard
            out = flash_attention(q, k, v, key_padding_mask=attention_mask,
                                  window=window, mesh=cfg.mesh,
                                  batch_axis=cfg.ring_batch_axis,
                                  head_axis=cfg.ring_head_axis)
        elif cfg.attention_impl == "chunked":
            out = chunked_sdpa(q, k, v, key_padding_mask=attention_mask,
                               window=window,
                               block_size=cfg.chunk_block_size)
        elif cfg.attention_impl == "ring":
            # sequence-parallel exact attention: S shards over the
            # mesh's sp axis, K/V blocks rotate on the ICI ring — the
            # long-context path when one chip's HBM is not enough
            from ..ops.ring_attention import ring_attention

            if cfg.mesh is None:
                raise ValueError("attention_impl='ring' needs cfg.mesh")
            out = ring_attention(q, k, v, cfg.mesh,
                                 key_padding_mask=attention_mask,
                                 window=window,
                                 seq_axis=cfg.ring_seq_axis,
                                 batch_axis=cfg.ring_batch_axis,
                                 head_axis=cfg.ring_head_axis)
        else:
            bias = padding_bias(attention_mask)
            if window > 0:
                bias = bias + sliding_window_bias(S, window)
            out = sdpa(q, k, v, bias=bias)

        out = jnp.moveaxis(out, 1, 2).reshape(B, S, cfg.hidden_size)
        return dense(cfg.hidden_size, cfg.attention_bias, "Wo")(out)


def _yarn_dict(cfg: ModernBertConfig) -> Optional[dict]:
    rs = cfg.rope_scaling
    if rs and rs.get("rope_type", rs.get("type")) == "yarn":
        return dict(rs)
    return None


class ModernBertEncoderLayer(nn.Module):
    config: ModernBertConfig
    layer_id: int
    dense_factory: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, attention_mask: jnp.ndarray,
                 task_index: Optional[jnp.ndarray] = None,
                 position_ids: Optional[jnp.ndarray] = None,
                 segment_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.config
        if self.layer_id == 0:
            attn_in = x  # identity: embedding norm already applied
        else:
            attn_in = nn.LayerNorm(epsilon=cfg.norm_eps,
                                   use_bias=cfg.norm_bias, name="attn_norm",
                                   dtype=cfg.dtype)(x)
        x = x + ModernBertAttention(cfg, self.layer_id, name="attn",
                                    dense_factory=self.dense_factory)(
            attn_in, attention_mask, task_index, position_ids, segment_ids)
        mlp_in = nn.LayerNorm(epsilon=cfg.norm_eps, use_bias=cfg.norm_bias,
                              name="mlp_norm", dtype=cfg.dtype)(x)
        return x + ModernBertMLP(cfg, name="mlp",
                                 dense_factory=self.dense_factory)(
            mlp_in, task_index)


class ModernBertModel(nn.Module):
    """Encoder trunk → final-norm hidden states [B, S, hidden].

    ``dense_factory``/``task_index`` thread the LoRA adaptation through
    every projection (models/lora.py) without duplicating the trunk."""

    config: ModernBertConfig
    dense_factory: Any = None

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None,
                 exit_layer: Optional[int] = None,
                 task_index: Optional[jnp.ndarray] = None,
                 position_ids: Optional[jnp.ndarray] = None,
                 segment_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """``position_ids``/``segment_ids`` select the sequence-packed
        path (engine.packing): multiple prompts share each row under a
        block-diagonal attention mask with per-segment RoPE positions —
        numerically each segment computes exactly what it would alone in
        a padded row (docs/PACKING.md is the contract)."""
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        # named scopes are metadata on the HLO (every output is bit-
        # identical): a device profile's ops read embed_tokens/… or
        # trunk/layers_<i>/{attn,mlp}/… (the inner names are the flax
        # modules' own), which is how trunk time is told from head time
        with jax.named_scope("embed_tokens"):
            x = ModernBertEmbeddings(cfg, name="embeddings")(input_ids)
        n_layers = cfg.num_hidden_layers if exit_layer is None \
            else min(exit_layer, cfg.num_hidden_layers)
        with jax.named_scope("trunk"):
            for i in range(cfg.num_hidden_layers):
                if i >= n_layers:
                    break  # Matryoshka layer early-exit (static under jit)
                x = ModernBertEncoderLayer(
                    cfg, i, name=f"layers_{i}",
                    dense_factory=self.dense_factory)(
                    x, attention_mask, task_index, position_ids,
                    segment_ids)
            return nn.LayerNorm(epsilon=cfg.norm_eps,
                                use_bias=cfg.norm_bias, name="final_norm",
                                dtype=cfg.dtype)(x)


class ModernBertPredictionHead(nn.Module):
    config: ModernBertConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        x = nn.Dense(cfg.hidden_size, use_bias=cfg.classifier_bias,
                     name="dense", dtype=cfg.dtype)(x)
        x = _act(cfg.classifier_activation)(x)
        return nn.LayerNorm(epsilon=cfg.norm_eps, use_bias=cfg.norm_bias,
                            name="norm", dtype=cfg.dtype)(x)


class ModernBertForSequenceClassification(nn.Module):
    """Sequence classifier (intent/domain, jailbreak, fact-check, feedback,
    complexity … — the reference's seq-cls FFI surface,
    modernbert.rs `ModernBertForSequenceClassification`)."""

    config: ModernBertConfig

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        hidden = ModernBertModel(cfg, name="model")(input_ids, attention_mask)
        with jax.named_scope("pool"):
            if cfg.classifier_pooling == "mean":
                pooled = mean_pool(hidden, attention_mask)
            else:
                pooled = cls_pool(hidden)
        with jax.named_scope("heads"):
            pooled = ModernBertPredictionHead(cfg, name="head")(pooled)
            return nn.Dense(cfg.num_labels, use_bias=True,
                            name="classifier", dtype=cfg.dtype)(pooled)


class ModernBertForTokenClassification(nn.Module):
    """Token classifier (PII spans, hallucination token detection — the
    reference's token-cls surface, modernbert.rs token classification +
    HaluGate N9)."""

    config: ModernBertConfig

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        hidden = ModernBertModel(cfg, name="model")(input_ids, attention_mask)
        with jax.named_scope("token_heads"):
            hidden = ModernBertPredictionHead(cfg, name="head")(hidden)
            return nn.Dense(cfg.num_labels, use_bias=True,
                            name="classifier",
                            dtype=cfg.dtype)(hidden)  # [B, S, num_labels]
