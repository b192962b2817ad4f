"""mmBERT (ModernBERT) embedding model with 2D-Matryoshka serving.

Reference: mmbert_embedding.rs:1,516 (layer early-exit × dim truncation) and
the dense bottleneck (dense_layers.rs). The trunk is the shared
ModernBertModel; ``exit_layer`` is static per jit-compiled variant, so each
configured exit point is its own (smaller) XLA program — the TPU shape of
"skip the top layers".
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import cls_pool, mean_pool
from ..ops.matryoshka import truncate_normalize
from .modernbert import ModernBertConfig, ModernBertModel


class MmBertEmbeddingModel(nn.Module):
    """ModernBERT trunk → pool → (optional bottleneck) → L2 normalize.

    ``exit_layer``/``output_dim`` give the 2D-Matryoshka grid; both are
    static under jit (exit changes the program, dim is a cheap slice).
    """

    config: ModernBertConfig
    pooling: str = "mean"  # mean | cls
    bottleneck_dims: Tuple[int, ...] = ()

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None,
                 exit_layer: Optional[int] = None,
                 output_dim: Optional[int] = None) -> jnp.ndarray:
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        hidden = ModernBertModel(cfg, name="model")(
            input_ids, attention_mask, exit_layer=exit_layer)
        with jax.named_scope("pool"):
            pooled = (cls_pool(hidden) if self.pooling == "cls"
                      else mean_pool(hidden, attention_mask))
            for i, dim in enumerate(self.bottleneck_dims):
                pooled = nn.Dense(dim, use_bias=False, name=f"dense_{i}",
                                  dtype=cfg.dtype)(pooled)
        with jax.named_scope("matryoshka"):
            return truncate_normalize(pooled, output_dim).astype(cfg.dtype)
