"""The ``sdar_moe`` decoder: a Qwen3-shaped pre-norm block whose MLP is a
bank of sparse SwiGLU experts, decoded a BLOCK of tokens at a time
(block diffusion: block-causal attention against a causal cache, the block
itself bidirectional).

Layer equations (``chipbench/reference/sdar_moe.py`` is the plain form):
``x <- x + Attn(RMSNorm(x))``, ``x <- x + MoE(RMSNorm(x))``; GQA with
per-head RMSNorm on q and k, RoPE at absolute positions, no bias;
``MoE(x) = sum over the top-k experts of w_e * down_e(silu(gate_e x) *
up_e x)`` with ``w`` the router's softmax over ALL experts, renormalised
over the k chosen.

Functions over a plain parameter tree, not flax modules.  The expert layer
(sort, grouped matmuls, combine, ``load``) is ``models/experts.py``'s
``routed_experts``, the ONE of every sparse decoder, which is told which
experts this chip holds (``experts_held = (first, count)``); only the
router in front of it differs (its ``softmax_route`` here).  Scopes on the
device timeline:
``embed_tokens``, ``layers_<i>/attn``, ``layers_<i>/moe`` (``moe/router``,
``moe/sort``, ``moe/gmm``, ``moe/combine``), ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import flash_attention
from .checkpoints import (
    checkpoint_reader,
    on_device,
    swiglu_matrices,
    torch_dtype_of,
)
from .decoder_parts import NEG_INF, qkv, rms_norm
from .experts import routed_experts, softmax_route


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16
    # (first, count) of the experts this chip holds; None = all of them
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @classmethod
    def from_hf(cls, hf: Mapping[str, Any], **overrides) -> "SdarMoeConfig":
        """From a checkpoint's ``config.json`` (``model_type: sdar_moe``).
        What the architecture cannot express is refused, not ignored."""
        if hf.get("attention_bias", False):
            raise ValueError("sdar_moe: attention_bias is not supported")
        if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
            raise ValueError("sdar_moe: every layer must be sparse "
                             "(mlp_only_layers [], decoder_sparse_step 1)")
        if hf.get("rope_scaling"):
            raise ValueError("sdar_moe: rope_scaling is not supported")
        if hf.get("use_sliding_window", False):
            raise ValueError("sdar_moe: sliding window is not supported")
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in fields}
        kw.setdefault("head_dim", hf["hidden_size"]
                      // hf["num_attention_heads"])
        kw["dtype"] = torch_dtype_of(hf.get("torch_dtype", "bfloat16"))
        kw["rope_theta"] = float(kw.get("rope_theta", 1e6))
        kw.update(overrides)
        return cls(**kw)


# -- parameters ------------------------------------------------------------------


def params_from_checkpoint(path: str, cfg: SdarMoeConfig) -> Dict[str, Any]:
    """A checkpoint directory as this module's tree."""
    with checkpoint_reader(path) as get:
        return params_from_state(get, cfg)


def params_from_state(get: Callable[[str], np.ndarray], cfg: SdarMoeConfig
                      ) -> Dict[str, Any]:
    """The published tensor names (one ``[I, H]`` matrix per expert and
    projection; ``get(name)`` loads one) as this module's tree, in
    ``cfg.dtype`` on the default device.  Only the experts held are read.
    A layer's expert matrices are stacked on the host and transposed on
    the device."""
    dev = functools.partial(on_device, cfg)
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        layers.append({
            "norm1": dev(get(p + "input_layernorm.weight")),
            "norm2": dev(get(p + "post_attention_layernorm.weight")),
            "q_proj": dev(get(p + "self_attn.q_proj.weight"), True),
            "k_proj": dev(get(p + "self_attn.k_proj.weight"), True),
            "v_proj": dev(get(p + "self_attn.v_proj.weight"), True),
            "o_proj": dev(get(p + "self_attn.o_proj.weight"), True),
            "q_norm": dev(get(p + "self_attn.q_norm.weight")),
            "k_norm": dev(get(p + "self_attn.k_norm.weight")),
            "router": dev(get(p + "mlp.gate.weight"), True),
            **swiglu_matrices(get, cfg, p + "mlp.experts.",
                              experts=cfg.held)})
    embed = dev(get("model.embed_tokens.weight"))
    params = {"embed": embed, "layers": layers,
              "norm": dev(get("model.norm.weight")),
              "lm_head": embed.T if cfg.tie_word_embeddings
              else dev(get("lm_head.weight"), True)}
    return params


# -- layers ----------------------------------------------------------------------


def moe(cfg: SdarMoeConfig, p, x, valid):
    """``x [T, H]`` through the softmax router and ``routed_experts``.
    Returns ``(y [T, H], top_e [T, k], load [4])``."""
    with jax.named_scope("router"):
        top_e, top_p = softmax_route(x, p["router"], cfg.num_experts_per_tok,
                                     cfg.norm_topk_prob)
    y, load = routed_experts(p, x, valid, top_e, top_p, cfg.held, cfg.dtype)
    return y, top_e, load


def _moe_block(cfg, p, x, valid):
    """The second half of a layer on ``x [B, S, H]``."""
    B, S, H = x.shape
    with jax.named_scope("moe"):
        h = rms_norm(x, p["norm2"], cfg.rms_norm_eps, cfg.dtype)
        y, top_e, load = moe(cfg, p, h.reshape(B * S, H), valid.reshape(-1))
    return x + y.reshape(B, S, H), top_e.reshape(B, S, -1), load


# -- prefill: whole prompt blocks under the block-causal mask --------------------


def prefill(cfg: SdarMoeConfig, params, ids, committed, cache_len: int,
            block_length: int):
    """``ids [B, S]`` right-padded prompts, ``committed [B]`` the number of
    leading tokens in whole blocks (``len // L * L``): their K and V go to
    the cache's columns ``[0, committed)``.  Whatever lies beyond is
    computed and never seen (a key is visible iff it is committed and
    ``key // L <= query // L``); the first generated block overwrites it.
    Returns ``(caches [(k, v) [B, kv, M, D]] per layer, load [layers, 4])``;
    no head: the first block's forward scores the first tokens."""
    B, S = ids.shape
    nkv, D = cfg.num_key_value_heads, cfg.head_dim
    rep = cfg.num_attention_heads // nkv
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < committed[:, None]
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], ids, axis=0)
    caches, loads = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope(f"layers_{i}"):
            with jax.named_scope("attn"):
                h = rms_norm(x, p["norm1"], cfg.rms_norm_eps, cfg.dtype)
                q, k, v = qkv(cfg, p, h, positions, S)
                kc, vc = (jnp.moveaxis(t, 2, 1) for t in (k, v))
                out = flash_attention(
                    jnp.moveaxis(q, 2, 1), jnp.repeat(kc, rep, axis=1),
                    jnp.repeat(vc, rep, axis=1),
                    key_padding_mask=valid.astype(jnp.int32), causal=True,
                    causal_block=block_length)
                out = jnp.moveaxis(out, 1, 2).reshape(B, S, -1)
                x = x + out.astype(cfg.dtype) @ p["o_proj"]
                pad = ((0, 0), (0, 0), (0, cache_len - S), (0, 0))
                caches.append((jnp.pad(kc, pad), jnp.pad(vc, pad)))
            x, _, load = _moe_block(cfg, p, x, valid)
            loads.append(load)
    return caches, jnp.stack(loads)


# -- one block against the committed cache ---------------------------------------


def block_forward(cfg: SdarMoeConfig, params, caches, tokens, start,
                  rows_valid, previous=None):
    """``tokens [B, L]`` at absolute positions ``start[b] .. start[b] + L``,
    seeing the committed cache and one another (bidirectional inside the
    block), scored by the head.  Without ``previous`` the cache holds the
    columns ``[0, start[b])`` and is left alone (a denoise forward).

    ``previous [B, L]`` is the final tokens of the block BEFORE, at
    ``start[b] - L .. start[b]``, whose K and V the cache does not hold
    yet: the forward then carries both blocks, ``2L`` positions a row,
    under the block-causal rule.  The previous block's rows see the cache's
    columns ``[0, start[b] - L)`` and one another, not the new block; the
    new block's rows see the cache, the previous block's K and V of this
    same forward (in the model's dtype: what the cache will hold) and one
    another.  The cache comes back with the previous block's K and V at its
    columns (commit).  The final norm and the head run on the new block's
    ``L`` positions only.

    Returns ``(logits [B, L, V] float32, caches | None, top_e [layers, B,
    S, k], load [layers, 4])`` with ``S = L``, or ``2L`` with the previous
    block's tokens first."""
    B, L = tokens.shape
    first = start
    if previous is not None:
        tokens, first = jnp.concatenate([previous, tokens], 1), start - L
    S = tokens.shape[1]
    nh, nkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    rep = nh // nkv
    M = caches[0][0].shape[2]
    positions = first[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = jnp.broadcast_to(rows_valid[:, None], (B, S))
    seen = (jnp.arange(M)[None, :] < first[:, None])  # [B, M]
    cache_bias = jnp.where(seen, 0.0, NEG_INF)[:, None, None, None, :]
    # inside the forward a key is seen from its own block on
    blk = np.arange(S) // L
    block_bias = np.where(blk[None, :] <= blk[:, None], 0.0, NEG_INF) \
        .astype(np.float32)
    scale = 1.0 / np.sqrt(float(D))
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], tokens, axis=0)
    new_caches, experts, loads = [], [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope(f"layers_{i}"):
            with jax.named_scope("attn"):
                h = rms_norm(x, p["norm1"], cfg.rms_norm_eps, cfg.dtype)
                q, k, v = qkv(cfg, p, h, positions, M)
                k_cache, v_cache = caches[i]
                qg = jnp.moveaxis(q, 2, 1).reshape(B, nkv, rep, S, D)
                kb, vb = (jnp.moveaxis(t, 2, 1) for t in (k, v))
                s_cache = jnp.einsum(
                    "bgrqd,bgmd->bgrqm", qg, k_cache,
                    preferred_element_type=jnp.float32) * scale + cache_bias
                s_block = jnp.einsum(
                    "bgrqd,bgkd->bgrqk", qg, kb,
                    preferred_element_type=jnp.float32) * scale + block_bias
                probs = jax.nn.softmax(
                    jnp.concatenate([s_cache, s_block], -1), axis=-1)
                out = jnp.einsum("bgrqm,bgmd->bgrqd",
                                 probs[..., :M].astype(cfg.dtype), v_cache,
                                 preferred_element_type=jnp.float32) \
                    + jnp.einsum("bgrqk,bgkd->bgrqd",
                                 probs[..., M:].astype(cfg.dtype), vb,
                                 preferred_element_type=jnp.float32)
                out = jnp.moveaxis(out.reshape(B, nh, S, D), 1, 2)
                x = x + out.reshape(B, S, nh * D).astype(cfg.dtype) \
                    @ p["o_proj"]
                if previous is not None:
                    put = jax.vmap(lambda c, new, at: jax.lax.
                                   dynamic_update_slice(c, new, (0, at, 0)))
                    new_caches.append((put(k_cache, kb[:, :, :L], first),
                                       put(v_cache, vb[:, :, :L], first)))
            x, top_e, load = _moe_block(cfg, p, x, valid)
            experts.append(top_e)
            loads.append(load)
    with jax.named_scope("lm_head"):
        h = rms_norm(x[:, S - L:], params["norm"], cfg.rms_norm_eps,
                     cfg.dtype)
        logits = jnp.dot(h, params["lm_head"],
                         preferred_element_type=jnp.float32)
    return (logits, new_caches if previous is not None else None,
            jnp.stack(experts), jnp.stack(loads))
