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

The expert layer is told which experts it holds (``experts_held = (first,
count)``): the router keeps its published width and its experts per token,
the routed (token, expert) pairs are sorted by expert and only the pairs of
experts held here go through the grouped matmul; what an expert that lives
elsewhere would add is left out (on one chip there is no exchange, and no
stand-in for one).  No token is dropped and nothing is padded to a
capacity.

Functions over a plain parameter tree, not flax modules: the experts are
stacked ``[experts held, H, 2I]`` / ``[experts held, I, H]`` arrays that a
grouped matmul indexes by group.  ``routed_experts`` is the ONE expert layer
(sort, grouped matmuls, combine, ``load``) of every sparse decoder; only the
router in front of it differs (softmax here, sigmoid and a selection bias
in ``models/lfm2_moe.py``).  Scopes on the device timeline:
``embed_tokens``, ``layers_<i>/attn``, ``layers_<i>/moe`` (``moe/router``,
``moe/sort``, ``moe/gmm``, ``moe/combine``), ``lm_head``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import flash_attention
from ..ops.rope import RopeSpec, apply_rotary
from .qwen3 import torch_dtype_of

NEG_INF = -1e30


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


@contextlib.contextmanager
def checkpoint_reader(path: str):
    """``get(name) -> tensor`` over a checkpoint directory
    (``model.safetensors``, or sharded files with
    ``model.safetensors.index.json``), one tensor loaded per call;
    ``get.rows(name, first, count)`` reads those rows of it alone."""
    from safetensors import safe_open

    index = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index) as f:
            where = json.load(f)["weight_map"]
    else:
        with safe_open(os.path.join(path, "model.safetensors"), "np") as f:
            where = {k: "model.safetensors" for k in f.keys()}
    handles: Dict[str, Any] = {}

    def handle(name: str):
        fname = where[name]
        if fname not in handles:
            handles[fname] = safe_open(os.path.join(path, fname), "np")
        return handles[fname]

    def get(name: str) -> np.ndarray:
        return handle(name).get_tensor(name)

    get.rows = lambda name, first, count: \
        handle(name).get_slice(name)[first:first + count]

    try:
        yield get
    finally:
        handles.clear()


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

    def dev(a: np.ndarray, transpose: bool = False) -> jnp.ndarray:
        x = jnp.asarray(a).astype(cfg.dtype)
        return jnp.swapaxes(x, -1, -2) if transpose else x

    first, count = cfg.held
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        experts = {k: np.stack([get(f"{p}mlp.experts.{e}.{k}_proj.weight")
                                for e in range(first, first + count)])
                   for k in ("gate", "up", "down")}
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
            "gate_up": jnp.concatenate(
                [dev(experts["gate"], True), dev(experts["up"], True)], -1),
            "down": dev(experts["down"], True)})
        del experts
    embed = dev(get("model.embed_tokens.weight"))
    params = {"embed": embed, "layers": layers,
              "norm": dev(get("model.norm.weight")),
              "lm_head": embed.T if cfg.tie_word_embeddings
              else dev(get("lm_head.weight"), True)}
    return params


# -- layers ----------------------------------------------------------------------


def rms_norm(x, w, eps: float, dtype):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(dtype)


def qkv(cfg: SdarMoeConfig, p, x, positions, table_len: int):
    """``x [B, S, H]`` -> q ``[B, S, heads, D]``, k and v ``[B, S, kv, D]``,
    q and k normalised per head and rotated at ``positions [B, S]``, all
    below ``table_len``."""
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


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# (k, n) of an expert matrix -> (k tile, n tile) where a sweep on the chip
# beat the rule below: whole-K tiles at the lfm2_moe widths
# (benchmarks/lfm2_gmm_sweep.py, PERF.md section 6, PR 32: a prefill row's
# gate+up 3.23 -> 2.51 ms, down 2.30 -> 1.33 ms; a decode forward the same)
_SWEPT_TILES = {(2048, 3072): (2048, 1024), (1536, 2048): (1536, 1024)}


def _megablox(lhs, rhs, group_sizes):
    """The Pallas megablox kernel (interpreted on the CPU, for tests).
    Rows a tile: 128 reads a touched expert's matrices once for its handful
    of rows (256: 1.55 ms a layer of a block forward, 512: 3.06); from
    8192 rows on, 256 (a prefill: 11.2 against 11.7 ms)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[-1]
    tk, tn = _SWEPT_TILES.get((k, n), (min(1024, k), min(768, n)))
    tiling = (min(256 if m >= 8192 else 128, m), tk, tn)
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tiling, interpret=_on_cpu())


def _grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [m, k]`` rows sorted by group times ``rhs [groups, k, n]``.
    On a TPU the megablox kernel: at the published widths on a v5e
    (benchmarks/moe_gmm_bench.py, PERF.md section 6, PR 28) a block
    forward's 512 pairs over 90 touched experts take 1.30 ms a layer
    against 2.89 for ``jax.lax.ragged_dot`` (the experts' matrices alone
    are 1.04 ms of HBM), a 16 x 512 prefill 11.2 against 12.9.  On the CPU
    ``ragged_dot``, the same sums."""
    if _on_cpu():
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return _megablox(lhs, rhs, group_sizes)


def routed_experts(p, x, valid, top_e, top_w, held: Tuple[int, int], dtype):
    """The expert layer behind any router: ``x [T, H]``, ``valid [T]`` (a
    padding token routes nowhere), the router's choice ``top_e [T, k]``
    with its weights ``top_w [T, k]`` (float32), ``held = (first, count)``
    the experts whose matrices ``p["gate_up"] [count, H, 2I]`` and
    ``p["down"] [count, I, H]`` are.  The routed pairs are sorted by
    expert, the pairs of held experts go through the grouped matmuls, and
    each token's results come back weighted and summed.

    Each pair is moved once each way, in ``dtype``: ``x``'s rows are
    gathered into sorted order, and the grouped matmuls' rows are gathered
    back with the k-th choices of all tokens together (``[k, T, H]``: k
    on the major axis is a view; on a tiled axis it would be a copy), so
    that ONE fusion reads them and writes ``y``: drop, convert to float32,
    times the float32 weight, summed over k in index order, one rounding
    to ``dtype``.  A value converts the same before or after it is moved,
    so no float32 array of ``pairs`` rows is ever written.  Both gathers'
    indices are in range by construction (``mode="clip"``: no select
    against a fill value).  The rows past the held groups are DROPPED by a
    ``where``, not weighted by zero: the grouped matmul never wrote them,
    and whatever stands there may be NaN.

    Returns ``(y [T, H], load [4])`` with ``load`` = the busiest held
    expert's pairs, the pairs computed here, the number of held experts
    that got any, and the busiest's pairs over the mean."""
    T, H = x.shape
    k, I = top_e.shape[-1], p["down"].shape[-2]
    first, count = held
    with jax.named_scope("sort"):
        local = top_e - first
        here = (local >= 0) & (local < count) & valid[:, None]  # [T, k]
        group = jnp.where(here, local, count).reshape(-1)  # elsewhere: last
        order = jnp.argsort(group, stable=True)
        group_sizes = jnp.bincount(group, length=count + 1)[:count] \
            .astype(jnp.int32)
        xs = jnp.take(x, order // k, axis=0, mode="clip")
    with jax.named_scope("gmm"):
        gu = _grouped_matmul(xs, p["gate_up"], group_sizes)
        h = (jax.nn.silu(gu[:, :I].astype(jnp.float32))
             * gu[:, I:].astype(jnp.float32)).astype(dtype)
        ys = _grouped_matmul(h, p["down"], group_sizes)
    with jax.named_scope("combine"):
        # where pair (t, j) stands among the sorted rows, j-major
        back = jnp.argsort(order).reshape(T, k).T
        ys = jnp.take(ys, back.reshape(-1), axis=0, mode="clip") \
            .reshape(k, T, H)
        w = jnp.where(here, top_w, 0.0)
        y = sum(jnp.where(here[:, j, None], ys[j].astype(jnp.float32), 0.0)
                * w[:, j, None] for j in range(k))
    busiest, pairs = group_sizes.max(), group_sizes.sum()
    load = jnp.stack([busiest, pairs, (group_sizes > 0).sum(),
                      busiest * count / jnp.maximum(pairs, 1)]
                     ).astype(jnp.float32)
    return y.astype(dtype), load


def softmax_route(x, router, k: int, norm: bool):
    """The softmax choice of every decoder that has one (this one and
    ``models/laguna.py``): ``x [T, H]`` -> ``(top_e [T, k], weights [T, k]
    float32)``.  ``p = softmax(x W)`` in float32 over ALL experts, the ``k``
    largest are chosen, their weights ``p`` there, over their sum if
    ``norm``."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if norm:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_e, top_p


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
