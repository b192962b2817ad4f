"""The ``dots3_note`` decoder (dots3-note-prev): latent attention in two
geometries over ONE latent cache — full layers whose every query sees the
``index_topk`` keys a learned indexer picks, sliding layers that see the
latest ``sliding_window_size`` — and sparse experts beside a shared one,
decoded a token at a time.

Layer equations (``chipbench/reference/dots3_note.py`` is the plain form
and lists what is assumed of the published model): ``x <- x +
Attn(RMSNorm(x))``, ``x <- x + FF(RMSNorm(x))``, a final RMSNorm, an untied
head.

- ``Attn``: ``c_q = RMSNorm(W_qa h) * sqrt(H / r_q)``, ``q_i = W_qb,i c_q =
  [q_i^nope ; RoPE(q_i^rope)]``; ``[c_kv' ; k^r'] = W_kva h``, ``c_kv =
  RMSNorm(c_kv') * sqrt(H / r_kv)``, ``k^r = RoPE(k^r')`` (one for all
  heads); ``[k_i^nope ; v_i] = W_kvb,i c_kv``; softmax over the keys ``S_t``
  at scale ``1 / sqrt(nope + rope)``; one sigmoid gate a head from ``h`` on
  the head's output; ``W_o``.  A ``sliding_attention`` layer reads the
  ``swa_`` numbers and ``S_t = {s : 0 <= t - s < sliding_window_size}``.  A
  ``full_attention`` layer's ``S_t`` is the indexer's: ``I(t, s) = sum_j
  w_j(t) relu(q^I_j(t) . k^I(s))`` in float32 over ``index_n_heads`` heads
  (``q^I = W^I_q c_q``, ``k^I = LayerNorm(W^I_k h)``, ``w = W^I_w h *
  heads^-0.5 * dim^-0.5``, RoPE on the first ``qk_rope_head_dim`` dims), and
  ``S_t = {s <= t : I(t, s) >= the index_topk-th largest of I(t, 0..t)}``.
- ``FF``: a dense SwiGLU in the first ``first_k_dense_replace`` layers, else
  ``experts.routed_experts`` behind ``experts.sigmoid_route`` (sigmoid
  scores, the choice by score + bias, the weights the unbiased scores over
  their sum) plus the shared expert, which every chip computes whole.

The selection is EXACT, in prefill and in decode alike: ``kth_largest``
finds the ``index_topk``-th largest visible score of a query by bisection
on the scores' bits (32 counting passes, no sort, no approximate top-k),
and a key is seen iff its score is at or above it — ONE threshold a query.
A prefill applies it as a per-query mask ``[S, S]`` (int8, shared by the
heads) under the flash kernel, which visits every causal block: dense work
under a mask.  A decode step masks its ``[rows, M]`` scores the same way.

Prefill keeps K and V a head (``W_kvb`` applied to every position); decode
is the absorbed form: ``q_i^nope`` goes through ``W_kvb,i``'s key half into
the latent space, scores and the weighted sum are taken against the cached
latents, and the value half comes last.  The same mathematics — and the
same code as ``models/joyai_llm_flash.py``'s: ``models/latent_attention.py``
has the projections, the cache's row and both forms; what is this family's
alone (the rescale and ``rotate_half`` through ``Geometry``, the indexer)
is here; the gate a head and the window's ring are ``models/gated_window.py``'s,
which ``models/laguna.py`` runs too.

The cache a row carries: per full layer ``latent [rows, M, r_kv + rope]``
(``c_kv ; k^r``, every token) and ``index [rows, M, index_head_dim]``
(``k^I``, every token); per sliding layer ``window [rows,
sliding_window_size, swa r_kv + rope]``, a ring: position ``p`` lives in
slot ``p mod sliding_window_size``; ``lengths [rows]`` (0 = a padding row).

A chip's share: ``experts_held = (first, count)`` as in ``sdar_moe``;
``vocab_held = (first, count)`` reads those rows of the embedding and the
head, and ids, logits and the choice are then over that slice (a sliced
vocabulary is a smaller vocabulary: id 0 is row ``first``).

Precision: parameters and cache in ``cfg.dtype``; norms, RoPE, softmax, the
indexer's scores, the gates, the router and the head's logits in float32.
A prefill maps its rows INSIDE the program, a group at a time
(``models/mapped_prefill.py``).

Scopes: ``embed_tokens``; ``layers_<i>/attn_full`` (``q``, ``kv``,
``indexer/scores``, ``indexer/select``, ``core``, ``gate_out``);
``layers_<i>/attn_window`` (``q``, ``kv``, ``core``, ``gate_out``);
``layers_<i>/mlp``; ``layers_<i>/moe`` (``router``, ``sort``, ``gmm``,
``combine``, ``shared``); ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import flash_attention
from ..ops.rope import apply_rotary_front
from .cached_model import CachedDecoder
from .checkpoints import (
    checkpoint_reader,
    on_device,
    swiglu_matrices,
    tensor_rows,
    torch_dtype_of,
)
from .decoder_parts import head, rms_norm
from .experts import (
    expert_ids,
    feed_forward,
    routed_experts,
    sigmoid_route,
    sum_loads,
    swiglu,
)
from .gated_window import gate_out, ring_of, ring_seen, ring_slot
from .latent_attention import (
    Geometry,
    absorbed,
    keys_values,
    latents,
    put_latents,
    queries,
    tables,
)
from .mapped_prefill import prefill_group, prefill_in_groups

LAYER_TYPES = ("full_attention", "sliding_attention")
INDEX_NORM_EPS = 1e-6   # the indexer's LayerNorm
ROUTE_EPS = 1e-20       # under the chosen scores' sum
INDEX_BLOCK = 512       # queries a block of a prefill's indexer scores
SELECT_SAMPLE = 8       # prompt positions a row whose selection is reported


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    apply_mla_qkv_lora_rescale: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # (first, count) of the experts / vocabulary rows this chip holds;
    # None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def vocab(self) -> Tuple[int, int]:
        return self.vocab_held or (0, self.vocab_size)

    def geometry(self, kind: str) -> Geometry:
        p = "swa_" if kind == "sliding_attention" else ""
        g = lambda name: getattr(self, p + name)  # noqa: E731
        return Geometry(
            g("num_attention_heads"), g("q_lora_rank"), g("kv_lora_rank"),
            g("qk_nope_head_dim"), g("qk_rope_head_dim"), g("v_head_dim"),
            float(g("rope_theta")), eps=self.rms_norm_eps,
            rescale_to=self.hidden_size
            if self.apply_mla_qkv_lora_rescale else 0, dtype=self.dtype)

    def is_sparse(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == "full_attention")

    @classmethod
    def from_hf(cls, hf: Mapping[str, Any], **overrides) -> "Dots3NoteConfig":
        """From a checkpoint's ``config.json`` (``model_type:
        dots3_note``).  What the architecture cannot express is refused,
        not ignored."""
        def refuse(what: str) -> None:
            raise ValueError(f"dots3_note: {what}")

        if hf.get("rope_scaling"):
            refuse("rope_scaling is not supported")
        if hf.get("attention_bias", False):
            refuse("attention_bias is not supported")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if hf.get(key, "headwise") != "headwise":
                refuse(f"{key} {hf[key]!r}: only the headwise gate is "
                       f"supported")
        if hf.get("moe_layer_freq", 1) != 1:
            refuse("moe_layer_freq must be 1 (every layer after the dense "
                   "ones is sparse)")
        if hf.get("n_group", 1) > 1 or hf.get("topk_group", 1) > 1:
            refuse("n_group > 1 (grouped expert choice) is not supported")
        if hf.get("scoring_func", "sigmoid") != "sigmoid" \
                or hf.get("topk_method", "noaux_tc") != "noaux_tc":
            refuse("the router is sigmoid scores chosen by noaux_tc")
        if hf.get("hidden_act", "silu") != "silu":
            refuse(f"hidden_act {hf['hidden_act']!r}: silu only")
        if hf.get("tie_word_embeddings", False):
            refuse("a tied head is not supported")
        types = tuple(hf.get("layer_types") or ())
        if len(types) != hf["num_hidden_layers"] \
                or set(types) - set(LAYER_TYPES):
            refuse(f"layer_types must name one of {LAYER_TYPES} for each "
                   f"of the {hf['num_hidden_layers']} layers, not {types}")
        if "full_attention" not in types \
                or hf.get("first_k_dense_replace", 1) >= len(types):
            refuse("a stack needs a full_attention layer and an expert "
                   "layer (the loop reports both kinds' choices)")
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in fields}
        kw["layer_types"] = types
        kw["dtype"] = torch_dtype_of(hf.get("torch_dtype", "bfloat16"))
        kw.update(overrides)
        cfg = cls(**kw)
        if cfg.index_head_dim < cfg.qk_rope_head_dim:
            refuse("index_head_dim is smaller than qk_rope_head_dim")
        return cfg


# -- parameters ------------------------------------------------------------------


def params_from_checkpoint(path: str, cfg: Dots3NoteConfig) -> Dict[str, Any]:
    with checkpoint_reader(path) as get:
        return params_from_state(get, cfg)


def layer_params(get: Callable[[str], np.ndarray], cfg, i: int
                 ) -> Dict[str, Any]:
    """Layer ``i`` under DeepSeek-V3's tensor names — the two norms, the
    latent attention's seven tensors, and the dense SwiGLU or the router,
    its selection bias (float32), the experts HELD (``cfg.held``: an
    expert is a tensor of its own, so only those are read) and the shared
    expert — as this family's and ``models/joyai_llm_flash.py``'s tree."""
    dev = functools.partial(on_device, cfg)
    p = f"model.layers.{i}."
    a = p + "self_attn."
    layer = {"norm1": dev(get(p + "input_layernorm.weight")),
             "norm2": dev(get(p + "post_attention_layernorm.weight")),
             "q_a": dev(get(a + "q_a_proj.weight"), True),
             "q_a_norm": dev(get(a + "q_a_layernorm.weight")),
             "q_b": dev(get(a + "q_b_proj.weight"), True),
             "kv_a": dev(get(a + "kv_a_proj_with_mqa.weight"), True),
             "kv_a_norm": dev(get(a + "kv_a_layernorm.weight")),
             "kv_b": dev(get(a + "kv_b_proj.weight"), True),
             "o_proj": dev(get(a + "o_proj.weight"), True)}
    f = p + "mlp."
    if not cfg.is_sparse(i):
        layer.update(swiglu_matrices(get, cfg, f))
        return layer
    layer.update(
        router=dev(get(f + "gate.weight"), True),
        expert_bias=jnp.asarray(np.asarray(
            get(f + "gate.e_score_correction_bias"), np.float32)),
        **swiglu_matrices(get, cfg, f + "experts.", experts=cfg.held),
        shared=swiglu_matrices(get, cfg, f + "shared_experts."))
    return layer


def params_from_state(get: Callable[[str], np.ndarray], cfg: Dots3NoteConfig
                      ) -> Dict[str, Any]:
    """The published tensor names (``get(name)`` loads one) as this
    module's tree, in ``cfg.dtype`` on the default device; the router's
    selection bias stays float32.  Only the experts held and the
    vocabulary rows held are read."""
    dev = functools.partial(on_device, cfg)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        a = f"model.layers.{i}.self_attn."
        layer = layer_params(get, cfg, i)
        layer["gate_proj"] = dev(get(a + "gate_proj.weight"), True)
        if kind == "full_attention":
            x = a + "indexer."
            layer.update(
                index_q=dev(get(x + "wq_b.weight"), True),
                index_k=dev(get(x + "wk.weight"), True),
                index_k_norm=dev(get(x + "k_norm.weight")),
                index_k_bias=dev(get(x + "k_norm.bias")),
                index_w=dev(get(x + "weights_proj.weight"), True))
        layers.append(layer)
    v_first, v_count = cfg.vocab
    return {"embed": dev(tensor_rows(get, "model.embed_tokens.weight",
                                     v_first, v_count)),
            "layers": layers,
            "norm": dev(get("model.norm.weight")),
            "lm_head": dev(tensor_rows(get, "lm_head.weight", v_first,
                                       v_count))}


# -- what prefill and decode share -----------------------------------------------


def _rotate_front(x, cos, sin):
    """RoPE on the first dims of ``x``'s last axis: as many as ``cos``
    and ``sin`` are wide."""
    return apply_rotary_front(x, x, cos, sin)[0]


def _index_key(cfg, p, h, cos, sin):
    """``k^I [..., index_head_dim]`` of ``h [..., H]``; ``cos``/``sin``
    broadcast against it."""
    k = (h @ p["index_k"]).astype(jnp.float32)
    mu = jnp.mean(k, -1, keepdims=True)
    var = jnp.mean((k - mu) ** 2, -1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(var + INDEX_NORM_EPS) \
        * p["index_k_norm"].astype(jnp.float32) \
        + p["index_k_bias"].astype(jnp.float32)
    return _rotate_front(k.astype(cfg.dtype), cos, sin)


def _index_weights(cfg, p, h):
    """``w [..., index_n_heads]`` float32."""
    return jnp.dot(h, p["index_w"], preferred_element_type=jnp.float32) \
        * float(cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)


def _sortable(x):
    """float32 -> uint32 in the same order (``-0.0`` as ``0.0``); never 0
    for a finite ``x``, so 0 can stand for a key that is not visible."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = jnp.where(b == jnp.uint32(0x80000000), jnp.uint32(0), b)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def kth_largest(u, k: int):
    """The ``k``-th largest of ``u [..., n]`` (uint32) along the last axis,
    exactly, by bisection on the bits: the largest ``t`` with at least ``k``
    entries ``>= t``.  0 where fewer than ``k`` entries are above 0."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (u >= cand[..., None]).sum(-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(u.shape[:-1], jnp.uint32))


def select_keys(scores, visible, k: int):
    """``S_t`` of each query: ``visible`` keys whose score is at or above
    the query's ``k``-th largest visible score (all of them where fewer
    are visible).  ``scores``, ``visible [..., n]`` -> bool ``[..., n]``."""
    u = jnp.where(visible, _sortable(scores), jnp.uint32(0))
    return visible & (u >= kth_largest(u, k)[..., None])


def route(cfg: Dots3NoteConfig, p, x):
    """``(top_e [T, k], weights [T, k] float32)`` of ``x [T, H]``."""
    return sigmoid_route(x, p["router"], p["expert_bias"],
                         cfg.num_experts_per_tok, cfg.norm_topk_prob,
                         cfg.routed_scaling_factor, ROUTE_EPS)


def moe(cfg: Dots3NoteConfig, p, x, valid):
    """``x [T, H]`` through the router, the experts held here and the
    shared expert.  Returns ``(y [T, H], top_e [T, k], load [4])``."""
    with jax.named_scope("router"):
        top_e, w = route(cfg, p, x)
    y, load = routed_experts(p, x, valid, top_e, w, cfg.held, cfg.dtype)
    with jax.named_scope("shared"):
        y = y + swiglu(cfg, p["shared"], x)
    return y, top_e, load


def _gate_out(cfg, g: Geometry, p, h, out):
    """``h [B, S, H]``, ``out [B, heads, S, v]`` -> ``W_o [g_i * o_i]``
    (``gated_window.gate_out``, under the name the benchmark's tests of
    this family reach it by)."""
    return gate_out(p, h, out, cfg.dtype)


# -- prefill ---------------------------------------------------------------------


def _select_prefill(cfg, q_i, k_i, w, valid):
    """The prompt's selection ``[B, S, S]`` (bool) from ``q^I [B, S, j,
    d]``, ``k^I [B, S, d]``, ``w [B, S, j]``: a block of queries at a time
    against the keys up to the block's last (the rest are its future), so
    the float32 scores of all heads exist for one block only."""
    B, S = valid.shape
    at = jnp.arange(S)
    blocks = []
    for start in range(0, S, INDEX_BLOCK):
        stop = min(S, start + INDEX_BLOCK)
        with jax.named_scope("scores"):
            s = jnp.einsum("bqjd,bkd->bjqk", q_i[:, start:stop],
                           k_i[:, :stop],
                           preferred_element_type=jnp.float32)
            scores = (jax.nn.relu(s) * jnp.moveaxis(
                w[:, start:stop], -1, 1)[..., None]).sum(1)  # [B, q, k]
        with jax.named_scope("select"):
            visible = (at[None, :stop] <= at[start:stop, None])[None] \
                & valid[:, None, :stop]
            chosen = visible if stop <= cfg.index_topk else \
                select_keys(scores, visible, cfg.index_topk)
            blocks.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, S - stop))))
    return jnp.concatenate(blocks, 1)


def _sample_positions(cfg, lengths, n: int):
    """``n`` prompt positions a row past ``index_topk`` (where a query
    chooses), from the row's length; -1 where the row has none."""
    span = lengths - cfg.index_topk
    i = jnp.arange(n, dtype=jnp.int32)
    at = cfg.index_topk + (i[None, :] * 7919 + lengths[:, None] * 31) \
        % jnp.maximum(span, 1)[:, None]
    return jnp.where(span[:, None] > 0, at, -1)


def _prefill_rows(cfg: Dots3NoteConfig, params, ids, lengths, cache_len: int):
    """``ids [B, S]`` right-padded, ``lengths [B]``: the whole prompt under
    the causal mask, all rows of ``ids`` at once.  Returns the three kinds
    of cache, ``logits [B, V]``, ``experts [layers, B, S, k]``, ``load
    [layers, 4]``, ``keys [B, 2]`` (selected, visible: summed over the
    full layers and the row's queries), ``selected [full layers, B, n,
    S / 8]`` (bits) at ``selected_at [B, n]``."""
    B, S = ids.shape
    W = cfg.sliding_window_size
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]
    mask = valid.astype(jnp.int32)
    last = jnp.maximum(lengths - 1, 0)
    sample_at = _sample_positions(cfg, lengths, SELECT_SAMPLE)
    pad_to = ((0, 0), (0, cache_len - S), (0, 0))
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], ids, axis=0)
    latent, index, window, experts, loads, selected = [], [], [], [], [], []
    keys = jnp.zeros((B, 2), jnp.int32)
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        g = cfg.geometry(kind)
        cos, sin = tables(g, positions, S)
        with jax.named_scope(f"layers_{i}"):
            h = rms_norm(x, p["norm1"], cfg.rms_norm_eps, cfg.dtype)
            if kind == "full_attention":
                with jax.named_scope("attn_full"):
                    c_q, q = queries(g, p, h, cos, sin)
                    with jax.named_scope("kv"):
                        lat = latents(g, p, h, cos, sin)
                        k, v = keys_values(g, p, lat)
                    with jax.named_scope("indexer"):
                        q_i = _rotate_front(
                            (c_q @ p["index_q"]).reshape(
                                B, S, cfg.index_n_heads, cfg.index_head_dim),
                            cos[:, :, None], sin[:, :, None])
                        k_i = _index_key(cfg, p, h, cos, sin)
                        chosen = _select_prefill(
                            cfg, q_i, k_i, _index_weights(cfg, p, h), valid)
                        with jax.named_scope("select"):
                            keys = keys + jnp.stack(
                                [(chosen & valid[:, :, None]).sum((1, 2)),
                                 lengths * (lengths + 1) // 2],
                                -1).astype(jnp.int32)
                            rows = jnp.take_along_axis(
                                chosen, jnp.maximum(sample_at, 0)[:, :, None],
                                axis=1)
                            selected.append(jnp.packbits(rows, axis=-1))
                    with jax.named_scope("core"):
                        out = flash_attention(
                            q, k, v, key_padding_mask=mask, causal=True,
                            scale=g.scale, select=chosen.astype(jnp.int8),
                            lengths=lengths)
                    x = x + _gate_out(cfg, g, p, h, out)
                    latent.append(jnp.pad(lat, pad_to))
                    index.append(jnp.pad(k_i, pad_to))
            else:
                with jax.named_scope("attn_window"):
                    _, q = queries(g, p, h, cos, sin)
                    with jax.named_scope("kv"):
                        lat = latents(g, p, h, cos, sin)
                        k, v = keys_values(g, p, lat)
                    with jax.named_scope("core"):
                        out = flash_attention(
                            q, k, v, key_padding_mask=mask, causal=True,
                            window=2 * (W - 1), scale=g.scale,
                            lengths=lengths)
                    x = x + _gate_out(cfg, g, p, h, out)
                    with jax.named_scope("kv"):
                        window.append(ring_of(lat, lengths, W))
            x, top_e, load = feed_forward(cfg, cfg.is_sparse(i), p, x, valid,
                                          moe, as_one_row=True)
            if top_e is not None:
                experts.append(expert_ids(top_e, cfg.n_routed_experts))
                loads.append(load)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return (latent, index, window, head(cfg, params, x_last),
            jnp.stack(experts), jnp.stack(loads), keys,
            jnp.stack(selected), sample_at)


def _row_bytes(cfg: Dots3NoteConfig, S: int) -> int:
    """A prefill row's temporaries, reckoned from above: a full layer's
    arrays a head (q and k ``nope + rope``, ``W_kvb``'s output ``nope +
    v``, v and the core's output ``v``), one block of the indexer's
    float32 scores of all its heads, the indexer's queries, the selection
    as bool and int8 — and every array of ``S * k`` rows the expert layer
    writes (``3 (H + I)`` a pair) as if live beside them.  At the guard's
    widths (S 8192, 128 heads, k 8, bfloat16) 3.2 + 2.6 = 5.8 GB; the
    compiler's count for a described v5e is 4.2 GB at one row a group of
    the cell's six layers (2.5 of two layers) and 1.7-2.5 more a row."""
    item = jnp.dtype(cfg.dtype).itemsize
    g = cfg.geometry("full_attention")
    heads = g.heads * S * (2 * (g.nope + g.rope) + (g.nope + g.v)
                           + 2 * g.v) * item
    indexer = cfg.index_n_heads * S * (4 * min(INDEX_BLOCK, S)
                                       + cfg.index_head_dim * item)
    experts = 3 * S * cfg.num_experts_per_tok * item \
        * (cfg.hidden_size + cfg.moe_intermediate_size)
    return heads + indexer + 2 * S * S + experts


def _cache_bytes(cfg: Dots3NoteConfig, rows: int, cache_len: int) -> int:
    """The bytes of the cache a prefill of ``rows`` returns."""
    full, sliding = (cfg.geometry(k) for k in LAYER_TYPES)
    n_full = len(cfg.full_layers)
    return rows * jnp.dtype(cfg.dtype).itemsize * (
        n_full * cache_len * (full.r_kv + full.rope + cfg.index_head_dim)
        + (len(cfg.layer_types) - n_full) * cfg.sliding_window_size
        * (sliding.r_kv + sliding.rope))


def _prefill_groups(cfg: Dots3NoteConfig, params, ids, lengths,
                    cache_len: int, group: int):
    """``prefill`` at ``group`` rows a call of ``_prefill_rows``."""
    if group == 1:
        return _prefill_a_row_at_a_time(cfg, params, ids, lengths, cache_len)

    return prefill_in_groups(
        lambda ids, lengths: _prefill_rows(cfg, params, ids, lengths,
                                           cache_len),
        ("latent", "index", "window", "logits", "experts", "load", "keys",
         "selected", "selected_at"), ("experts", "selected"), group, ids,
        lengths)


def _prefill_a_row_at_a_time(cfg: Dots3NoteConfig, params, ids, lengths,
                             cache_len: int):
    """``_prefill_groups`` at one row a group, written as it was before
    there were groups: the row cut out of ``[B, S]`` inside the map and
    every output but the cache leaves squeezed there.  ``map_row_groups``
    at one row is the same arithmetic under other reshapes, and the
    compiler schedules THAT program's weight prefetches differently: on a
    v5e the guard's cell read 2.2519 routes/s against 2.3212 for this form
    (PERF.md section 6, PR 37).  This lowers to the program of before
    groups line for line."""
    def one(row):
        (latent, index, window, logits, experts, load, keys, selected,
         at) = _prefill_rows(cfg, params, row[0][None], row[1][None],
                             cache_len)
        return (latent, index, window, logits[0], experts[:, 0], load,
                keys[0], selected[:, 0], at[0])

    (latent, index, window, logits, experts, loads, keys, selected,
     at) = jax.lax.map(one, (ids, lengths))
    cache = {"latent": [a[:, 0] for a in latent],
             "index": [a[:, 0] for a in index],
             "window": [a[:, 0] for a in window],
             "lengths": lengths.astype(jnp.int32)}
    return cache, logits, {
        "experts": jnp.moveaxis(experts, 0, 1), "load": sum_loads(loads),
        "keys": keys, "selected": jnp.moveaxis(selected, 0, 1),
        "selected_at": at}


def prefill(cfg: Dots3NoteConfig, params, ids, lengths, cache_len: int):
    """``ids [B, S]`` right-padded prompts of ``lengths [B]`` (0 = a padding
    row) -> ``(cache, logits [B, V] float32 at each row's last token, aux)``
    with ``aux = {"experts" [expert layers, B, S, k], "load" [expert
    layers, 4], "keys" [B, 2], "selected" [full layers, B, n, S / 8],
    "selected_at" [B, n]}``.  ``mapped_prefill.prefill_group`` rows at a
    time inside the program, so a bucket's temporaries are those of ONE
    group whatever the batch (at the guard's widths a row's attention
    arrays leave room for no second one)."""
    return _prefill_groups(
        cfg, params, ids, lengths, cache_len,
        prefill_group(cfg, params, *ids.shape, cache_len, _row_bytes,
                      _cache_bytes))


# -- decode: one token a row against the latent cache ----------------------------


def decode(cfg: Dots3NoteConfig, params, cache, tokens, positions):
    """``tokens [B]`` at ``positions [B]`` (a row's count of tokens before
    this one), all rows together.  Returns ``(cache, logits [B, V], aux)``
    with ``aux["experts"] [expert layers, B, k]``, ``"keys" [B, 2]`` and
    ``"selected" [full layers, B, M / 8]`` (bits); the cache comes back
    with this token's latent and index key at column ``positions`` of every
    full layer and in slot ``positions mod window`` of every sliding one."""
    B = tokens.shape[0]
    W = cfg.sliding_window_size
    live = cache["lengths"] > 0
    pos = positions[:, None]
    put = put_latents
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], tokens, axis=0)[:, None]  # [B, 1, H]
    latent, index, window, experts, loads, selected = [], [], [], [], [], []
    keys = jnp.zeros((B, 2), jnp.int32)
    lat_in, idx_in, win_in = (iter(cache[k])
                              for k in ("latent", "index", "window"))
    M = cache["latent"][0].shape[1]  # every position has a column there
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        g = cfg.geometry(kind)
        with jax.named_scope(f"layers_{i}"):
            h = rms_norm(x, p["norm1"], cfg.rms_norm_eps, cfg.dtype)
            if kind == "full_attention":
                with jax.named_scope("attn_full"):
                    lat, idx = next(lat_in), next(idx_in)
                    cos, sin = tables(g, pos, M)
                    c_q, q = queries(g, p, h, cos, sin)
                    with jax.named_scope("kv"):
                        lat = put(lat, latents(g, p, h, cos, sin),
                                  positions)
                    visible = jnp.arange(M)[None, :] <= pos  # [B, M]
                    with jax.named_scope("indexer"):
                        q_i = _rotate_front(
                            (c_q @ p["index_q"]).reshape(
                                B, cfg.index_n_heads, cfg.index_head_dim),
                            cos, sin)
                        idx = put(idx, _index_key(cfg, p, h, cos, sin),
                                  positions)
                        with jax.named_scope("scores"):
                            s = jnp.einsum(
                                "bjd,bmd->bjm", q_i, idx,
                                preferred_element_type=jnp.float32)
                            scores = (jax.nn.relu(s) * _index_weights(
                                cfg, p, h[:, 0])[..., None]).sum(1)
                        with jax.named_scope("select"):
                            chosen = select_keys(scores, visible,
                                                 cfg.index_topk)
                            keys = keys + jnp.stack(
                                [chosen.sum(-1), visible.sum(-1)],
                                -1).astype(jnp.int32) * live[:, None]
                            selected.append(jnp.packbits(chosen, axis=-1))
                    with jax.named_scope("core"):
                        out = absorbed(g, p, q, lat, chosen[:, None])
                    x = x + _gate_out(cfg, g, p, h, out)
                    latent.append(lat)
                    index.append(idx)
            else:
                with jax.named_scope("attn_window"):
                    ring = next(win_in)
                    cos, sin = tables(g, pos, M)
                    _, q = queries(g, p, h, cos, sin)
                    with jax.named_scope("kv"):
                        ring = put(ring, latents(g, p, h, cos, sin),
                                   ring_slot(positions, W))
                    with jax.named_scope("core"):
                        out = absorbed(g, p, q, ring,
                                       ring_seen(positions, W)[:, None])
                    x = x + _gate_out(cfg, g, p, h, out)
                    window.append(ring)
            x, top_e, load = feed_forward(cfg, cfg.is_sparse(i), p, x,
                                          live[:, None], moe)
            if top_e is not None:
                experts.append(expert_ids(top_e[:, 0], cfg.n_routed_experts))
                loads.append(load)
    cache = {"latent": latent, "index": index, "window": window,
             "lengths": cache["lengths"]}
    return cache, head(cfg, params, x[:, 0]), {
        "experts": jnp.stack(experts), "load": jnp.stack(loads),
        "keys": keys, "selected": jnp.stack(selected)}


class CachedModel(CachedDecoder):
    """This decoder behind the interface ``models.generate.GreedyGenerator``
    decodes through; its prefill's flash calls are its layers' of both
    geometries, all their heads."""

    def __init__(self, config: Dots3NoteConfig) -> None:
        super().__init__(
            config, prefill, decode,
            cache_kinds=("latent", "index", "window"),
            group_sizes=(_row_bytes, _cache_bytes),
            attn_layers=[
                (config.geometry(kind).heads, 0 if kind == "full_attention"
                 else 2 * (config.sliding_window_size - 1))
                for kind in config.layer_types])
