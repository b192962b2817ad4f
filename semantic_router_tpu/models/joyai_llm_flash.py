"""The ``joyai_llm_flash`` decoder (JoyAI-LLM-Flash, 48B-A2.7B): DeepSeek-V3's
layers — latent attention in every layer, a leading dense SwiGLU, then
sparse experts chosen by sigmoid scores plus a selection bias beside a
shared expert — and its multi-token-prediction (MTP) module, which in
serving is a DRAFTER: a decode step runs the last committed token and the
module's draft of the next one, and commits one token or two.

Layer equations (``chipbench/reference/joyai_llm_flash.py`` is the plain
form and lists what is assumed of the published model): ``x <- x +
Attn(RMSNorm(x))``, ``x <- x + FF(RMSNorm(x))``; ``h_i = RMSNorm(x_i)`` after
the last layer is what the untied head reads: ``L_i = W_head h_i``, the
logits for ``t_{i+1}``.

- ``Attn``: ``models/latent_attention.py`` with no rescale, no gate, no
  selection, and RoPE in INTERLEAVED pairing (``rope_interleave``).  A
  prefill expands K and V a head under the flash kernel; a step is the
  absorbed form against the latent cache, at one or TWO query positions a
  row, the second seeing the first.
- ``FF``: ``dots3_note``'s ``moe`` (the same ``sigmoid_route`` and
  ``routed_experts``): the top 8 of ``sigmoid + b``, weights the unbiased
  sigmoids over their sum times ``routed_scaling_factor``, plus the shared
  expert, which every chip computes whole.
- the MTP module: ``h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]``,
  one block of the expert layers' kind over a latent cache of its own, a
  final RMSNorm of its own and the MAIN model's head (embedding and head are
  held once): ``D_i``, the logits for ``t_{i+2}``.

Self-drafting, as ``models.generate.GreedyGenerator`` drives it (one device
program a step; the choice of tokens is the generator's).  A row's state:
the last committed token ``x`` at position ``p``, not yet run, and a draft
``d`` for ``p + 1``.  ``verify`` runs the layers on ``[x, d]`` at ``[p, p +
1]`` and writes both latents; the generator takes ``y = argmax L_p``,
accepts iff ``y == d``, and then ``z = argmax L_{p+1}`` is committed too;
``draft`` runs the module on ``[(h_p, y), (h_{p+1}, z)]`` — the second
routes nowhere on a rejection — and gives ``D_p`` or, accepted, ``D_{p+1}``:
the next draft.  A rejected position's columns (in both caches) are
overwritten by the next step, which starts there; every query sees the
columns up to its own position and nothing else says what is real.  After a
prefill the module runs over the prompt (``first_draft``: it fills its cache
and drafts for the first step).  With no module in the checkpoint
(``num_nextn_predict_layers`` 0) the model decodes a token at a time
(``decode``: the same step at one position).

The cache: ``latent`` a layer ``[rows, M, r_kv + rope]`` (``c_kv ; k^r``),
``draft [rows, M, r_kv + rope]`` (the module's block's), ``lengths [rows]``
(the prompt's; 0 = a padding row).

A chip's share: ``experts_held = (first, count)`` as in ``sdar_moe``.

Precision: parameters and cache in ``cfg.dtype``; norms, RoPE, softmax, the
router and the head's logits in float32.

Scopes: a prefill's ``embed_tokens``, ``layers_<i>/attn`` (``q``, ``kv``,
``core``), ``layers_<i>/mlp`` | ``layers_<i>/moe`` (``router``, ``sort``,
``gmm``, ``combine``, ``shared``), ``lm_head``; a step's the same under
``verify/``; the module's ``mtp/eh_proj``, ``mtp/block/{attn, moe}``,
``mtp/head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import flash_attention
# a layer's tensors and its expert half are dots3_note's: ``layer_params`` and
# ``moe``, read there at call time — the ONE import of a model file by
# another (the benchmark's tests inject this family's router fault through
# ``dots3_note.route``; ROADMAP D6 names the debt)
from . import dots3_note
from .cached_model import CachedDecoder
from .checkpoints import checkpoint_reader, on_device, torch_dtype_of
from .decoder_parts import rms_norm
from .experts import expert_ids, feed_forward
from .latent_attention import (
    Geometry,
    absorbed,
    keys_values,
    latents,
    out_proj,
    put_latents,
    queries,
    tables,
)


@dataclasses.dataclass(frozen=True)
class JoyaiLlmFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # (first, count) of the experts this chip holds; None = all of them
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def geometry(self) -> Geometry:
        return Geometry(
            self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            float(self.rope_theta), eps=self.rms_norm_eps,
            interleave=self.rope_interleave, dtype=self.dtype)

    def is_sparse(self, i: int) -> bool:
        """The MTP module's block, ``i == num_hidden_layers``, is one."""
        return i >= self.first_k_dense_replace

    @classmethod
    def from_hf(cls, hf: Mapping[str, Any], **overrides
                ) -> "JoyaiLlmFlashConfig":
        """From a checkpoint's ``config.json`` (``model_type:
        joyai_llm_flash``).  What the architecture cannot express is
        refused, not ignored."""
        def refuse(what: str) -> None:
            raise ValueError(f"joyai_llm_flash: {what}")

        if hf.get("rope_scaling"):
            refuse("rope_scaling is not supported")
        if hf.get("attention_bias", False):
            refuse("attention_bias is not supported")
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
        if hf.get("num_nextn_predict_layers", 0) > 1:
            refuse("more than one MTP module (one drafted token a step)")
        if not hf.get("first_k_dense_replace", 1) < hf["num_hidden_layers"]:
            refuse("a stack needs an expert layer (the loop reports their "
                   "choices)")
        if "qk_head_dim" in hf and hf["qk_head_dim"] != \
                hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]:
            refuse("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in fields}
        kw["dtype"] = torch_dtype_of(hf.get("torch_dtype", "bfloat16"))
        kw.update(overrides)
        return cls(**kw)


# -- parameters ------------------------------------------------------------------


def params_from_checkpoint(path: str, cfg: JoyaiLlmFlashConfig
                           ) -> Dict[str, Any]:
    with checkpoint_reader(path) as get:
        return params_from_state(get, cfg)


def params_from_state(get: Callable[[str], np.ndarray],
                      cfg: JoyaiLlmFlashConfig) -> Dict[str, Any]:
    """The published tensor names (``get(name)`` loads one) as this
    module's tree, in ``cfg.dtype`` on the default device (a layer is
    ``dots3_note.layer_params``: only the experts held are read).  The MTP
    module's tensors lie under ``model.layers.<num_hidden_layers>.``; its
    embedding and head are the main model's and are not read twice."""
    def dev(name: str, transpose: bool = False):
        return on_device(cfg, get(name), transpose)

    n = cfg.num_hidden_layers
    params = {"embed": dev("model.embed_tokens.weight"),
              "layers": [dots3_note.layer_params(get, cfg, i)
                         for i in range(n)],
              "norm": dev("model.norm.weight"),
              "lm_head": dev("lm_head.weight")}
    if cfg.num_nextn_predict_layers:
        m = f"model.layers.{n}."
        params["mtp"] = {"enorm": dev(m + "enorm.weight"),
                         "hnorm": dev(m + "hnorm.weight"),
                         "eh_proj": dev(m + "eh_proj.weight", True),
                         "norm": dev(m + "shared_head.norm.weight"),
                         "block": dots3_note.layer_params(get, cfg, n)}
    return params


# -- layers ----------------------------------------------------------------------


def _attend_prompt(g: Geometry, p, h, cos, sin, mask):
    """``Attn(h)`` of a prompt ``h [B, S, H]`` under the causal mask, and
    the latents it leaves ``[B, S, r_kv + rope]``."""
    with jax.named_scope("attn"):
        _, q = queries(g, p, h, cos, sin)
        with jax.named_scope("kv"):
            lat = latents(g, p, h, cos, sin)
            k, v = keys_values(g, p, lat)
        with jax.named_scope("core"):
            out = flash_attention(q, k, v, key_padding_mask=mask,
                                  causal=True, scale=g.scale)
        return out_proj(g, p, out), lat


def _attend_cached(g: Geometry, p, h, cos, sin, lat, positions, seen):
    """``Attn(h)`` of ``h [B, Q, H]`` at columns ``positions [B]`` ..
    ``positions + Q`` of the cache ``lat``, which comes back with their
    latents; ``seen [B, Q, M]``."""
    with jax.named_scope("attn"):
        _, q = queries(g, p, h, cos, sin)
        with jax.named_scope("kv"):
            lat = put_latents(lat, latents(g, p, h, cos, sin), positions)
        with jax.named_scope("core"):
            out = absorbed(g, p, q, lat, seen)
        return out_proj(g, p, out), lat


def _hidden(cfg, norm, x):
    """``h = RMSNorm(x)``: what a head reads (and the MTP module)."""
    return rms_norm(x, norm, cfg.rms_norm_eps, cfg.dtype)


def _logits(params, h):
    """``h [..., H]`` -> logits ``[..., V]`` float32, the one head."""
    return jnp.einsum("...h,vh->...v", h, params["lm_head"],
                      preferred_element_type=jnp.float32)


def _mtp_input(cfg, m, embed_next, hidden):
    """``W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]``."""
    with jax.named_scope("eh_proj"):
        return jnp.concatenate(
            [rms_norm(embed_next, m["enorm"], cfg.rms_norm_eps, cfg.dtype),
             rms_norm(hidden, m["hnorm"], cfg.rms_norm_eps, cfg.dtype)],
            -1) @ m["eh_proj"]


def _join(aux, top_e, load):
    """``aux`` with one more expert layer's choice and load behind the
    main model's (the MTP module's block is row ``-1`` of both)."""
    return {"experts": jnp.concatenate([aux["experts"], top_e[None]], 0),
            "load": jnp.concatenate([aux["load"], load[None]], 0)}


# -- prefill ---------------------------------------------------------------------


def _prompt_layer(cfg, i, p, x, positions, valid):
    """One whole layer over a prompt ``x [B, S, H]``: ``(x, latents, top_e
    | None, load | None)``."""
    g = cfg.geometry
    cos, sin = tables(g, positions, x.shape[1])
    h = rms_norm(x, p["norm1"], cfg.rms_norm_eps, cfg.dtype)
    out, lat = _attend_prompt(g, p, h, cos, sin, valid.astype(jnp.int32))
    x, top_e, load = feed_forward(cfg, cfg.is_sparse(i), p, x + out, valid,
                                  dots3_note.moe, as_one_row=True)
    return x, lat, top_e, load


def prefill(cfg: JoyaiLlmFlashConfig, params, ids, lengths, cache_len: int):
    """``ids [B, S]`` right-padded prompts of ``lengths [B]`` (0 = a padding
    row), all rows together -> ``(cache, logits [B, V] float32 at each
    row's last token, aux)`` with ``aux = {"experts" [expert layers, B, S,
    k], "load" [expert layers, 4]}``.  With an MTP module the cache also
    carries ``hidden [B, S, H]``, the prompt's ``h_i``, for ``first_draft``
    to take out."""
    B, S = ids.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]
    pad_to = ((0, 0), (0, cache_len - S), (0, 0))
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], ids, axis=0)
    latent, experts, loads = [], [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope(f"layers_{i}"):
            x, lat, top_e, load = _prompt_layer(cfg, i, p, x, positions,
                                                valid)
        latent.append(jnp.pad(lat, pad_to))
        if top_e is not None:
            experts.append(expert_ids(top_e, cfg.n_routed_experts))
            loads.append(load)
    with jax.named_scope("lm_head"):
        hidden = _hidden(cfg, params["norm"], x)
        last = jnp.maximum(lengths - 1, 0)
        logits = _logits(params, jnp.take_along_axis(
            hidden, last[:, None, None], axis=1)[:, 0])
    cache = {"latent": latent, "lengths": lengths.astype(jnp.int32)}
    if cfg.num_nextn_predict_layers:
        cache["hidden"] = hidden
    return cache, logits, {"experts": jnp.stack(experts),
                           "load": jnp.stack(loads)}


def first_draft(cfg: JoyaiLlmFlashConfig, params, cache, ids, lengths,
                tokens, aux):
    """The MTP module over the prompt, after the prefill whose choice was
    ``tokens [B]``: position ``i`` reads ``(h_i, t_{i+1})``, the last one
    ``tokens``.  Returns the cache with the module's latents in place of
    ``hidden``, ``D [B, V]`` at each row's last position — the draft for the
    token after ``tokens`` — and ``aux`` with the module's block joined."""
    cache = dict(cache)
    hidden = cache.pop("hidden")
    B, S = ids.shape
    M = cache["latent"][0].shape[1]
    m = params["mtp"]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]
    last = jnp.maximum(lengths - 1, 0)
    with jax.named_scope("mtp"):
        after = jnp.where(positions == last[:, None], tokens[:, None],
                          jnp.roll(ids, -1, axis=1))
        x = _mtp_input(cfg, m, jnp.take(params["embed"], after, axis=0),
                       hidden)
        with jax.named_scope("block"):
            x, lat, top_e, load = _prompt_layer(
                cfg, cfg.num_hidden_layers, m["block"], x, positions, valid)
        with jax.named_scope("head"):
            x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            logits = _logits(params, _hidden(cfg, m["norm"], x_last))
    cache["draft"] = jnp.pad(lat, ((0, 0), (0, M - S), (0, 0)))
    return cache, logits, _join(
        aux, expert_ids(top_e, cfg.n_routed_experts), load)


# -- a step: one or two positions a row against the latent cache -------------------


def _seen(pos, M: int):
    """What the queries at ``pos [B, Q]`` see of ``M`` columns: those up to
    their own — a step's second position sees the first — and nothing past
    it, whatever an earlier, rejected position left there."""
    return jnp.arange(M)[None, None, :] <= pos[:, :, None]


def _cached_layer(cfg, i, p, x, lat, positions, valid):
    """One whole layer over ``x [B, Q, H]`` at columns ``positions [B]`` ..
    ``positions + Q``."""
    g = cfg.geometry
    M, Q = lat.shape[1], x.shape[1]
    pos = positions[:, None] + jnp.arange(Q, dtype=jnp.int32)  # [B, Q]
    cos, sin = tables(g, pos, M)
    seen = _seen(pos, M)
    h = rms_norm(x, p["norm1"], cfg.rms_norm_eps, cfg.dtype)
    out, lat = _attend_cached(g, p, h, cos, sin, lat, positions, seen)
    x, top_e, load = feed_forward(cfg, cfg.is_sparse(i), p, x + out, valid,
                                  dots3_note.moe)
    return x, lat, top_e, load


def verify(cfg: JoyaiLlmFlashConfig, params, cache, tokens, positions):
    """The layers on ``tokens [B, Q]`` at ``positions [B]`` .. ``positions
    + Q`` (``Q`` 2: the last committed token and the draft of the next;
    ``Q`` 1: a token at a time), all rows together.  Returns ``(cache,
    logits [B, Q, V], hidden [B, Q, H], aux)`` with ``aux["experts"]
    [expert layers, B, Q, k]``; every position's latent is written."""
    live = cache["lengths"] > 0
    valid = jnp.broadcast_to(live[:, None], tokens.shape)
    with jax.named_scope("verify"):
        with jax.named_scope("embed_tokens"):
            x = jnp.take(params["embed"], tokens, axis=0)
        latent, experts, loads = [], [], []
        for i, (p, lat) in enumerate(zip(params["layers"],
                                         cache["latent"])):
            with jax.named_scope(f"layers_{i}"):
                x, lat, top_e, load = _cached_layer(cfg, i, p, x, lat,
                                                    positions, valid)
            latent.append(lat)
            if top_e is not None:
                experts.append(expert_ids(top_e, cfg.n_routed_experts))
                loads.append(load)
        with jax.named_scope("lm_head"):
            hidden = _hidden(cfg, params["norm"], x)
            logits = _logits(params, hidden)
    return dict(cache, latent=latent), logits, hidden, {
        "experts": jnp.stack(experts), "load": jnp.stack(loads)}


def draft(cfg: JoyaiLlmFlashConfig, params, cache, hidden, chosen, positions,
          accepted, aux):
    """The MTP module after a ``verify`` of two positions whose choices were
    ``chosen [B, 2]``: ``[(h_p, chosen_0), (h_{p+1}, chosen_1)]``, the
    second routed nowhere where ``accepted [B]`` is false.  Returns the
    cache, ``D [B, V]`` at ``p + accepted`` — the draft for the token after
    the last one committed — and ``aux`` with the module's block joined."""
    live = cache["lengths"] > 0
    m = params["mtp"]
    valid = live[:, None] & jnp.stack(
        [jnp.ones_like(accepted), accepted], -1)
    with jax.named_scope("mtp"):
        x = _mtp_input(cfg, m, jnp.take(params["embed"], chosen, axis=0),
                       hidden)
        with jax.named_scope("block"):
            x, lat, top_e, load = _cached_layer(
                cfg, cfg.num_hidden_layers, m["block"], x, cache["draft"],
                positions, valid)
        with jax.named_scope("head"):
            x_last = jnp.where(accepted[:, None], x[:, 1], x[:, 0])
            logits = _logits(params, _hidden(cfg, m["norm"], x_last))
    return dict(cache, draft=lat), logits, \
        _join(aux, expert_ids(top_e, cfg.n_routed_experts), load)


def decode(cfg: JoyaiLlmFlashConfig, params, cache, tokens, positions):
    """A token at a time: ``tokens [B]`` at ``positions [B]`` -> ``(cache,
    logits [B, V], aux)`` with ``aux["experts"] [expert layers, B, k]``."""
    cache, logits, _, aux = verify(cfg, params, cache, tokens[:, None],
                                   positions)
    return cache, logits[:, 0], dict(aux, experts=aux["experts"][:, :, 0])


class CachedModel(CachedDecoder):
    """This decoder behind the interface ``models.generate.GreedyGenerator``
    decodes through.  A chat bucket's rows go through the layers together,
    one block a row under a flash call that is handed no lengths.
    ``drafts``: the checkpoint has an MTP module, and the generator then
    steps through ``first_draft`` / ``verify`` / ``draft`` and not
    ``decode``."""

    def __init__(self, config: JoyaiLlmFlashConfig) -> None:
        super().__init__(
            config, prefill, decode, cache_kinds=("latent", "draft"),
            drafter=(first_draft, verify, draft)
            if config.num_nextn_predict_layers else None)
