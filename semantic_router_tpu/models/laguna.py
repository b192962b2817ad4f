"""The ``laguna`` decoder (Laguna-S-2.1): grouped-query attention in TWO
geometries over plain keys and values — full layers of one head count over
a whole cache, sliding layers of another over a ring of ``sliding_window``
positions — each head's output under a sigmoid gate, and sparse experts
behind a softmax router beside a gated shared expert, decoded a token at a
time.

Layer equations (``chipbench/reference/laguna.py`` is the plain form and
lists what is assumed of the published model): ``x <- x + Attn_t(RMSNorm(x))``,
``x <- x + FF_i(RMSNorm(x))``, a final RMSNorm, an untied head.

- ``Attn_t`` by ``layer_types[i]``: ``H_t`` query heads
  (``num_attention_heads_per_layer[i]``) over ``num_key_value_heads`` k/v
  heads of ``head_dim``, no bias; q and k through a per-head RMSNorm; RoPE
  (``rotate_half`` pairing) with the TYPE's own parameters
  (``rope_parameters[t]``: theta, ``yarn`` or ``default``, and
  ``partial_rotary_factor`` — only the first ``factor * head_dim`` dims of a
  head rotate); softmax in float32 at scale ``1 / sqrt(head_dim)`` over
  ``S_t = {s <= t}`` (``full_attention``) or ``{s : 0 <= t - s <
  sliding_window}`` (``sliding_attention``: the token itself among its
  ``sliding_window`` keys); one sigmoid gate a head from the layer's normed
  input on the head's output; ``W_o``.
- ``FF_i`` by ``mlp_layer_types[i]``: ``dense`` a SwiGLU of
  ``intermediate_size``; ``sparse`` ``experts.routed_experts`` behind
  ``experts.softmax_route`` (softmax over all experts, the top k, over
  their sum, times ``moe_routed_scaling_factor``, on the experts' outputs)
  plus ``sigmoid(w_s . h) * SwiGLU_shared(h)``, which every chip computes
  whole.

The cache a row carries, two kinds side by side: per full layer K and V
``[rows, kv, M, D]`` (every position a column); per sliding layer K and V
``[rows, kv, sliding_window, D]``, a ring (``models/gated_window.py`` has its
arithmetic and the gate, shared with ``models/dots3_note.py``); ``lengths
[rows]`` (0 = a padding row).  A head's 128 numbers are a whole lane tile,
so the columns stand before them (``lfm2_moe``'s 64 stand after).

A chip's share: ``experts_held`` / ``vocab_held = (first, count)`` as in
``dots3_note``.

Precision: parameters and cache in ``cfg.dtype``; norms, RoPE, softmax, the
gates, the router and the head's logits in float32.  A prefill maps its
rows INSIDE the program, a group at a time (``models/mapped_prefill.py``);
its cores are the flash kernel, k and v repeated to the layer's query heads
as ``lfm2_moe`` repeats them.

Scopes: ``embed_tokens``; ``layers_<i>/attn_full`` and
``layers_<i>/attn_window`` (``qkv``, ``core``, ``gate_out``);
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
from ..ops.rope import RopeSpec, apply_rotary_front
from .cached_model import CachedDecoder
from .checkpoints import (
    checkpoint_reader,
    on_device,
    swiglu_matrices,
    tensor_rows,
    torch_dtype_of,
)
from .decoder_parts import NEG_INF, head, rms_norm
from .experts import (
    expert_ids,
    feed_forward,
    routed_experts,
    softmax_route,
    swiglu,
)
from .gated_window import gate_out, ring_of, ring_seen, ring_slot
from .mapped_prefill import prefill_group, prefill_in_groups

LAYER_TYPES = ("full_attention", "sliding_attention")
MLP_TYPES = ("dense", "sparse")
ROPE_TYPES = ("default", "yarn")
_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
              "beta_slow", "attention_factor", "mscale", "mscale_all_dim",
              "truncate")


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer type's RoPE: the dims of a head that rotate (its first
    ``rotary_dim``), and YaRN's numbers if it has them."""
    theta: float
    rotary_dim: int
    yarn: Optional[Tuple[Tuple[str, Any], ...]] = None

    def tables(self, table_len: int):
        """cos and sin ``[table_len, rotary_dim]``, YaRN's attention factor
        on both."""
        return RopeSpec(self.rotary_dim, self.theta,
                        dict(self.yarn) if self.yarn else None
                        ).tables(table_len)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    num_hidden_layers: int = 48
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope: Tuple[Rope, Rope] = ()  # by LAYER_TYPES
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    num_experts: int = 256
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    dtype: Any = jnp.bfloat16
    # (first, count) of the experts / vocabulary rows this chip holds;
    # None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def vocab(self) -> Tuple[int, int]:
        return self.vocab_held or (0, self.vocab_size)

    def is_sparse(self, i: int) -> bool:
        return self.mlp_layer_types[i] == "sparse"

    def heads(self, kind: str) -> int:
        """The query heads of a layer of ``kind`` (one count a kind:
        ``from_hf`` refuses a list that gives a kind two)."""
        return self.num_attention_heads_per_layer[
            self.layer_types.index(kind)]

    def rope_of(self, kind: str) -> Rope:
        return self.rope[LAYER_TYPES.index(kind)]

    @classmethod
    def from_hf(cls, hf: Mapping[str, Any], **overrides) -> "LagunaConfig":
        """From a checkpoint's ``config.json`` (``model_type: laguna``).
        What the architecture cannot express is refused, not ignored."""
        def refuse(what: str) -> None:
            raise ValueError(f"laguna: {what}")

        n = hf["num_hidden_layers"]
        if hf.get("moe_router_logit_softcapping", 0):
            refuse("moe_router_logit_softcapping other than 0 is not "
                   "supported")
        if hf.get("moe_apply_router_weight_on_input", False):
            refuse("moe_apply_router_weight_on_input is not supported (the "
                   "weights go on the experts' outputs)")
        if hf.get("gating", "per-head") != "per-head":
            refuse(f"gating {hf['gating']!r}: only 'per-head' is supported")
        if hf.get("decoder_sparse_step", 1) != 1:
            refuse("decoder_sparse_step must be 1")
        if hf.get("attention_bias", False):
            refuse("attention_bias is not supported")
        if hf.get("tie_word_embeddings", False):
            refuse("a tied head is not supported")
        if hf.get("hidden_act", "silu") != "silu":
            refuse(f"hidden_act {hf['hidden_act']!r}: silu only")
        types = tuple(hf.get("layer_types") or ())
        if len(types) != n or set(types) - set(LAYER_TYPES):
            refuse(f"layer_types must name one of {LAYER_TYPES} for each of "
                   f"the {n} layers, not {types}")
        only = hf.get("mlp_only_layers")
        mlp = tuple(hf.get("mlp_layer_types")
                    or ("dense" if i in (only or ()) else "sparse"
                        for i in range(n)))
        if len(mlp) != n or set(mlp) - set(MLP_TYPES) or (
                only is not None and set(only) != {
                    i for i, k in enumerate(mlp) if k == "dense"}):
            refuse(f"mlp_layer_types must name one of {MLP_TYPES} for each "
                   f"of the {n} layers and be dense exactly at "
                   f"mlp_only_layers {only}, not {mlp}")
        gates = tuple(hf.get("gating_types") or ("per_head",) * n)
        if len(gates) != n or set(gates) != {"per_head"}:
            refuse(f"gating_types must be 'per_head' for each of the {n} "
                   f"layers, not {gates}")
        nkv = hf["num_key_value_heads"]
        heads = tuple(hf.get("num_attention_heads_per_layer")
                      or (hf["num_attention_heads"],) * n)
        by_kind = {k: {h for h, t in zip(heads, types) if t == k}
                   for k in LAYER_TYPES}
        if len(heads) != n or any(h % nkv for h in heads) \
                or any(len(v) > 1 for v in by_kind.values()):
            refuse(f"num_attention_heads_per_layer must give each of the "
                   f"{n} layers a multiple of num_key_value_heads {nkv}, "
                   f"one count a layer type, not {heads}")
        if not all(by_kind.values()) or "sparse" not in mlp:
            refuse("a stack needs a full_attention layer, a "
                   "sliding_attention layer and a sparse layer (the loop "
                   "carries both kinds of cache and reports the experts)")
        D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
        params = hf.get("rope_parameters") or {}
        rope = []
        for kind in LAYER_TYPES:
            r = dict(params.get(kind) or {})
            if r.get("rope_type", "default") not in ROPE_TYPES:
                refuse(f"rope_type {r['rope_type']!r} of {kind}: one of "
                       f"{ROPE_TYPES}")
            rotary = int(D * float(r.get("partial_rotary_factor", 1)))
            if rotary < 2 or rotary % 2 or rotary > D:
                refuse(f"partial_rotary_factor of {kind} leaves {rotary} "
                       f"rotary dims of {D}")
            yarn = tuple(sorted((k, r[k]) for k in _YARN_KEYS if k in r)) \
                if r.get("rope_type") == "yarn" else None
            rope.append(Rope(float(r.get("rope_theta", 10000.0)), rotary,
                             yarn))
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in fields}
        kw.update(layer_types=types, mlp_layer_types=mlp,
                  num_attention_heads_per_layer=heads, head_dim=D,
                  rope=tuple(rope),
                  dtype=torch_dtype_of(hf.get("torch_dtype", "bfloat16")))
        kw.update(overrides)
        return cls(**kw)


# -- parameters ------------------------------------------------------------------


def params_from_checkpoint(path: str, cfg: LagunaConfig) -> Dict[str, Any]:
    with checkpoint_reader(path) as get:
        return params_from_state(get, cfg)


def params_from_state(get: Callable[[str], np.ndarray], cfg: LagunaConfig
                      ) -> Dict[str, Any]:
    """The tensor names (``get(name)`` loads one; Qwen3-Next's, with
    ``self_attn.g_proj`` the gate: ``chipbench/reference/laguna.py`` lists
    them) as this module's tree, in ``cfg.dtype`` on the default device.
    Only the experts held and the vocabulary rows held are read."""
    dev = functools.partial(on_device, cfg)
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        layer = {"norm1": dev(get(p + "input_layernorm.weight")),
                 "norm2": dev(get(p + "post_attention_layernorm.weight")),
                 "q_norm": dev(get(a + "q_norm.weight")),
                 "k_norm": dev(get(a + "k_norm.weight")),
                 "gate_proj": dev(get(a + "g_proj.weight"), True)}
        for k in ("q", "k", "v", "o"):
            layer[k + "_proj"] = dev(get(f"{a}{k}_proj.weight"), True)
        f = p + "mlp."
        if cfg.is_sparse(i):
            layer.update(
                router=dev(get(f + "gate.weight"), True),
                **swiglu_matrices(get, cfg, f + "experts.",
                                  experts=cfg.held),
                shared=swiglu_matrices(get, cfg, f + "shared_expert."),
                shared_gate=dev(get(f + "shared_expert_gate.weight"), True))
        else:
            layer.update(swiglu_matrices(get, cfg, f))
        layers.append(layer)
    v_first, v_count = cfg.vocab
    return {"embed": dev(tensor_rows(get, "model.embed_tokens.weight",
                                     v_first, v_count)),
            "layers": layers,
            "norm": dev(get("model.norm.weight")),
            "lm_head": dev(tensor_rows(get, "lm_head.weight", v_first,
                                       v_count))}


# -- what prefill and decode share -----------------------------------------------


def qkv(cfg: LagunaConfig, kind: str, p, h, positions, table_len: int):
    """``h [B, S, H]`` -> q ``[B, S, H_t, D]``, k and v ``[B, S, kv, D]``,
    q and k normalised per head and rotated, by the layer type's RoPE, at
    ``positions [B, S]``, all below ``table_len``."""
    B, S, _ = h.shape
    nkv, D = cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("qkv"):
        q = (h @ p["q_proj"]).reshape(B, S, cfg.heads(kind), D)
        k = (h @ p["k_proj"]).reshape(B, S, nkv, D)
        v = (h @ p["v_proj"]).reshape(B, S, nkv, D)
        q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps, cfg.dtype)
        k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps, cfg.dtype)
        cos_t, sin_t = cfg.rope_of(kind).tables(table_len)
        cos = jnp.take(cos_t, positions, axis=0)[:, :, None, :]
        sin = jnp.take(sin_t, positions, axis=0)[:, :, None, :]
        q, k = apply_rotary_front(q, k, cos, sin)  # float32 inside
        return q, k, v


def route(cfg: LagunaConfig, p, x):
    """``(top_e [T, k], weights [T, k] float32)`` of ``x [T, H]``."""
    top_e, w = softmax_route(x, p["router"], cfg.num_experts_per_tok,
                             cfg.norm_topk_prob)
    return top_e, w * cfg.moe_routed_scaling_factor


def shared_expert(cfg: LagunaConfig, p, x):
    """``sigmoid(w_s . x) * SwiGLU_shared(x)`` of ``x [T, H]``."""
    gate = jax.nn.sigmoid(jnp.dot(x, p["shared_gate"],
                                  preferred_element_type=jnp.float32))
    return (gate * swiglu(cfg, p["shared"], x).astype(jnp.float32)
            ).astype(cfg.dtype)


def moe(cfg: LagunaConfig, p, x, valid):
    """``x [T, H]`` through the router, the experts held here and the
    gated shared expert.  Returns ``(y [T, H], top_e [T, k], load [4])``."""
    with jax.named_scope("router"):
        top_e, w = route(cfg, p, x)
    y, load = routed_experts(p, x, valid, top_e, w, cfg.held, cfg.dtype)
    with jax.named_scope("shared"):
        y = y + shared_expert(cfg, p, x)
    return y, top_e, load


def _scope_of(kind: str) -> str:
    return "attn_full" if kind == "full_attention" else "attn_window"


# -- prefill ---------------------------------------------------------------------


def _prefill_rows(cfg: LagunaConfig, params, ids, lengths, cache_len: int):
    """``ids [B, S]`` right-padded, ``lengths [B]`` -> ``(full, window,
    logits [B, V], experts [layers, B, S, k], load [layers, 4])``: the
    whole prompt under the causal mask, all rows of ``ids`` at once."""
    B, S = ids.shape
    W = cfg.sliding_window
    nkv = cfg.num_key_value_heads
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]
    mask = valid.astype(jnp.int32)
    last = jnp.maximum(lengths - 1, 0)
    pad = ((0, 0), (0, 0), (0, cache_len - S), (0, 0))
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], ids, axis=0)
    full, window, experts, loads = [], [], [], []
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        whole = kind == "full_attention"
        rep = cfg.heads(kind) // nkv
        with jax.named_scope(f"layers_{i}"):
            h = rms_norm(x, p["norm1"], cfg.rms_norm_eps, cfg.dtype)
            with jax.named_scope(_scope_of(kind)):
                q, k, v = qkv(cfg, kind, p, h, positions, S)
                with jax.named_scope("core"):
                    kc, vc = (jnp.moveaxis(t, 2, 1) for t in (k, v))
                    out = flash_attention(
                        jnp.moveaxis(q, 2, 1), jnp.repeat(kc, rep, axis=1),
                        jnp.repeat(vc, rep, axis=1), key_padding_mask=mask,
                        causal=True, window=0 if whole else 2 * (W - 1),
                        lengths=lengths)
                x = x + gate_out(p, h, out, cfg.dtype)
                if whole:
                    full.append((jnp.pad(kc, pad), jnp.pad(vc, pad)))
                else:
                    window.append(tuple(
                        jnp.moveaxis(ring_of(t, lengths, W), 1, 2)
                        for t in (k, v)))
            x, top_e, load = feed_forward(cfg, cfg.is_sparse(i), p, x, valid,
                                          moe, as_one_row=True)
            if top_e is not None:
                experts.append(expert_ids(top_e, cfg.num_experts))
                loads.append(load)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return (full, window, head(cfg, params, x_last), jnp.stack(experts),
            jnp.stack(loads))


def _row_bytes(cfg: LagunaConfig, S: int) -> int:
    """A prefill row's temporaries, reckoned from above: a sliding layer's
    arrays a head (q, the core's output, k and v repeated to the query
    heads) and every array of ``S * k`` rows the expert layer writes (``3
    (H + I)`` a pair) as if live beside them.  At the guard's widths (S
    8192, 72 heads of 128, k 10, H 3072, I 1024, bfloat16) 0.60 + 2.01 =
    2.6 GB."""
    item = jnp.dtype(cfg.dtype).itemsize
    heads = 4 * max(cfg.num_attention_heads_per_layer) * S * cfg.head_dim \
        * item
    experts = 3 * S * cfg.num_experts_per_tok * item \
        * (cfg.hidden_size + cfg.moe_intermediate_size)
    return heads + experts


def _cache_bytes(cfg: LagunaConfig, rows: int, cache_len: int) -> int:
    """The bytes of the cache a prefill of ``rows`` returns."""
    n_full = sum(k == "full_attention" for k in cfg.layer_types)
    column = 2 * cfg.num_key_value_heads * cfg.head_dim
    return rows * jnp.dtype(cfg.dtype).itemsize * column * (
        n_full * cache_len
        + (len(cfg.layer_types) - n_full) * cfg.sliding_window)


def _prefill_groups(cfg: LagunaConfig, params, ids, lengths, cache_len: int,
                    group: int):
    """``prefill`` at ``group`` rows a call of ``_prefill_rows``."""
    return prefill_in_groups(
        lambda ids, lengths: _prefill_rows(cfg, params, ids, lengths,
                                           cache_len),
        ("full", "window", "logits", "experts", "load"), ("experts",), group,
        ids, lengths)


def prefill(cfg: LagunaConfig, params, ids, lengths, cache_len: int):
    """``ids [B, S]`` right-padded prompts of ``lengths [B]`` (0 = a padding
    row) -> ``(cache, logits [B, V] float32 at each row's last token, aux)``
    with ``aux = {"experts" [sparse layers, B, S, k], "load" [sparse
    layers, 4]}``.  ``mapped_prefill.prefill_group`` rows at a time inside
    the program, so a bucket's temporaries are those of ONE group whatever
    the batch (on a v5e one at the long bucket, every row at the short
    one)."""
    return _prefill_groups(
        cfg, params, ids, lengths, cache_len,
        prefill_group(cfg, params, *ids.shape, cache_len, _row_bytes,
                      _cache_bytes))


# -- decode: one token a row against both kinds of cache -------------------------


def decode(cfg: LagunaConfig, params, cache, tokens, positions):
    """``tokens [B]`` at ``positions [B]`` (a row's count of tokens before
    this one), all rows together.  Returns ``(cache, logits [B, V], aux)``
    with ``aux["experts"] [sparse layers, B, k]``; the cache comes back
    with this token's K and V at column ``positions`` of every full layer
    and in slot ``positions mod sliding_window`` of every sliding one."""
    B = tokens.shape[0]
    W, nkv, D = cfg.sliding_window, cfg.num_key_value_heads, cfg.head_dim
    live = cache["lengths"] > 0
    pos = positions[:, None]
    M = cache["full"][0][0].shape[2]  # every position has a column there
    put = jax.vmap(lambda c, new, at: jax.lax.dynamic_update_slice(
        c, new, (0, at, 0)))  # a row's column of [kv, M, D]
    scale = 1.0 / np.sqrt(float(D))
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], tokens, axis=0)[:, None]  # [B, 1, H]
    full, window, experts, loads = [], [], [], []
    full_in, window_in = iter(cache["full"]), iter(cache["window"])
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        whole = kind == "full_attention"
        nh = cfg.heads(kind)
        with jax.named_scope(f"layers_{i}"):
            h = rms_norm(x, p["norm1"], cfg.rms_norm_eps, cfg.dtype)
            with jax.named_scope(_scope_of(kind)):
                k_cache, v_cache = next(full_in if whole else window_in)
                q, k, v = qkv(cfg, kind, p, h, pos, M)  # k, v [B, 1, kv, D]
                with jax.named_scope("core"):
                    at = positions if whole else ring_slot(positions, W)
                    k_cache = put(k_cache, jnp.moveaxis(k, 1, 2), at)
                    v_cache = put(v_cache, jnp.moveaxis(v, 1, 2), at)
                    seen = jnp.arange(M)[None, :] <= pos if whole \
                        else ring_seen(positions, W)
                    s = jnp.einsum("bgrd,bgmd->bgrm",
                                   q.reshape(B, nkv, nh // nkv, D), k_cache,
                                   preferred_element_type=jnp.float32) \
                        * scale
                    s = s + jnp.where(seen, 0.0, NEG_INF)[:, None, None, :]
                    out = jnp.einsum(
                        "bgrm,bgmd->bgrd",
                        jax.nn.softmax(s, axis=-1).astype(cfg.dtype),
                        v_cache, preferred_element_type=jnp.float32)
                (full if whole else window).append((k_cache, v_cache))
                x = x + gate_out(p, h, out.reshape(B, nh, 1, D), cfg.dtype)
            x, top_e, load = feed_forward(cfg, cfg.is_sparse(i), p, x,
                                          live[:, None], moe)
            if top_e is not None:
                experts.append(expert_ids(top_e[:, 0], cfg.num_experts))
                loads.append(load)
    cache = {"full": full, "window": window, "lengths": cache["lengths"]}
    return cache, head(cfg, params, x[:, 0]), {
        "experts": jnp.stack(experts), "load": jnp.stack(loads)}


class CachedModel(CachedDecoder):
    """This decoder behind the interface ``models.generate.GreedyGenerator``
    decodes through; its cache by kind of state is the full layers' whole K
    and V and the sliding layers' rings, its prefill's flash calls its
    layers' of both kinds, all their heads."""

    def __init__(self, config: LagunaConfig) -> None:
        super().__init__(
            config, prefill, decode, cache_kinds=("full", "window"),
            group_sizes=(_row_bytes, _cache_bytes),
            attn_layers=[
                (config.heads(kind), 0 if kind == "full_attention"
                 else 2 * (config.sliding_window - 1))
                for kind in config.layer_types])
