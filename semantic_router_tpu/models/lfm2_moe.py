"""The ``lfm2_moe`` decoder (LFM2-24B-A2B): three kinds of layer in one
stack, decoded a token at a time over ONE hybrid cache.

Layer equations (``chipbench/reference/lfm2_moe.py`` is the plain form):
``x <- x + Op(RMSNorm_op(x))``, ``x <- x + FF(RMSNorm_ffn(x))``, a final
RMSNorm (``embedding_norm``), a head tied to the embedding.

- ``Op`` by ``layer_types[i]``.  ``conv``: ``[B, C, u] = split3(W_in h)``,
  ``z = B * u``, ``c_t = w0 z_{t-2} + w1 z_{t-1} + w2 z_t`` (depthwise,
  causal, zeros before position 0), ``Op(h)_t = W_out (C_t * c_t)``.
  ``full_attention``: causal GQA with per-head RMSNorm on q and k, RoPE,
  no bias.
- ``FF``: a dense SwiGLU in the first ``num_dense_layers`` layers, else the
  sparse experts of ``experts.routed_experts`` behind the SIGMOID router:
  ``s = sigmoid(W_g h)``, the top k of ``s + b`` (``use_expert_bias``) are
  chosen, the weights are the UNBIASED ``s`` there, ``/ (sum + 1e-6)``
  (``norm_topk_prob``), ``* routed_scaling_factor``.

The cache a row carries from token to token is a pytree with two kinds of
state side by side: per attention layer K and V ``[rows, kv, D, M]`` (the
columns last: a head's 64 numbers are half a lane tile, and the layout the
chip gives ``[.., M, 64]`` where it may choose is this one; written down,
a loop that carries the cache keeps it, where a free choice inside the loop
padded every column to 128 and copied the cache in and out), per
conv layer the last ``conv_L_cache - 1`` vectors ``z`` ``[rows, L-1, H]``,
whatever the context; and ``lengths [rows]`` (0 = a padding row, which
routes nowhere).  A right-padded row's conv state is taken at its true
last token.  A decode token's K and V go to the column of its position.

Precision: parameters, K/V and conv state in ``cfg.dtype``; norms, RoPE,
softmax, the gate products of the convolution, the router's logits and
sigmoid and the head's logits in float32 (the published code computes the
router's logits in the model's dtype: more precise here, never less).

A prefill is bounded in tokens: rows are mapped INSIDE the program a GROUP
at a time (``models/mapped_prefill.py``: ``rows_per_group`` has the rule),
so the layers' temporaries exist for one group and an expert layer's
grouped matmuls read each touched expert once a group; decoding runs all
rows together.

Scopes: ``embed_tokens``, ``layers_<i>/conv`` (``in_proj``, ``conv1d``,
``out_proj``), ``layers_<i>/attn``, ``layers_<i>/mlp``, ``layers_<i>/moe``
(``router``, ``sort``, ``gmm``, ``combine``), ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import flash_attention
from .cached_model import CachedDecoder
from .checkpoints import (
    checkpoint_reader,
    on_device,
    swiglu_matrices,
    torch_dtype_of,
)
from .decoder_parts import NEG_INF, qkv, rms_norm
from .experts import (
    expert_ids,
    feed_forward,
    routed_experts,
    sigmoid_route,
)
from .mapped_prefill import prefill_group, prefill_in_groups

LAYER_TYPES = ("conv", "full_attention")
_SWIGLU = ("w1", "w3", "w2")  # the published names of gate, up, down


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    # (first, count) of the experts this chip holds; None = all of them
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def rms_norm_eps(self) -> float:  # the name the shared parts read
        return self.norm_eps

    @classmethod
    def from_hf(cls, hf: Mapping[str, Any], **overrides) -> "Lfm2MoeConfig":
        """From a checkpoint's ``config.json`` (``model_type: lfm2_moe``).
        What the architecture cannot express is refused, not ignored."""
        if hf.get("conv_bias", False):
            raise ValueError("lfm2_moe: conv_bias is not supported")
        rope = dict(hf.get("rope_parameters") or {})
        if rope.get("rope_type", "default") != "default" \
                or hf.get("rope_scaling"):
            raise ValueError("lfm2_moe: only the default RoPE is supported")
        types = tuple(hf.get("layer_types") or ())
        if len(types) != hf["num_hidden_layers"] \
                or set(types) - set(LAYER_TYPES):
            raise ValueError(
                f"lfm2_moe: layer_types must name one of {LAYER_TYPES} for "
                f"each of the {hf['num_hidden_layers']} layers, not {types}")
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in fields}
        kw["layer_types"] = types
        if kw.get("head_dim") is None:
            kw["head_dim"] = hf["hidden_size"] // hf["num_attention_heads"]
        kw["dtype"] = torch_dtype_of(hf.get("torch_dtype", "bfloat16"))
        kw["rope_theta"] = float(rope.get("rope_theta",
                                          hf.get("rope_theta", 1e6)))
        kw.update(overrides)
        return cls(**kw)

    def is_sparse(self, i: int) -> bool:
        return i >= self.num_dense_layers


# -- parameters ------------------------------------------------------------------


def params_from_checkpoint(path: str, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    with checkpoint_reader(path) as get:
        return params_from_state(get, cfg)


def params_from_state(get: Callable[[str], np.ndarray], cfg: Lfm2MoeConfig
                      ) -> Dict[str, Any]:
    """The published tensor names (``get(name)`` loads one) as this
    module's tree, in ``cfg.dtype`` on the default device; the router's
    selection bias stays float32.  Only the experts held are read."""
    dev = functools.partial(on_device, cfg)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        layer = {"norm1": dev(get(p + "operator_norm.weight")),
                 "norm2": dev(get(p + "ffn_norm.weight"))}
        if kind == "conv":
            layer.update(
                in_proj=dev(get(p + "conv.in_proj.weight"), True),
                # [H, 1, L] as a depthwise Conv1d stores it -> [L, H]
                conv_w=dev(np.asarray(get(p + "conv.conv.weight"))[:, 0].T),
                out_proj=dev(get(p + "conv.out_proj.weight"), True))
        else:
            a = p + "self_attn."
            layer.update(
                q_proj=dev(get(a + "q_proj.weight"), True),
                k_proj=dev(get(a + "k_proj.weight"), True),
                v_proj=dev(get(a + "v_proj.weight"), True),
                o_proj=dev(get(a + "out_proj.weight"), True),
                q_norm=dev(get(a + "q_layernorm.weight")),
                k_norm=dev(get(a + "k_layernorm.weight")))
        f = p + "feed_forward."
        if cfg.is_sparse(i):
            layer.update(
                router=dev(get(f + "gate.weight"), True),
                **swiglu_matrices(get, cfg, f + "experts.", _SWIGLU,
                                  experts=cfg.held))
            if cfg.use_expert_bias:
                layer["expert_bias"] = jnp.asarray(
                    np.asarray(get(f + "expert_bias"), np.float32))
        else:
            layer.update(swiglu_matrices(get, cfg, f, _SWIGLU))
        layers.append(layer)
    params = {"embed": dev(get("model.embed_tokens.weight")),
              "layers": layers,
              "norm": dev(get("model.embedding_norm.weight"))}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dev(get("lm_head.weight"))
    return params


# -- layers ----------------------------------------------------------------------


def route(cfg: Lfm2MoeConfig, p, x):
    """The sigmoid router on ``x [T, H]``."""
    return sigmoid_route(
        x, p["router"], p["expert_bias"] if cfg.use_expert_bias else None,
        cfg.num_experts_per_tok, cfg.norm_topk_prob,
        cfg.routed_scaling_factor, 1e-6)


def moe(cfg: Lfm2MoeConfig, p, x, valid):
    """``x [T, H]`` through the sigmoid router and the shared expert
    layer.  Returns ``(y [T, H], top_e [T, k], load [4])``."""
    with jax.named_scope("router"):
        top_e, w = route(cfg, p, x)
    y, load = routed_experts(p, x, valid, top_e, w, cfg.held, cfg.dtype)
    return y, top_e, load


def _gates(cfg, p, h):
    """``h [..., H]`` -> the conv operator's ``z = B * u`` (in the model's
    dtype: what the state holds) and its output gate ``C`` (float32)."""
    with jax.named_scope("in_proj"):
        b, c, u = jnp.split((h @ p["in_proj"]).astype(jnp.float32), 3, -1)
        return (b * u).astype(cfg.dtype), c


def _taps(p, z_window):
    """``sum_k w_k * z_window[k]`` over the leading axis, float32."""
    w = p["conv_w"].astype(jnp.float32)
    return sum(w[k] * z_window[k].astype(jnp.float32)
               for k in range(w.shape[0]))


def _head(cfg, params, x):
    """``x [B, H]`` -> logits ``[B, V]`` float32."""
    with jax.named_scope("lm_head"):
        h = rms_norm(x, params["norm"], cfg.norm_eps, cfg.dtype)
        w = params["embed"] if cfg.tie_word_embeddings else params["lm_head"]
        return jnp.einsum("bh,vh->bv", h, w,
                          preferred_element_type=jnp.float32)


# -- prefill ---------------------------------------------------------------------


def _prefill_rows(cfg: Lfm2MoeConfig, params, ids, lengths, cache_len: int):
    """``ids [B, S]`` right-padded, ``lengths [B]`` -> ``(kv, conv, logits
    [B, V], experts [layers, B, S, k], load [layers, 4])``: the whole
    prompt under the causal mask, all rows of ``ids`` at once."""
    B, S = ids.shape
    nkv = cfg.num_key_value_heads
    rep = cfg.num_attention_heads // nkv
    keep = cfg.conv_L_cache - 1
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]
    last = jnp.maximum(lengths - 1, 0)
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], ids, axis=0)
    kv, conv, experts, loads = [], [], [], []
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        with jax.named_scope(f"layers_{i}"):
            h = rms_norm(x, p["norm1"], cfg.norm_eps, cfg.dtype)
            if kind == "conv":
                with jax.named_scope("conv"):
                    z, c = _gates(cfg, p, h)
                    with jax.named_scope("conv1d"):
                        zp = jnp.pad(z, ((0, 0), (keep, 0), (0, 0)))
                        y = c * _taps(p, [zp[:, k:k + S]
                                          for k in range(keep + 1)])
                        # the state at each row's TRUE last token: z at
                        # len - keep .. len - 1 (zeros before position 0)
                        at = last[:, None] + jnp.arange(1, keep + 1)
                        conv.append(jnp.take_along_axis(
                            zp, at[:, :, None], axis=1)
                            * (lengths > 0)[:, None, None].astype(z.dtype))
                    with jax.named_scope("out_proj"):
                        x = x + y.astype(cfg.dtype) @ p["out_proj"]
            else:
                with jax.named_scope("attn"):
                    q, k, v = qkv(cfg, p, h, positions, S)
                    kc, vc = (jnp.moveaxis(t, 2, 1) for t in (k, v))
                    out = flash_attention(
                        jnp.moveaxis(q, 2, 1), jnp.repeat(kc, rep, axis=1),
                        jnp.repeat(vc, rep, axis=1),
                        key_padding_mask=valid.astype(jnp.int32),
                        causal=True, lengths=lengths)
                    out = jnp.moveaxis(out, 1, 2).reshape(B, S, -1)
                    x = x + out.astype(cfg.dtype) @ p["o_proj"]
                    pad = ((0, 0), (0, 0), (0, 0), (0, cache_len - S))
                    kv.append(tuple(jnp.pad(jnp.swapaxes(t, 2, 3), pad)
                                    for t in (kc, vc)))
            x, top_e, load = feed_forward(cfg, cfg.is_sparse(i), p, x, valid,
                                          moe, as_one_row=True)
            if top_e is not None:
                experts.append(expert_ids(top_e, cfg.num_experts))
                loads.append(load)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return (kv, conv, _head(cfg, params, x_last), jnp.stack(experts),
            jnp.stack(loads))


def _row_bytes(cfg: Lfm2MoeConfig, S: int) -> int:
    """A prefill row's temporaries, reckoned from above: every array of
    ``S * k`` rows an expert layer writes (the sorted rows, gate+up, the
    activation, down, the rows gathered back: ``3 (H + I)`` a pair) as if
    all were live at once, beside four ``[S, H]`` of residual stream.  At
    the guard's widths (S 8192, k 4, H 2048, I 1536, bfloat16) 0.84 GB;
    the compiler's count for a described v5e is 0.75 GB a row at 1, 2, 4
    and 8 rows a group (the dense MLP's ``3 W`` a token is 0.58)."""
    H, I = cfg.hidden_size, cfg.moe_intermediate_size
    return S * jnp.dtype(cfg.dtype).itemsize \
        * (3 * cfg.num_experts_per_tok * (H + I) + 4 * H)


def _cache_bytes(cfg: Lfm2MoeConfig, rows: int, cache_len: int) -> int:
    """The bytes of the cache a prefill of ``rows`` returns."""
    item = jnp.dtype(cfg.dtype).itemsize
    attn = sum(k == "full_attention" for k in cfg.layer_types)
    kv = 2 * cfg.num_key_value_heads * cache_len * cfg.head_dim
    conv = (cfg.conv_L_cache - 1) * cfg.hidden_size
    return rows * item * (attn * kv + (len(cfg.layer_types) - attn) * conv)


def _prefill_groups(cfg: Lfm2MoeConfig, params, ids, lengths, cache_len: int,
                    group: int):
    """``prefill`` at ``group`` rows a call of ``_prefill_rows``."""
    return prefill_in_groups(
        lambda ids, lengths: _prefill_rows(cfg, params, ids, lengths,
                                           cache_len),
        ("kv", "conv", "logits", "experts", "load"), ("experts",), group,
        ids, lengths)


def prefill(cfg: Lfm2MoeConfig, params, ids, lengths, cache_len: int):
    """``ids [B, S]`` right-padded prompts of ``lengths [B]`` (0 = a padding
    row) -> ``(cache, logits [B, V] float32 at each row's last token, aux)``
    with ``aux = {"experts" [expert layers, B, S, k], "load" [expert
    layers, 4]}``.  ``mapped_prefill.prefill_group`` rows at a time inside
    the program, so a bucket's temporaries are those of ONE group whatever
    the batch, and an expert layer's grouped matmuls serve a group's tokens
    together."""
    return _prefill_groups(
        cfg, params, ids, lengths, cache_len,
        prefill_group(cfg, params, *ids.shape, cache_len, _row_bytes,
                      _cache_bytes))


# -- decode: one token a row against the hybrid cache ----------------------------


def decode(cfg: Lfm2MoeConfig, params, cache, tokens, positions):
    """``tokens [B]`` at ``positions [B]`` (a row's count of tokens before
    this one), all rows together.  Returns ``(cache, logits [B, V], aux)``
    with ``aux["experts"] [expert layers, B, k]``; the cache comes back
    with this token's K and V at column ``positions`` of every attention
    layer and with every conv layer's state moved on by one."""
    B = tokens.shape[0]
    nh, nkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    rep = nh // nkv
    live = cache["lengths"] > 0
    pos = positions[:, None]
    put = jax.vmap(lambda c, new, at: jax.lax.dynamic_update_slice(
        c, new, (0, 0, at)))  # a row's column of [kv, D, M]
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], tokens, axis=0)[:, None]  # [B, 1, H]
    kv, conv, experts, loads = [], [], [], []
    kv_in, conv_in = iter(cache["kv"]), iter(cache["conv"])
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        with jax.named_scope(f"layers_{i}"):
            h = rms_norm(x, p["norm1"], cfg.norm_eps, cfg.dtype)
            if kind == "conv":
                with jax.named_scope("conv"):
                    state = next(conv_in)  # [B, L-1, H]
                    z, c = _gates(cfg, p, h)
                    with jax.named_scope("conv1d"):
                        window = jnp.concatenate([state, z], axis=1)
                        y = c[:, 0] * _taps(p, jnp.moveaxis(window, 1, 0))
                        conv.append(window[:, 1:])
                    with jax.named_scope("out_proj"):
                        x = x + (y.astype(cfg.dtype) @ p["out_proj"])[:, None]
            else:
                with jax.named_scope("attn"):
                    k_cache, v_cache = next(kv_in)
                    M = k_cache.shape[3]
                    q, k, v = qkv(cfg, p, h, pos, M)  # k, v [B, 1, kv, D]
                    k_cache = put(k_cache, jnp.moveaxis(k, 1, 3), positions)
                    v_cache = put(v_cache, jnp.moveaxis(v, 1, 3), positions)
                    kv.append((k_cache, v_cache))
                    qg = q.reshape(B, nkv, rep, D)
                    s = jnp.einsum("bgrd,bgdm->bgrm", qg, k_cache,
                                   preferred_element_type=jnp.float32) \
                        * (1.0 / np.sqrt(float(D)))
                    seen = jnp.arange(M)[None, :] <= pos  # [B, M]
                    s = s + jnp.where(seen, 0.0, NEG_INF)[:, None, None, :]
                    out = jnp.einsum(
                        "bgrm,bgdm->bgrd",
                        jax.nn.softmax(s, axis=-1).astype(cfg.dtype),
                        v_cache, preferred_element_type=jnp.float32)
                    x = x + (out.reshape(B, nh * D).astype(cfg.dtype)
                             @ p["o_proj"])[:, None]
            x, top_e, load = feed_forward(cfg, cfg.is_sparse(i), p, x,
                                          live[:, None], moe)
            if top_e is not None:
                experts.append(expert_ids(top_e[:, 0], cfg.num_experts))
                loads.append(load)
    cache = {"kv": kv, "conv": conv, "lengths": cache["lengths"]}
    return cache, _head(cfg, params, x[:, 0]), {
        "experts": jnp.stack(experts), "load": jnp.stack(loads)}


class CachedModel(CachedDecoder):
    """This decoder behind the interface ``models.generate.GreedyGenerator``
    decodes through; its prefill's flash calls are its attention layers',
    all their heads over the whole prompt."""

    def __init__(self, config: Lfm2MoeConfig) -> None:
        super().__init__(
            config, prefill, decode, cache_kinds=("kv", "conv"),
            group_sizes=(_row_bytes, _cache_bytes),
            attn_layers=[(config.num_attention_heads, 0)
                         for kind in config.layer_types if kind != "conv"])
