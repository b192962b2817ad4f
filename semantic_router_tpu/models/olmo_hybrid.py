"""The ``olmo_hybrid`` decoder (Olmo-Hybrid-7B): dense, three gated-delta-rule
linear-attention layers to one full-attention layer, decoded a token at a
time over ONE cache with three kinds of state.

Layer equations (``chipbench/reference/olmo_hybrid.py`` is the plain form,
with the readings that are ASSUMED marked): the norm is on a sub-layer's
OUTPUT, ``x <- x + RMSNorm_a(Mix(x))``, ``x <- x + RMSNorm_f(FF(x))``, no
input norm; a final RMSNorm and an untied head.  ``FF`` is a SwiGLU.

- ``full_attention``: ``q = RMSNorm_q(W_q h)``, ``k = RMSNorm_k(W_k h)``
  over the WHOLE projected vector before the split into heads, ``v = W_v
  h``; causal softmax attention, as many k/v heads as query heads; RoPE
  only where ``rope_theta`` is a number (the published config says null:
  none); no bias.
- ``linear_attention``: ``q~, k~, v~ = W_q h, W_k h, W_v h``, each through
  its own depthwise causal convolution of ``linear_conv_kernel_dim`` taps
  and ``silu``; ``q <- q / |q| * d_k^-0.5``, ``k <- k / |k|`` per head;
  ``beta = sigmoid(W_b h)`` (doubled under ``linear_allow_neg_eigval``),
  ``g = -exp(A_log) * softplus(W_a h + dt_bias)``; the gated delta rule
  (``ops/gated_delta_rule.py``) over a state ``S [d_k, d_v]`` a head;
  ``Mix(h) = W_o concat_heads(RMSNorm_o(o) * silu(W_g h))``, the norm per
  head with one weight vector of ``d_v``.

The cache a row carries from token to token: ``full``: per full layer K
and V ``[rows, heads, M, 128]`` in the model's dtype (a head's 128 numbers
are one lane tile, so the columns need not go last as ``lfm2_moe``'s 64
do; a decode token's K and V go to row ``positions`` of ``M``); ``state``:
per linear layer ``S [rows, heads, d_k, d_v]`` FLOAT32 whatever the
context; ``conv``: per linear layer the last ``taps - 1`` inputs of the
three convolutions side by side, ``[rows, taps - 1, 2 heads d_k + heads
d_v]`` in the model's dtype; and ``lengths [rows]`` (0 = a padding row).  A
right-padded row's ``state`` and ``conv`` are those at its TRUE last token:
the scan is told the rows' lengths and padding never reaches the state; the
window is gathered at the last real position as ``lfm2_moe``'s is.

Precision: parameters, activations, K/V and conv windows in ``cfg.dtype``;
``S``, ``g``, ``beta``, the L2 norms, every RMSNorm, the softmax and the
head's logits float32.

A prefill is bounded in tokens: rows are mapped INSIDE the program a GROUP
at a time (``models/mapped_prefill.py``); decoding runs all rows together.

Scopes: ``embed_tokens``, ``layers_<i>/linear_attn`` (``in_proj``,
``conv1d``, ``scan``: the op and nothing else, ``gate_norm``,
``out_proj``), ``layers_<i>/attn`` (``core``), ``layers_<i>/mlp``,
``lm_head``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import flash_attention
from ..ops.gated_delta_rule import (
    CHUNK,
    chunk_gated_delta_rule,
    gated_delta_step,
)
from ..ops.rope import RopeSpec, apply_rotary
from .cached_model import CachedDecoder
from .checkpoints import (
    checkpoint_reader,
    on_device,
    swiglu_matrices,
    torch_dtype_of,
)
from .decoder_parts import NEG_INF, head, rms_norm
from .experts import swiglu
from .mapped_prefill import prefill_group, prefill_in_groups

LAYER_TYPES = ("linear_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    head_dim: int = 128
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: Optional[float] = None  # None: no rotary embedding
    max_position_embeddings: int = 65536
    dtype: Any = jnp.bfloat16

    @property
    def conv_width(self) -> int:
        """The channels of the three convolutions side by side: q, k, v."""
        return self.linear_num_key_heads * (2 * self.linear_key_head_dim
                                            + self.linear_value_head_dim)

    @classmethod
    def from_hf(cls, hf: Mapping[str, Any], **overrides
                ) -> "OlmoHybridConfig":
        """From a checkpoint's ``config.json`` (``model_type:
        olmo_hybrid``).  What the architecture cannot express is refused by
        name, not ignored."""
        def refuse(what: str):
            raise ValueError(f"olmo_hybrid: {what} is not supported")

        if hf.get("attention_bias", False):
            refuse("attention_bias")
        if hf.get("tie_word_embeddings", False):
            refuse("tie_word_embeddings (a head tied to the embedding)")
        if hf.get("hidden_act", "silu") != "silu":
            refuse(f"hidden_act {hf['hidden_act']!r}")
        rope = dict(hf.get("rope_parameters") or {})
        if rope.get("rope_type", "default") != "default" \
                or hf.get("rope_scaling"):
            refuse("a rope_type other than default")
        types = tuple(hf.get("layer_types") or ())
        if len(types) != hf["num_hidden_layers"] \
                or set(types) - set(LAYER_TYPES):
            raise ValueError(
                f"olmo_hybrid: layer_types must name one of {LAYER_TYPES} "
                f"for each of the {hf['num_hidden_layers']} layers, not "
                f"{types}")
        if hf.get("linear_num_key_heads") != hf.get("linear_num_value_heads"):
            refuse("linear_num_key_heads != linear_num_value_heads (key "
                   "heads repeated over value heads)")
        if hf.get("num_key_value_heads", hf["num_attention_heads"]) \
                != hf["num_attention_heads"]:
            refuse("num_key_value_heads != num_attention_heads (grouped "
                   "k/v in the full layers)")
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in fields}
        kw["layer_types"] = types
        if kw.get("head_dim") is None:
            kw["head_dim"] = hf["hidden_size"] // hf["num_attention_heads"]
        kw["dtype"] = torch_dtype_of(hf.get("torch_dtype", "bfloat16"))
        theta = rope.get("rope_theta", hf.get("rope_theta"))
        kw["rope_theta"] = None if theta is None else float(theta)
        kw.update(overrides)
        return cls(**kw)


# -- parameters ------------------------------------------------------------------


def params_from_checkpoint(path: str, cfg: OlmoHybridConfig) -> Dict[str, Any]:
    with checkpoint_reader(path) as get:
        return params_from_state(get, cfg)


def params_from_state(get: Callable[[str], np.ndarray], cfg: OlmoHybridConfig
                      ) -> Dict[str, Any]:
    """The checkpoint's tensor names (``get(name)`` loads one;
    ``chipbench/reference/olmo_hybrid.py`` lists them) as this module's
    tree, in ``cfg.dtype`` on the default device; ``A_log`` and ``dt_bias``
    stay float32.  A linear layer's q, k, v projections become ONE matrix
    ``qkv [H, conv_width]``, its three filters one ``conv_w [taps,
    conv_width]`` and its a, b projections one ``ab [H, 2 heads]``."""
    dev = functools.partial(on_device, cfg)
    f32 = lambda name: jnp.asarray(np.asarray(get(name), np.float32))  # noqa: E731
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        layer = {"attn_norm": dev(get(p + "post_attention_layernorm.weight")),
                 "ffn_norm": dev(get(p + "post_feedforward_layernorm.weight")),
                 **swiglu_matrices(get, cfg, p + "mlp.")}
        if kind == "full_attention":
            a = p + "self_attn."
            layer.update(
                q_proj=dev(get(a + "q_proj.weight"), True),
                k_proj=dev(get(a + "k_proj.weight"), True),
                v_proj=dev(get(a + "v_proj.weight"), True),
                o_proj=dev(get(a + "o_proj.weight"), True),
                q_norm=dev(get(a + "q_norm.weight")),
                k_norm=dev(get(a + "k_norm.weight")))
        else:
            a = p + "linear_attn."
            layer.update(
                qkv=jnp.concatenate([dev(get(f"{a}{n}_proj.weight"), True)
                                     for n in "qkv"], -1),
                # [channels, 1, taps] as a depthwise Conv1d stores it
                conv_w=jnp.concatenate([
                    dev(np.asarray(get(f"{a}{n}_conv1d.weight"))[:, 0].T)
                    for n in "qkv"], -1),
                ab=jnp.concatenate([dev(get(f"{a}{n}_proj.weight"), True)
                                    for n in "ab"], -1),
                gate=dev(get(a + "g_proj.weight"), True),
                A_log=f32(a + "A_log"), dt_bias=f32(a + "dt_bias"),
                o_norm=dev(get(a + "o_norm.weight")),
                o_proj=dev(get(a + "o_proj.weight"), True))
        layers.append(layer)
    return {"embed": dev(get("model.embed_tokens.weight")),
            "layers": layers,
            "norm": dev(get("model.norm.weight")),
            "lm_head": dev(get("lm_head.weight"))}


# -- the linear-attention layer's parts ------------------------------------------


def _in_proj(cfg: OlmoHybridConfig, p, h):
    """``h [..., H]`` -> the convolutions' input ``z [..., conv_width]``
    (the model's dtype: what the window holds), the output gate's input
    ``[..., heads d_v]``, and ``g``, ``beta [..., heads]`` float32."""
    with jax.named_scope("in_proj"):
        a, b = jnp.split(jnp.dot(h, p["ab"],
                                 preferred_element_type=jnp.float32), 2, -1)
        beta = jax.nn.sigmoid(b)
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
        return h @ p["qkv"], h @ p["gate"], g, beta


def _taps(p, window):
    """``silu(sum_j w_j * window[j])`` over the leading axis, float32:
    tap ``taps - 1`` is on the token itself."""
    w = p["conv_w"].astype(jnp.float32)
    return jax.nn.silu(sum(w[j] * window[j].astype(jnp.float32)
                           for j in range(w.shape[0])))


def _heads(cfg: OlmoHybridConfig, y):
    """The convolutions' output ``y [..., conv_width]`` float32 -> q, k
    ``[..., heads, d_k]`` L2-normalised (q times ``d_k^-0.5``) and v
    ``[..., heads, d_v]``, in the model's dtype."""
    n, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    q, k, v = jnp.split(y, (n * dk, 2 * n * dk), -1)
    q, k, v = (t.reshape(t.shape[:-1] + (n, -1)) for t in (q, k, v))
    unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    return ((unit(q) * dk ** -0.5).astype(cfg.dtype),
            unit(k).astype(cfg.dtype), v.astype(cfg.dtype))


def _gate_norm(cfg: OlmoHybridConfig, p, o, gate):
    """``o [..., heads, d_v]``, ``gate [..., heads d_v]`` -> the output
    projection's input: the norm a head, times ``silu(gate)``."""
    with jax.named_scope("gate_norm"):
        o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps, jnp.float32)
        o = o.reshape(gate.shape) * jax.nn.silu(gate.astype(jnp.float32))
        return o.astype(cfg.dtype)


def _qkv_full(cfg: OlmoHybridConfig, p, h, positions, table_len: int):
    """``h [B, S, H]`` -> q, k, v ``[B, S, heads, D]``: q and k normalised
    over the whole projected vector, rotated at ``positions [B, S]`` where
    the model has a rotary embedding."""
    shape = h.shape[:2] + (cfg.num_attention_heads, cfg.head_dim)
    q = rms_norm(h @ p["q_proj"], p["q_norm"], cfg.rms_norm_eps, cfg.dtype)
    k = rms_norm(h @ p["k_proj"], p["k_norm"], cfg.rms_norm_eps, cfg.dtype)
    q, k, v = q.reshape(shape), k.reshape(shape), \
        (h @ p["v_proj"]).reshape(shape)
    if cfg.rope_theta is not None:
        cos_t, sin_t = RopeSpec(cfg.head_dim, cfg.rope_theta).tables(
            table_len)
        cos = jnp.take(cos_t, positions, axis=0)[:, :, None, :]
        sin = jnp.take(sin_t, positions, axis=0)[:, :, None, :]
        q, k = apply_rotary(q, k, cos, sin)
    return q, k, v


def _close(cfg: OlmoHybridConfig, p, x, mixed):
    """The layer after its mixer: ``x + RMSNorm_a(mixed)``, then the
    feed-forward half ``x + RMSNorm_f(FF(x))``."""
    x = x + rms_norm(mixed, p["attn_norm"], cfg.rms_norm_eps, cfg.dtype)
    with jax.named_scope("mlp"):
        y = swiglu(cfg, p, x)
    return x + rms_norm(y, p["ffn_norm"], cfg.rms_norm_eps, cfg.dtype)


# -- prefill ---------------------------------------------------------------------


def _prefill_rows(cfg: OlmoHybridConfig, params, ids, lengths,
                  cache_len: int):
    """``ids [B, S]`` right-padded, ``lengths [B]`` -> ``(full, state,
    conv, logits [B, V], load [0, 4])``: the whole prompt under the causal
    mask, all rows of ``ids`` at once."""
    B, S = ids.shape
    keep = cfg.linear_conv_kernel_dim - 1
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]
    last = jnp.maximum(lengths - 1, 0)
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], ids, axis=0)
    full, state, conv = [], [], []
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        with jax.named_scope(f"layers_{i}"):
            if kind == "linear_attention":
                with jax.named_scope("linear_attn"):
                    z, gate, g, beta = _in_proj(cfg, p, x)
                    with jax.named_scope("conv1d"):
                        zp = jnp.pad(z, ((0, 0), (keep, 0), (0, 0)))
                        q, k, v = _heads(cfg, _taps(
                            p, [zp[:, j:j + S] for j in range(keep + 1)]))
                        # the window at each row's TRUE last token: z at
                        # len - keep .. len - 1 (zeros before position 0)
                        at = last[:, None] + jnp.arange(1, keep + 1)
                        conv.append(jnp.take_along_axis(
                            zp, at[:, :, None], axis=1)
                            * (lengths > 0)[:, None, None].astype(z.dtype))
                    with jax.named_scope("scan"):
                        o, s = chunk_gated_delta_rule(
                            *(jnp.moveaxis(t, 2, 1)
                              for t in (q, k, v, g, beta)), lengths=lengths)
                    state.append(s)
                    o = _gate_norm(cfg, p, jnp.moveaxis(o, 1, 2), gate)
                    with jax.named_scope("out_proj"):
                        mixed = o @ p["o_proj"]
            else:
                with jax.named_scope("attn"):
                    q, k, v = (jnp.moveaxis(t, 2, 1) for t in _qkv_full(
                        cfg, p, x, positions, S))
                    with jax.named_scope("core"):
                        out = flash_attention(
                            q, k, v, key_padding_mask=valid.astype(jnp.int32),
                            causal=True, lengths=lengths)
                    mixed = jnp.moveaxis(out, 1, 2).reshape(B, S, -1) \
                        .astype(cfg.dtype) @ p["o_proj"]
                    pad = ((0, 0), (0, 0), (0, cache_len - S), (0, 0))
                    full.append((jnp.pad(k, pad), jnp.pad(v, pad)))
            # what the layer leaves in the cache is cut from its
            # activations BEFORE the next layer begins: only the program's
            # end reads it, and a scheduler free to cut it there keeps
            # every layer's [S, conv_width] alive until then (2.0 GB more
            # temporaries in a 16-layer prefill compiled for a v5e)
            kept = (state, conv) if kind == "linear_attention" else (full,)
            mixed, last_of = jax.lax.optimization_barrier(
                (mixed, [c[-1] for c in kept]))
            for c, a in zip(kept, last_of):
                c[-1] = a
            x = _close(cfg, p, x, mixed)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return full, state, conv, head(cfg, params, x_last), _no_load()


def _no_load():
    """The expert load of a model with no expert layer, ``[0, 4]``: what
    ``aux["load"]`` is here, so that this model's steps close with the
    ``engine.gen.forward`` marker (written where a step reports a load)
    that carries a prefill's cache bytes and a loop's forwards."""
    return jnp.zeros((0, 4), jnp.float32)


def _row_bytes(cfg: OlmoHybridConfig, S: int) -> int:
    """A prefill row's temporaries, reckoned from above: four ``[S, H]`` of
    residual stream and the SwiGLU's gate+up and product (``3 I`` a token)
    in the model's dtype, beside what the scan holds in float32 for every
    chunk at once (``ops/gated_delta_rule.py``: qg, w, kdT ``3 d_k``, u
    ``d_v``, and five ``[C, C]`` a chunk, ``5 C`` a token, a head).  At the
    published widths (S 8192, bfloat16) 1.58 GB; the compiler's count for a
    described v5e is in ``tests/test_tpu_compile.py``."""
    n, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    return S * (jnp.dtype(cfg.dtype).itemsize
                * (4 * cfg.hidden_size + 3 * cfg.intermediate_size)
                + 4 * n * (3 * dk + dv + 5 * CHUNK))


def _cache_bytes(cfg: OlmoHybridConfig, rows: int, cache_len: int) -> int:
    """The bytes of the cache a prefill of ``rows`` returns."""
    item = jnp.dtype(cfg.dtype).itemsize
    n_full = sum(k == "full_attention" for k in cfg.layer_types)
    kv = 2 * cfg.num_attention_heads * cache_len * cfg.head_dim * item
    linear = 4 * cfg.linear_num_key_heads * cfg.linear_key_head_dim \
        * cfg.linear_value_head_dim \
        + (cfg.linear_conv_kernel_dim - 1) * cfg.conv_width * item
    return rows * (n_full * kv + (len(cfg.layer_types) - n_full) * linear)


def _prefill_groups(cfg: OlmoHybridConfig, params, ids, lengths,
                    cache_len: int, group: int):
    """``prefill`` at ``group`` rows a call of ``_prefill_rows``."""
    return prefill_in_groups(
        lambda ids, lengths: _prefill_rows(cfg, params, ids, lengths,
                                           cache_len),
        ("full", "state", "conv", "logits", "load"), (), group, ids, lengths)


def prefill(cfg: OlmoHybridConfig, params, ids, lengths, cache_len: int):
    """``ids [B, S]`` right-padded prompts of ``lengths [B]`` (0 = a padding
    row) -> ``(cache, logits [B, V] float32 at each row's last token, aux)``
    with ``aux = {"load" [0, 4]}`` (``_no_load``).
    ``mapped_prefill.prefill_group`` rows at a time inside the program, so a
    bucket's temporaries are those of ONE group whatever the batch."""
    return _prefill_groups(
        cfg, params, ids, lengths, cache_len,
        prefill_group(cfg, params, *ids.shape, cache_len, _row_bytes,
                      _cache_bytes))


# -- decode: one token a row against the cache -----------------------------------


def decode(cfg: OlmoHybridConfig, params, cache, tokens, positions):
    """``tokens [B]`` at ``positions [B]`` (a row's count of tokens before
    this one), all rows together.  Returns ``(cache, logits [B, V], aux)``
    (``aux["load"] [0, 4]``); the cache comes back with this token's K and V
    at row ``positions`` of every full layer, every linear layer's state
    moved on by one token and its window by one input."""
    B = tokens.shape[0]
    D = cfg.head_dim
    pos = positions[:, None]
    put = jax.vmap(lambda c, new, at: jax.lax.dynamic_update_slice(
        c, new, (0, at, 0)))  # a row's [heads, M, D]
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["embed"], tokens, axis=0)[:, None]  # [B, 1, H]
    full, state, conv = [], [], []
    full_in, state_in, conv_in = (iter(cache[k])
                                  for k in ("full", "state", "conv"))
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        with jax.named_scope(f"layers_{i}"):
            if kind == "linear_attention":
                with jax.named_scope("linear_attn"):
                    z, gate, g, beta = _in_proj(cfg, p, x[:, 0])
                    with jax.named_scope("conv1d"):
                        window = jnp.concatenate(
                            [next(conv_in), z[:, None]], axis=1)
                        q, k, v = _heads(cfg, _taps(
                            p, jnp.moveaxis(window, 1, 0)))
                        conv.append(window[:, 1:])
                    with jax.named_scope("scan"):
                        o, s = gated_delta_step(next(state_in), q, k, v, g,
                                                beta)
                    state.append(s)
                    o = _gate_norm(cfg, p, o, gate)
                    with jax.named_scope("out_proj"):
                        mixed = (o @ p["o_proj"])[:, None]
            else:
                with jax.named_scope("attn"):
                    k_cache, v_cache = next(full_in)
                    M = k_cache.shape[2]
                    q, k, v = _qkv_full(cfg, p, x, pos, M)  # [B, 1, n, D]
                    k_cache = put(k_cache, jnp.moveaxis(k, 1, 2), positions)
                    v_cache = put(v_cache, jnp.moveaxis(v, 1, 2), positions)
                    full.append((k_cache, v_cache))
                    with jax.named_scope("core"):
                        s = jnp.einsum("bhd,bhmd->bhm", q[:, 0], k_cache,
                                       preferred_element_type=jnp.float32) \
                            * (1.0 / np.sqrt(float(D)))
                        seen = jnp.arange(M)[None, :] <= pos  # [B, M]
                        s = s + jnp.where(seen, 0.0, NEG_INF)[:, None, :]
                        out = jnp.einsum(
                            "bhm,bhmd->bhd",
                            jax.nn.softmax(s, axis=-1).astype(cfg.dtype),
                            v_cache, preferred_element_type=jnp.float32)
                    mixed = (out.reshape(B, -1).astype(cfg.dtype)
                             @ p["o_proj"])[:, None]
            x = _close(cfg, p, x, mixed)
    cache = {"full": full, "state": state, "conv": conv,
             "lengths": cache["lengths"]}
    return cache, head(cfg, params, x[:, 0]), {"load": _no_load()}


class CachedModel(CachedDecoder):
    """This decoder behind the interface ``models.generate.GreedyGenerator``
    decodes through; its prefill's flash calls are its full layers', all
    their heads over the whole prompt."""

    def __init__(self, config: OlmoHybridConfig) -> None:
        super().__init__(
            config, prefill, decode, cache_kinds=("full", "state", "conv"),
            group_sizes=(_row_bytes, _cache_bytes),
            attn_layers=[(config.num_attention_heads, 0)
                         for kind in config.layer_types
                         if kind == "full_attention"])
