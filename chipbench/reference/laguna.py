"""The plain reference of the ``laguna`` decoder (Laguna-S-2.1,
https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json):
straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, no kernel, no cache, no ring, no
batching: ONE causal forward over one sequence, the window a MASK, one head
and one expert at a time (a head's ``[T, T]`` scores are the largest thing
held, so 8,032 positions fit).  It imports nothing of the program.

Equations (one sequence of ``T`` tokens; ``h`` = the layer's input after its
RMSNorm, ``x * rsqrt(mean(x^2) + eps) * w`` with eps ``rms_norm_eps``).  Every
layer ``i``: ``x <- x + Attn_t(RMSNorm_in(x))``, ``x <- x + FF_i(RMSNorm_post(x))``;
after the last a final RMSNorm and an untied head.

*Attention* of type ``t = layer_types[i]``: ``H_t =
num_attention_heads_per_layer[i]`` query heads over ``num_key_value_heads``
k/v heads of ``head_dim`` D, no bias.

- ``q = W_q h [H_t, D]``, ``k = W_k h``, ``v = W_v h [kv, D]``; q and k through
  a per-head RMSNorm over the D (one weight ``[D]`` each, shared by the heads).
- RoPE by ``rope_parameters[t]``, ``rotate_half`` pairing INSIDE the rotary
  dims: the first ``R = partial_rotary_factor * D`` dims of a head rotate (dim
  ``d`` pairs with ``d + R/2``), the rest pass.  ``rope_type: default``:
  ``inv_freq_j = theta^(-2j/R)``.  ``rope_type: yarn``: HF's
  ``_compute_yarn_parameters`` at ``dim = R`` — ``inv_freq`` blended between
  ``theta^(-2j/R)`` and ``theta^(-2j/R) / factor`` by the linear ramp between
  the correction dims of ``beta_fast`` and ``beta_slow`` over
  ``original_max_position_embeddings`` — and ``attention_factor`` multiplying
  cos and sin.
- query head ``i`` reads k/v head ``i // (H_t / kv)``; ``o_i(t) = sum over s in
  S_t of softmax_s(q_i(t) . k(s) / sqrt(D)) v(s)``; ``S_t = {s <= t}``
  (``full_attention``) or ``{s : 0 <= t - s < sliding_window}``
  (``sliding_attention``).
- ``g = sigmoid(W_g h)`` (``H_t`` scalars); ``Attn(h) = W_o [g_i * o_i]``.

*Feed-forward* by ``mlp_layer_types[i]``.  ``dense``: ``W_down (silu(W_gate h)
* W_up h)`` of width ``intermediate_size``.  ``sparse``: ``z = W_r h`` over all
``num_experts``; ``p = softmax(z)``; the ``num_experts_per_tok`` largest are
chosen; weights ``p_e / (sum of the chosen p)`` (``norm_topk_prob``) ``*
moe_routed_scaling_factor``, on the experts' OUTPUTS; ``FF(h) = sum_e w_e
E_e(h) + sigmoid(w_s . h) * E_shared(h)``, each a SwiGLU (width
``moe_intermediate_size``, the shared one ``shared_expert_intermediate_size``).
``experts_held = (first, count)`` leaves out what the routed experts outside
that range would add — the router still scores all of them, the shared expert
is whole.  ``vocab_held = (first, count)``: the embedding's and the head's rows
of that range are the vocabulary; ids, logits and the choice are over the slice.

Readings that ``config.json`` does not settle, each taken from ONE convention:
the config's key vocabulary (``decoder_sparse_step``, ``mlp_only_layers``,
``norm_topk_prob``, ``shared_expert_intermediate_size``, ``partial_rotary_factor``,
an attention output gate) is Qwen3-Next's, so where ``config`` is silent
Qwen3-Next's public modeling code is the convention.  Where the published
``laguna`` code and these lines differ, the published code wins:

- the per-head RMSNorm on q and k;
- ``gating: per-head`` as a sigmoid gate from the layer's normed input, one
  scalar a head, on the head's output before ``o_proj`` (the Gated-Attention
  headwise form);
- the softmax router (``moe_router_logit_softcapping`` 0 = no capping);
- ``sigmoid(w_s . h)`` on the shared expert (``shared_expert_gate``);
- ``sliding_window`` 512 = 512 keys with the token itself (HF's reading);
- the norms are plain (``x * w``), not Qwen3-Next's zero-centred ``1 + w``;
- the tensor names: ``self_attn.{q,k,v,o}_proj``, ``self_attn.{q,k}_norm``,
  ``self_attn.g_proj`` (the gate, ``[H_t, hidden]``), ``mlp.gate`` (the
  router), ``mlp.experts.<e>.{gate,up,down}_proj``,
  ``mlp.shared_expert.{gate,up,down}_proj``, ``mlp.shared_expert_gate``
  (``[1, hidden]``), ``mlp.{gate,up,down}_proj`` (dense).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
LAYER_TYPES = ("full_attention", "sliding_attention")


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(rope: Mapping[str, Any], dim: int) -> Tuple[np.ndarray, float]:
    """``(inv_freq [dim / 2], the factor on cos and sin)`` of one layer
    type's ``rope_parameters`` at ``dim`` rotary dims."""
    base = float(rope["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rope.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    scaling = rope.get("attention_factor")
    if scaling is None:
        scaling = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return plain / factor * ramp + plain * (1 - ramp), float(scaling)


def rotate(x, positions, rope: Mapping[str, Any]):
    """``x [T, heads, D]`` with its first ``partial_rotary_factor * D`` dims
    rotated at ``positions [T]`` (``rotate_half``), the rest as they are."""
    D = x.shape[-1]
    R = int(D * float(rope.get("partial_rotary_factor", 1)))
    freq, scaling = inv_freq(rope, R)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None, :]
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
           * scaling)[:, None, :]
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
           * scaling)[:, None, :]
    front = x[..., :R]
    x1, x2 = front[..., :R // 2], front[..., R // 2:]
    turned = front * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, x[..., R:]], -1)


def attention(cfg, i: int, w, h):
    """``Attn(h)`` of layer ``i``, one query head at a time."""
    kind = cfg["layer_types"][i]
    T = h.shape[0]
    nh, nkv, D = (cfg["num_attention_heads_per_layer"][i],
                  cfg["num_key_value_heads"], cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    rope = cfg["rope_parameters"][kind]
    at = jnp.arange(T)
    q = rms_norm((h @ w["q"].T).reshape(T, nh, D), w["q_norm"], eps)
    k = rms_norm((h @ w["k"].T).reshape(T, nkv, D), w["k_norm"], eps)
    v = (h @ w["v"].T).reshape(T, nkv, D)
    q, k = rotate(q, at, rope), rotate(k, at, rope)
    back = at[:, None] - at[None, :]
    seen = back >= 0
    if kind == "sliding_attention":
        seen = seen & (back < cfg["sliding_window"])
    scale = 1.0 / np.sqrt(float(D))
    k_heads, v_heads = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    def head(args):
        qh, j = args
        s = jnp.where(seen, (qh @ k_heads[j].T) * scale, NEG)
        return jax.nn.softmax(s, -1) @ v_heads[j]

    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                             jnp.arange(nh) // (nh // nkv)))
    gate = jax.nn.sigmoid(h @ w["gate"].T)  # [T, nh]
    out = jnp.moveaxis(out, 0, 1) * gate[:, :, None]
    return out.reshape(T, nh * D) @ w["o"].T


def swiglu(w1, w3, w2, x):
    return (jax.nn.silu(x @ w1.T) * (x @ w3.T)) @ w2.T


def route(cfg, w, x):
    """``(the logits the choice is made by [T, E], ids of the top k [T, k],
    their weights [T, k])``."""
    z = x @ w["router"].T
    p = jax.nn.softmax(z, -1)
    top_p, top_e = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return z, top_e, top_p * cfg["moe_routed_scaling_factor"]


def moe(cfg, w, x, experts: Tuple[int, int], shared: bool = True):
    """``w["gate"|"up"]: [n, I, H]``, ``w["down"]: [n, H, I]``: the
    matrices of the experts ``experts = (first, count)``, stacked.  One
    expert at a time over all the tokens; the gated shared expert beside
    them (``shared=False``: the routed part alone, for adding shares up)."""
    z, top_e, top_w = route(cfg, w, x)
    first, count = experts
    weights = jnp.zeros((x.shape[0], cfg["num_experts"]), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], top_e].set(top_w)

    def one(y, e):
        out = swiglu(w["gate"][e], w["up"][e], w["down"][e], x)
        return y + weights[:, first + e][:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    if shared:
        y = y + jax.nn.sigmoid(x @ w["shared_gate_w"].T) * swiglu(
            w["shared_gate"], w["shared_up"], w["shared_down"], x)
    return y, z, top_e


def _f32(a) -> jnp.ndarray:
    """On the device, widened there (a bfloat16 widens exactly)."""
    return jnp.asarray(np.asarray(a)).astype(jnp.float32)


def _lower(a: jnp.ndarray, precision: str) -> jnp.ndarray:
    """The control's weights: every matrix (the last two axes) through
    float8 (e4m3, scaled to its largest entry), the nearest format below
    bfloat16."""
    if precision == "highest" or a.ndim < 2:
        return a
    if precision != "float8_e4m3_weights":
        raise ValueError(f"unknown precision {precision!r}")
    scale = jnp.max(jnp.abs(a), axis=(-2, -1), keepdims=True) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def is_sparse(cfg, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "sparse"


def layer_weights(cfg, state: Mapping[str, Any], i: int, precision: str,
                  experts: Tuple[int, int]) -> Dict[str, Any]:
    """Layer ``i`` of a checkpoint's state dict in float32; ``state`` may
    load lazily, one tensor per access."""
    p = f"model.layers.{i}."
    get = lambda name: _lower(_f32(state[p + name]), precision)  # noqa: E731
    a = "self_attn."
    attn = {k: get(f"{a}{k}_proj.weight") for k in ("q", "k", "v", "o")}
    attn.update(q_norm=get(a + "q_norm.weight"),
                k_norm=get(a + "k_norm.weight"),
                gate=get(a + "g_proj.weight"))
    w: Dict[str, Any] = {"norm1": get("input_layernorm.weight"),
                         "norm2": get("post_attention_layernorm.weight"),
                         "attn": attn}
    f = "mlp."
    if is_sparse(cfg, i):
        first, count = experts
        ff = {k: _lower(_f32(np.stack(
            [np.asarray(state[f"{p}{f}experts.{e}.{k}_proj.weight"])
             for e in range(first, first + count)])), precision)
            for k in ("gate", "up", "down")}
        ff["router"] = get(f + "gate.weight")
        for k in ("gate", "up", "down"):
            ff["shared_" + k] = get(f"{f}shared_expert.{k}_proj.weight")
        ff["shared_gate_w"] = get(f + "shared_expert_gate.weight")
    else:
        ff = {k: get(f"{f}{k}_proj.weight") for k in ("gate", "up", "down")}
    w["ff"] = ff
    return w


class _hashable(dict):
    """The model's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layer(cfg, i, w, x, experts):
    h = rms_norm(x, w["norm1"], cfg["rms_norm_eps"])
    x = x + attention(cfg, i, w["attn"], h)
    h = rms_norm(x, w["norm2"], cfg["rms_norm_eps"])
    if not is_sparse(cfg, i):
        f = w["ff"]
        return x + swiglu(f["gate"], f["up"], f["down"], h), None, None
    y, z, top_e = moe(cfg, w["ff"], h, experts)
    return x + y, z, top_e


_layer_jit = jax.jit(_layer, static_argnums=(0, 1, 4))


def forward(cfg: Dict[str, Any], state: Mapping[str, Any], ids,
            want_rows: Optional[Sequence[int]] = None,
            precision: str = "highest",
            experts_held: Optional[Tuple[int, int]] = None,
            vocab_held: Optional[Tuple[int, int]] = None
            ) -> Dict[str, np.ndarray]:
    """One sequence through the whole model under the causal mask.  ``cfg``
    holds the PUBLISHED counts (``num_experts`` the router's width,
    ``vocab_size``); ``ids`` are ids of the vocabulary held (0 = its first
    row).  Returns ``logits [rows, V held]`` at ``want_rows`` (every token if
    None) and per sparse layer the logits its choice was made by ``router_s
    [layers, T, E]`` and the ids chosen ``top_e [layers, T, k]``.  One
    layer's weights are in float32 at a time."""
    experts = tuple(experts_held or (0, cfg["num_experts"]))
    first, count = vocab_held or (0, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = _f32(np.asarray(
            state["model.embed_tokens.weight"])[first:first + count])
        x = embed[ids]
        del embed
        router_s, top_e = [], []
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, state, i, precision, experts)
            x, s, e = _layer_jit(_hashable(cfg), i, w, x, experts)
            if s is not None:
                router_s.append(np.asarray(s))
                top_e.append(np.asarray(e))
            del w
        x = rms_norm(x, _f32(state["model.norm.weight"]),
                     cfg["rms_norm_eps"])
        if want_rows is not None:
            x = x[jnp.asarray(np.asarray(want_rows, np.int32))]
        head = _lower(_f32(np.asarray(
            state["lm_head.weight"])[first:first + count]), precision)
        logits = np.asarray(x @ head.T)
    out = {"logits": logits}
    if router_s:
        out.update(router_s=np.stack(router_s), top_e=np.stack(top_e))
    return out
