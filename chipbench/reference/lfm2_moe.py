"""The plain reference of the ``lfm2_moe`` decoder (LFM2-24B-A2B,
https://huggingface.co/LiquidAI/LFM2-24B-A2B): straightforward ``jax.numpy``
in float32 under ``default_matmul_precision("highest")``, no kernel, no
cache, no batching: ONE causal forward over one sequence.  It imports
nothing of the program.

Equations (one sequence of ``T`` tokens, hidden ``H``):

- every layer: ``x <- x + Op(RMSNorm_op(x))``, ``x <- x + FF(RMSNorm_ffn(x))``;
  after the last a final RMSNorm (``embedding_norm``) and the head, which is
  the embedding (tied).  RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``.
- ``Op`` of a ``conv`` layer: ``[B, C, u] = split3(x W_in^T)``, ``z = B * u``,
  ``c_t = w[0] z_{t-2} + w[1] z_{t-1} + w[2] z_t`` per channel (what a
  depthwise ``Conv1d(H, H, 3, groups=H, padding=2)`` cut to ``T`` gives:
  a cross-correlation over the left-padded sequence), zeros before position
  0; ``Op(x)_t = (C_t * c_t) W_out^T``.  Written as three shifted products.
- ``Op`` of a ``full_attention`` layer: ``q = x Wq -> [heads, D]``, ``k, v ->
  [kv_heads, D]``, RMSNorm over ``D`` on every head of q and k, RoPE
  (``rotate_half`` form, theta as published, default type) at the token's
  position, each k/v head serves ``heads / kv_heads`` q heads,
  ``softmax(q k^T / sqrt(D) + causal mask) v``, then ``Wo``.  No bias.
- ``FF`` of layer ``i < num_dense_layers``: ``W2 (silu(W1 x) * W3 x)``.
- ``FF`` of the others: ``s = sigmoid(x Wg^T)``; the ``k`` largest of ``s +
  b`` (``use_expert_bias``; ``b`` a float32 vector) are chosen; the weights
  are the UNBIASED ``s`` at the chosen experts, ``/ (their sum + 1e-6)``
  (``norm_topk_prob``), ``* routed_scaling_factor``; ``y = sum_e w_e
  W2_e (silu(W1_e x) * W3_e x)``.  Every held expert is computed for every
  token and the unselected weighted by zero.  ``experts_held = (first,
  count)`` leaves out what the experts outside that range would add; the
  router still scores all of them.

Departures from the published modelling code (transformers' ``lfm2_moe``),
each because this sandbox has no network and the code could not be read
again; where it and these lines differ, the published code wins:

- the tensor names (``operator_norm``, ``ffn_norm``, ``conv.in_proj``,
  ``conv.conv``, ``conv.out_proj``, ``self_attn.{q,k,v,out}_proj``,
  ``{q,k}_layernorm``, ``feed_forward.{w1,w2,w3}``, ``feed_forward.gate``,
  ``feed_forward.expert_bias``, ``feed_forward.experts.<e>.{w1,w2,w3}``,
  ``model.embedding_norm``) are written from memory of that code;
- the head is tied to the embedding (the family's way; the catalog's row
  does not say): ``assumed`` in the configuration's file;
- the published code computes the router's logits in the model's dtype;
  here, as everything, in float32;
- greedy selection and the length of a generation are inference-time
  settings, ``assumed`` in the configuration's file.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """``x [T, n, D]`` rotated at ``positions [T]`` (``rotate_half``)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def short_conv(w, x):
    """The gated short convolution; ``w["conv"] [H, L]`` one filter a
    channel, tap ``L - 1`` on the token itself."""
    b, c, u = jnp.split(x @ w["in_proj"].T, 3, axis=-1)
    z = b * u
    T, taps = z.shape[0], w["conv"].shape[1]
    conv = jnp.zeros_like(z)
    for k in range(taps):
        back = taps - 1 - k  # tap k reads z_{t - back}
        shifted = jnp.pad(z, ((back, 0), (0, 0)))[:T]
        conv = conv + w["conv"][:, k][None, :] * shifted
    return (c * conv) @ w["out_proj"].T


def attention(cfg, w, x):
    """Causal GQA, one head at a time (a head's ``[T, T]`` scores are the
    largest thing held)."""
    T = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // nh
    theta = cfg["rope_parameters"]["rope_theta"]
    positions = jnp.arange(T)
    q = (x @ w["q_proj"].T).reshape(T, nh, d)
    k = (x @ w["k_proj"].T).reshape(T, nkv, d)
    v = (x @ w["v_proj"].T).reshape(T, nkv, d)
    q = rope(rms_norm(q, w["q_norm"], cfg["norm_eps"]), positions, theta)
    k = rope(rms_norm(k, w["k_norm"], cfg["norm_eps"]), positions, theta)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    causal = positions[None, :] <= positions[:, None]

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(causal, qh @ kh.T / jnp.sqrt(float(d)), NEG)
        return jax.nn.softmax(s, -1) @ vh

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 1).reshape(T, nh * d) @ w["o_proj"].T


def swiglu(w1, w3, w2, x):
    return (jax.nn.silu(x @ w1.T) * (x @ w3.T)) @ w2.T


def route(cfg, w, x):
    """``(the scores the choice is made by [T, E], ids of the top k [T,
    k], their weights [T, k])``."""
    s = jax.nn.sigmoid(x @ w["router"].T)
    pick = s + w["expert_bias"] if cfg["use_expert_bias"] else s
    _, top_e = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(s, top_e, -1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-6)
    return pick, top_e, top_w * cfg["routed_scaling_factor"]


def moe(cfg, w, x, experts_held: Optional[Tuple[int, int]] = None):
    """``w["w1"|"w3"]: [E, I, H]``, ``w["w2"]: [E, H, I]`` as the checkpoint
    stores each expert's matrices, stacked.  One expert at a time over all
    the tokens."""
    pick, top_e, top_w = route(cfg, w, x)
    E = cfg["num_experts"]
    weights = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], top_e].set(top_w)
    if experts_held is not None:
        first, count = experts_held
        held = (jnp.arange(E) >= first) & (jnp.arange(E) < first + count)
        weights = weights * held[None, :]

    def one(y, e):
        out = swiglu(w["w1"][e], w["w3"][e], w["w2"][e], x)
        return y + weights[:, e][:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
    return y, pick, top_e


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
    return i >= cfg["num_dense_layers"]


def layer_weights(cfg, state: Mapping[str, Any], i: int, precision: str
                  ) -> Dict[str, Any]:
    """Layer ``i`` of a checkpoint's state dict (the published names) in
    float32; ``state`` may load lazily, one tensor per access."""
    p = f"model.layers.{i}."
    get = lambda name: _lower(_f32(state[p + name]), precision)  # noqa: E731
    w: Dict[str, Any] = {"norm1": get("operator_norm.weight"),
                         "norm2": get("ffn_norm.weight")}
    if cfg["layer_types"][i] == "conv":
        w["op"] = {"in_proj": get("conv.in_proj.weight"),
                   "conv": _f32(state[p + "conv.conv.weight"])[:, 0, :],
                   "out_proj": get("conv.out_proj.weight")}
    else:
        w["op"] = {"q_proj": get("self_attn.q_proj.weight"),
                   "k_proj": get("self_attn.k_proj.weight"),
                   "v_proj": get("self_attn.v_proj.weight"),
                   "o_proj": get("self_attn.out_proj.weight"),
                   "q_norm": get("self_attn.q_layernorm.weight"),
                   "k_norm": get("self_attn.k_layernorm.weight")}
    f = "feed_forward."
    if is_sparse(cfg, i):
        ff = {k: _lower(_f32(np.stack(
            [np.asarray(state[f"{p}{f}experts.{e}.{k}.weight"])
             for e in range(cfg["num_experts"])])), precision)
            for k in ("w1", "w3", "w2")}
        ff["router"] = get(f + "gate.weight")
        if cfg["use_expert_bias"]:
            ff["expert_bias"] = _f32(state[p + f + "expert_bias"])
    else:
        ff = {k: get(f"{f}{k}.weight") for k in ("w1", "w3", "w2")}
    w["ff"] = ff
    return w


class _hashable(dict):
    """The model's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layer(cfg, i, w, x, experts_held):
    h = rms_norm(x, w["norm1"], cfg["norm_eps"])
    x = x + (short_conv(w["op"], h) if cfg["layer_types"][i] == "conv"
             else attention(cfg, w["op"], h))
    h = rms_norm(x, w["norm2"], cfg["norm_eps"])
    if not is_sparse(cfg, i):
        f = w["ff"]
        return x + swiglu(f["w1"], f["w3"], f["w2"], h), None, None
    y, pick, top_e = moe(cfg, w["ff"], h, experts_held)
    return x + y, pick, top_e


_layer_jit = jax.jit(_layer, static_argnums=(0, 1, 4))


def forward(cfg: Dict[str, Any], state: Mapping[str, Any], ids,
            want_rows: Optional[Sequence[int]] = None,
            precision: str = "highest",
            experts_held: Optional[Tuple[int, int]] = None
            ) -> Dict[str, np.ndarray]:
    """One sequence through the whole model under the causal mask.
    Returns ``logits [rows, V]`` at ``want_rows`` (every token if None),
    and per expert layer the scores its choice was made by ``router_s
    [layers, T, E]`` and the ids chosen ``top_e [layers, T, k]``.  One
    layer's weights are in float32 at a time."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = _f32(state["model.embed_tokens.weight"])
        x = embed[ids]
        router_s, top_e = [], []
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, state, i, precision)
            x, s, e = _layer_jit(_hashable(cfg), i, w, x, experts_held)
            if s is not None:
                router_s.append(np.asarray(s))
                top_e.append(np.asarray(e))
            del w
        x = rms_norm(x, _f32(state["model.embedding_norm.weight"]),
                     cfg["norm_eps"])
        if want_rows is not None:
            x = x[jnp.asarray(np.asarray(want_rows, np.int32))]
        logits = np.asarray(x @ _lower(embed, precision).T)
    return {"logits": logits, "router_s": np.stack(router_s),
            "top_e": np.stack(top_e)}
