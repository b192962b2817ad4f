"""The plain reference of the ``olmo_hybrid`` decoder (Olmo-Hybrid-7B,
https://huggingface.co/allenai/Olmo-Hybrid-7B): straightforward ``jax.numpy``
in float32 under ``default_matmul_precision("highest")``, no kernel, no
chunks, no cache, no padding, no batching: ONE causal forward over one
sequence, the linear-attention layers as the token-by-token recurrence.  It
imports nothing of the program.

Equations (one sequence of ``T`` tokens, hidden ``H``; ``x`` the residual
stream).  RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``.

- every layer: ``x <- x + RMSNorm_a(Mix(x))``, ``x <- x + RMSNorm_f(FF(x))``
  — the norm on the sub-layer's OUTPUT, no input norm (ASSUMED (i): Olmo
  2/3's reordered norm); after the last layer a final RMSNorm and an untied
  head.  ``FF(h) = W_down (silu(W_gate h) * (W_up h))``, no bias.
- ``Mix`` of a ``full_attention`` layer: ``q = RMSNorm_q(W_q h)``, ``k =
  RMSNorm_k(W_k h)`` over the WHOLE projected vector of ``heads * D`` before
  the split into heads (ASSUMED (ii): Olmo 2/3's), ``v = W_v h``; ``heads``
  heads of ``D = H / heads``, as many k/v heads; ``softmax(q k^T / sqrt(D) +
  causal mask) v``, then ``W_o``.  NO rotary embedding: the config says
  ``rope_parameters.rope_theta: null`` and no other reading lets one be
  computed (ASSUMED (iii)); where ``rope_theta`` is a number the default
  RoPE (``rotate_half`` form) is applied to q and k after their norms.
- ``Mix`` of a ``linear_attention`` layer, the gated delta rule, per head
  ``n``, key width ``d_k``, value width ``d_v``:
  ``q~ = W_q h``, ``k~ = W_k h``, ``v~ = W_v h``; each through its own
  depthwise CAUSAL convolution of ``L = linear_conv_kernel_dim`` taps (zeros
  before position 0; what a ``Conv1d(C, C, L, groups=C, padding=L-1)`` cut
  to ``T`` gives: tap ``L - 1`` on the token itself) and then ``silu``:
  ``q_t = silu(sum_j c^q_j q~_{t-L+1+j})``, likewise k, v;
  ``q_t <- q_t / sqrt(|q_t|^2 + 1e-6) * d_k^-0.5``, ``k_t <- k_t /
  sqrt(|k_t|^2 + 1e-6)`` per head;
  ``beta_t = sigmoid(W_b h)_n``, times 2 under ``linear_allow_neg_eigval``;
  ``g_t = -exp(A_log_n) * softplus((W_a h)_n + dt_bias_n)``;
  state ``S [d_k, d_v]`` a head, ``S_0 = 0``: ``S' = exp(g_t) S_{t-1}``,
  ``u_t = beta_t (v_t - S'^T k_t)``, ``S_t = S' + k_t u_t^T``, ``o_t =
  S_t^T q_t``; ``Mix(h)_t = W_o concat_n(RMSNorm_o(o_t,n) * silu((W_g
  h)_t,n))``, the norm per head over ``d_v`` with ONE weight vector of
  ``d_v``.

The public ``olmo_hybrid`` modelling code is not in the repository (no
network).  Four readings are ASSUMED from the family's convention; where the
published code and these lines differ, the published code wins:

(i)   the reordered norm (on the sub-layer's output, no input norm);
(ii)  q and k normalised over the whole projected vector, not per head;
(iii) no rotary embedding in the full layers (``rope_theta: null``);
(iv)  the tensor names, and the q/k/v/a/b/g projections and the three
      convolutions as SEPARATE tensors (the FLA layer's form; fused, as
      Qwen3-Next stores them, is the same mathematics):
      ``model.embed_tokens.weight``, ``model.norm.weight``,
      ``lm_head.weight``; per layer ``model.layers.<i>.``
      ``post_attention_layernorm.weight``,
      ``post_feedforward_layernorm.weight``,
      ``mlp.{gate,up,down}_proj.weight``; a full layer's
      ``self_attn.{q,k,v,o}_proj.weight``, ``self_attn.{q,k}_norm.weight``
      ``[heads * D]``; a linear layer's
      ``linear_attn.{q,k,v,a,b,g,o}_proj.weight``,
      ``linear_attn.{q,k,v}_conv1d.weight`` ``[channels, 1, L]``,
      ``linear_attn.A_log`` and ``linear_attn.dt_bias`` ``[heads]`` float32,
      ``linear_attn.o_norm.weight`` ``[d_v]``.

``head_dim`` 128 (3840 / 30: the config gives none), greedy selection and the
length of a generation are ``assumed`` in the configuration's file.

``precision``: ``"highest"`` is the reference.  The controls stand in the
program's place: ``"float8_e4m3_weights"`` rounds every matrix through float8
(the nearest format below the bfloat16 the configuration states);
``"bfloat16_state"`` rounds every linear layer's state ``S`` to bfloat16 after
every 64th token (a chunk's boundary in the program), where the
configuration states a float32 state.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
STATE_CHUNK = 64  # where the bfloat16-state control rounds


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


def attention(cfg, w, x):
    """Causal attention, one head at a time (a head's ``[T, T]`` scores are
    the largest thing held)."""
    T = x.shape[0]
    nh = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // nh
    eps = cfg["rms_norm_eps"]
    positions = jnp.arange(T)
    q = rms_norm(x @ w["q_proj"].T, w["q_norm"], eps).reshape(T, nh, d)
    k = rms_norm(x @ w["k_proj"].T, w["k_norm"], eps).reshape(T, nh, d)
    v = (x @ w["v_proj"].T).reshape(T, nh, d)
    theta = (cfg.get("rope_parameters") or {}).get("rope_theta")
    if theta is not None:
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    causal = positions[None, :] <= positions[:, None]

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(causal, qh @ kh.T / jnp.sqrt(float(d)), NEG)
        return jax.nn.softmax(s, -1) @ vh

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 1).reshape(T, nh * d) @ w["o_proj"].T


def causal_conv_silu(z, filt):
    """``z [T, C]`` through the depthwise causal convolution ``filt [C, L]``
    (tap ``L - 1`` on the token itself) and ``silu``."""
    T, taps = z.shape[0], filt.shape[1]
    out = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads z_{t - back}
        out = out + filt[:, j][None, :] * jnp.pad(z, ((back, 0), (0, 0)))[:T]
    return jax.nn.silu(out)


def linear_attention(cfg, w, x, bf16_state: bool = False):
    """The gated delta rule, a token at a time (``lax.scan`` over the
    positions)."""
    T = x.shape[0]
    n, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    q, k, v = (causal_conv_silu(x @ w[f"{c}_proj"].T, w[f"{c}_conv"])
               .reshape(T, n, -1) for c in "qkv")
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(x @ w["b_proj"].T)
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["a_proj"].T
                                               + w["dt_bias"])

    def token(S, t):
        q_t, k_t, v_t, g_t, b_t, at = t
        S = jnp.exp(g_t)[:, None, None] * S
        u = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], 1))  # S^T k
        S = S + k_t[:, :, None] * u[:, None, :]
        o = jnp.sum(S * q_t[:, :, None], 1)  # S^T q
        if bf16_state:
            # (reduce_precision, not astype there and back: a compiler
            # that is allowed excess precision drops that round trip)
            S = jnp.where((at + 1) % STATE_CHUNK == 0,
                          jax.lax.reduce_precision(S, 8, 7), S)
        return S, o

    _, o = jax.lax.scan(token, jnp.zeros((n, dk, dv), jnp.float32),
                        (q, k, v, g, beta, jnp.arange(T)), unroll=8)
    o = rms_norm(o, w["o_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.silu(x @ w["g_proj"].T).reshape(T, n, dv)
    return o.reshape(T, n * dv) @ w["o_proj"].T


def swiglu(w, x):
    return (jax.nn.silu(x @ w["gate_proj"].T) * (x @ w["up_proj"].T)) \
        @ w["down_proj"].T


def _f32(a) -> jnp.ndarray:
    """On the device, widened there (a bfloat16 widens exactly)."""
    return jnp.asarray(np.asarray(a)).astype(jnp.float32)


def _lower(a: jnp.ndarray, precision: str) -> jnp.ndarray:
    """The float8 control's weights: every matrix (the last two axes)
    through float8 (e4m3, scaled to its largest entry), the nearest format
    below bfloat16."""
    if precision in ("highest", "bfloat16_state") or a.ndim < 2:
        return a
    if precision != "float8_e4m3_weights":
        raise ValueError(f"unknown precision {precision!r}")
    scale = jnp.max(jnp.abs(a), axis=(-2, -1), keepdims=True) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def layer_weights(cfg, state: Mapping[str, Any], i: int, precision: str
                  ) -> Dict[str, Any]:
    """Layer ``i`` of a checkpoint's state dict (the names above) in
    float32; ``state`` may load lazily, one tensor per access."""
    p = f"model.layers.{i}."
    get = lambda name: _lower(_f32(state[p + name]), precision)  # noqa: E731
    w: Dict[str, Any] = {
        "attn_norm": get("post_attention_layernorm.weight"),
        "ffn_norm": get("post_feedforward_layernorm.weight"),
        "ff": {f"{k}_proj": get(f"mlp.{k}_proj.weight")
               for k in ("gate", "up", "down")}}
    if cfg["layer_types"][i] == "full_attention":
        a = "self_attn."
        w["mix"] = {**{f"{k}_proj": get(f"{a}{k}_proj.weight") for k in "qkvo"},
                    "q_norm": get(a + "q_norm.weight"),
                    "k_norm": get(a + "k_norm.weight")}
    else:
        a = "linear_attn."
        w["mix"] = {
            **{f"{k}_proj": get(f"{a}{k}_proj.weight") for k in "qkvabgo"},
            **{f"{k}_conv": _f32(state[f"{p}{a}{k}_conv1d.weight"])[:, 0, :]
               for k in "qkv"},
            "A_log": _f32(state[p + a + "A_log"]),
            "dt_bias": _f32(state[p + a + "dt_bias"]),
            "o_norm": get(a + "o_norm.weight")}
    return w


class _hashable(dict):
    """The model's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layer(cfg, kind, bf16_state, w, x):
    eps = cfg["rms_norm_eps"]
    mixed = attention(cfg, w["mix"], x) if kind == "full_attention" \
        else linear_attention(cfg, w["mix"], x, bf16_state)
    x = x + rms_norm(mixed, w["attn_norm"], eps)
    return x + rms_norm(swiglu(w["ff"], x), w["ffn_norm"], eps)


# (by the layer's KIND, not its index: two programs a sequence length)
_layer_jit = jax.jit(_layer, static_argnums=(0, 1, 2))


def forward(cfg: Dict[str, Any], state: Mapping[str, Any], ids,
            want_rows: Optional[Sequence[int]] = None,
            precision: str = "highest") -> Dict[str, np.ndarray]:
    """One sequence through the whole model under the causal mask.
    Returns ``logits [rows, V]`` at ``want_rows`` (every token if None).
    One layer's weights are in float32 at a time."""
    with jax.default_matmul_precision("highest"):
        ids = np.asarray(ids, np.int32)
        # the prompt's rows, taken on the host: the table is never widened
        x = _f32(np.asarray(state["model.embed_tokens.weight"])[
            np.asarray(ids)])
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, state, i, precision)
            x = _layer_jit(_hashable(cfg), cfg["layer_types"][i],
                           precision == "bfloat16_state", w, x)
            del w
        x = rms_norm(x, _f32(state["model.norm.weight"]),
                     cfg["rms_norm_eps"])
        if want_rows is not None:
            x = x[jnp.asarray(np.asarray(want_rows, np.int32))]
        head = _lower(_f32(state["lm_head.weight"]), precision)
        logits = np.asarray(x @ head.T)
    return {"logits": logits}
