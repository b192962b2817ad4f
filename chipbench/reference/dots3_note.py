"""The plain reference of the ``dots3_note`` decoder (dots3-note-prev,
https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json):
straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, no kernel, no cache, no batching:
ONE causal forward over one sequence, one head and one expert at a time.
It imports nothing of the program.

Equations (one sequence of ``T`` tokens; ``h`` = the layer's input after its
RMSNorm, ``x * rsqrt(mean(x^2) + eps) * w`` with eps ``rms_norm_eps``; ``H`` =
``hidden_size``).  Every layer: ``x <- x + Attn(RMSNorm_in(x))``, ``x <- x +
FF(RMSNorm_post(x))``; after the last a final RMSNorm and an untied head.

*Latent attention*, in two geometries by ``layer_types[i]``: a
``full_attention`` layer reads the plain keys (``q_lora_rank`` r_q,
``kv_lora_rank`` r_kv, ``num_attention_heads``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``), a ``sliding_attention``
layer the ``swa_`` ones (``swa_rope_theta``, ...).

- ``c_q = RMSNorm(W_qa h) * sqrt(H / r_q)``; ``q_i = W_qb,i c_q = [q_i^nope ;
  RoPE(q_i^rope)]``.
- ``[c_kv' ; k^r'] = W_kva h``; ``c_kv = RMSNorm(c_kv') * sqrt(H / r_kv)``;
  ``k^r = RoPE(k^r')``, one for all heads.
- ``[k_i^nope ; v_i] = W_kvb,i c_kv``; ``k_i = [k_i^nope ; k^r]``.
- ``o_i(t) = sum over s in S_t of softmax_s(q_i(t) . k_i(s) / sqrt(nope +
  rope)) v_i(s)``.
- ``g = sigmoid(W_g h)`` (one scalar a head); ``Attn(h) = W_o [g_i * o_i]``.
- the keys a query sees, ``S_t``.  A sliding layer: ``{s : 0 <= t - s <
  sliding_window_size}`` (513 keys with the token itself).  A full layer:
  the indexer's choice.  ``q^I_j = W^I_q,j c_q`` (``index_n_heads`` heads of
  ``index_head_dim``), ``k^I = LayerNorm(W^I_k h)`` (weight and bias, eps
  1e-6), the first ``qk_rope_head_dim`` dims of each q^I_j and of k^I under
  the layer's RoPE; ``w = W^I_w h * index_n_heads^-0.5 *
  index_head_dim^-0.5``; ``I(t, s) = sum_j w_j(t) * relu(q^I_j(t) . k^I(s))``;
  ``S_t = {s <= t : I(t, s) >= the index_topk-th largest of I(t, 0..t)}``,
  every ``s <= t`` while ``t < index_topk``.

*Feed-forward*.  Layer ``i < first_k_dense_replace``: ``W_down (silu(W_gate
h) * W_up h)`` of width ``intermediate_size``.  The others: ``s = sigmoid(W_r
h)`` over all ``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s
+ b`` are chosen (``noaux_tc``, one group); weights ``s_e / (sum of the chosen
s + 1e-20)`` (``norm_topk_prob``) ``* routed_scaling_factor``; ``FF(h) = sum_e
w_e E_e(h) + E_shared(h)``, each a SwiGLU of width ``moe_intermediate_size``
(the shared one of ``n_shared_experts`` times that).  ``experts_held = (first,
count)`` leaves out what the routed experts outside that range would add —
the router still scores all of them, the shared expert is whole.
``vocab_held = (first, count)``: the embedding's and the head's rows of that
range are the vocabulary; ids, logits and the choice are over the slice.

RoPE is the ``rotate_half`` form: dim ``d`` pairs with ``d + D/2``.

Departures from the published model, each because ``config.json`` and the
catalog's description are all this sandbox has of it; where the published
code and these lines differ, the published code wins:

- ``apply_mla_qkv_lora_rescale``: taken as LongCat-Flash's
  ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` (the normed latent times
  ``sqrt(hidden / rank)``);
- ``attention_gate_type: headwise``: the Gated-Attention form (one sigmoid
  scalar a head from the layer's normed input, on the head's output before
  ``o_proj``);
- the indexer is DeepSeek-V3.2's (its keys are); it reads the RESCALED
  ``c_q``; its key norm's eps and the two scales on ``w`` are that code's; it
  is computed in float32 here (bfloat16 in the program; the family publishes
  float8 there);
- ties at the ``index_topk``-th score all stay (``>=``), so a query may see
  more than ``index_topk`` keys where two scores are equal to the bit;
- the tensor names (``self_attn.{q_a_proj, q_a_layernorm, q_b_proj,
  kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj, gate_proj}``,
  ``self_attn.indexer.{wq_b, wk, k_norm, weights_proj}``, ``mlp.gate``
  (+ ``e_score_correction_bias``), ``mlp.experts.<e>.{gate,up,down}_proj``,
  ``mlp.shared_experts.*``) are DeepSeek-V3's;
- the vision and audio towers and the MTP head are not in ``config`` and are
  left out.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
INDEX_NORM_EPS = 1e-6
ROUTE_EPS = 1e-20


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rope(x, positions, theta):
    """``x [T, ..., D]`` rotated at ``positions [T]`` (``rotate_half``)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d,)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1).reshape(shape)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def geometry(cfg, kind: str) -> Dict[str, Any]:
    """The latent attention's numbers of one kind of layer."""
    p = "swa_" if kind == "sliding_attention" else ""
    return {"heads": cfg[p + "num_attention_heads"],
            "r_q": cfg[p + "q_lora_rank"], "r_kv": cfg[p + "kv_lora_rank"],
            "nope": cfg[p + "qk_nope_head_dim"],
            "rope": cfg[p + "qk_rope_head_dim"], "v": cfg[p + "v_head_dim"],
            "theta": cfg[p + "rope_theta"]}


def index_scores(cfg, w, h, c_q, positions, theta):
    """``I [T, T]``, one indexer head at a time."""
    T = h.shape[0]
    nj, dj, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                  cfg["qk_rope_head_dim"])
    q = (c_q @ w["index_q"].T).reshape(T, nj, dj)
    q = jnp.concatenate([rope(q[..., :dr], positions, theta), q[..., dr:]],
                        -1)
    k = layer_norm(h @ w["index_k"].T, w["index_k_norm"],
                   w["index_k_bias"], INDEX_NORM_EPS)
    k = jnp.concatenate([rope(k[:, :dr], positions, theta), k[:, dr:]], -1)
    weight = (h @ w["index_w"].T) * (nj ** -0.5) * (dj ** -0.5)  # [T, nj]

    def one(acc, j):
        return acc + weight[:, j][:, None] * jax.nn.relu(q[:, j] @ k.T), None

    out, _ = jax.lax.scan(one, jnp.zeros((T, T), jnp.float32),
                          jnp.arange(nj))
    return out


def select(scores, topk: int):
    """``S_t`` as ``[T, T]`` booleans from ``I``: causal, and at or above
    the query's ``topk``-th largest visible score."""
    T = scores.shape[0]
    at = jnp.arange(T)
    causal = at[None, :] <= at[:, None]
    if T <= topk:
        return causal
    seen = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(seen, topk)[0][:, -1]
    return causal & (seen >= kth[:, None])


def latent_attention(cfg, kind: str, w, h, select_rows=None):
    """``Attn(h)`` of one layer, one head at a time (a head's ``[T, T]``
    scores are the largest thing held).  Of a full layer also what the
    indexer chose at ``select_rows``: ``(out, selected [n, T] bool, scores
    [n, T])``."""
    g = geometry(cfg, kind)
    T, H = h.shape
    nh, nope, dr, dv = g["heads"], g["nope"], g["rope"], g["v"]
    eps = cfg["rms_norm_eps"]
    at = jnp.arange(T)
    c_q = rms_norm(h @ w["q_a"].T, w["q_a_norm"], eps) \
        * np.sqrt(H / g["r_q"])
    q = (c_q @ w["q_b"].T).reshape(T, nh, nope + dr)
    q_rope = rope(q[..., nope:], at, g["theta"])
    kv = h @ w["kv_a"].T
    c_kv = rms_norm(kv[:, :g["r_kv"]], w["kv_a_norm"], eps) \
        * np.sqrt(H / g["r_kv"])
    k_rope = rope(kv[:, g["r_kv"]:], at, g["theta"])  # [T, dr]
    kvb = (c_kv @ w["kv_b"].T).reshape(T, nh, nope + dv)
    chosen = scores = None
    if kind == "sliding_attention":
        back = at[:, None] - at[None, :]
        seen = (back >= 0) & (back < cfg["sliding_window_size"])
    else:
        scores = index_scores(cfg, w, h, c_q, at, g["theta"])
        seen = select(scores, cfg["index_topk"])
        if select_rows is not None:
            chosen, scores = seen[select_rows], scores[select_rows]
    scale = 1.0 / np.sqrt(float(nope + dr))

    def head(args):
        qn, qr, kn, v = args
        s = jnp.where(seen, (qn @ kn.T + qr @ k_rope.T) * scale, NEG)
        return jax.nn.softmax(s, -1) @ v

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (
        q[..., :nope], q_rope, kvb[..., :nope], kvb[..., nope:])))
    gate = jax.nn.sigmoid(h @ w["gate"].T)  # [T, nh]
    out = jnp.moveaxis(out, 0, 1) * gate[:, :, None]
    return out.reshape(T, nh * dv) @ w["o"].T, chosen, scores


def swiglu(w1, w3, w2, x):
    return (jax.nn.silu(x @ w1.T) * (x @ w3.T)) @ w2.T


def route(cfg, w, x):
    """``(the scores the choice is made by [T, E], ids of the top k [T,
    k], their weights [T, k])``."""
    s = jax.nn.sigmoid(x @ w["router"].T)
    pick = s + w["router_bias"]
    _, top_e = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(s, top_e, -1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + ROUTE_EPS)
    return pick, top_e, top_w * cfg["routed_scaling_factor"]


def moe(cfg, w, x, experts: Tuple[int, int], shared: bool = True):
    """``w["gate"|"up"]: [n, I, H]``, ``w["down"]: [n, H, I]``: the
    matrices of the experts ``experts = (first, count)``, stacked.  One
    expert at a time over all the tokens; the shared expert beside them
    (``shared=False``: the routed part alone, for adding shares up)."""
    pick, top_e, top_w = route(cfg, w, x)
    E = cfg["n_routed_experts"]
    first, count = experts
    weights = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], top_e].set(top_w)

    def one(y, e):
        out = swiglu(w["gate"][e], w["up"][e], w["down"][e], x)
        return y + weights[:, first + e][:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    if shared:
        y = y + swiglu(w["shared_gate"], w["shared_up"], w["shared_down"], x)
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
    return i >= cfg["first_k_dense_replace"]


def layer_weights(cfg, state: Mapping[str, Any], i: int, precision: str,
                  experts: Tuple[int, int]) -> Dict[str, Any]:
    """Layer ``i`` of a checkpoint's state dict (the published names) in
    float32; ``state`` may load lazily, one tensor per access."""
    p = f"model.layers.{i}."
    get = lambda name: _lower(_f32(state[p + name]), precision)  # noqa: E731
    a = "self_attn."
    attn = {"q_a": get(a + "q_a_proj.weight"),
            "q_a_norm": get(a + "q_a_layernorm.weight"),
            "q_b": get(a + "q_b_proj.weight"),
            "kv_a": get(a + "kv_a_proj_with_mqa.weight"),
            "kv_a_norm": get(a + "kv_a_layernorm.weight"),
            "kv_b": get(a + "kv_b_proj.weight"),
            "o": get(a + "o_proj.weight"),
            "gate": get(a + "gate_proj.weight")}
    if cfg["layer_types"][i] == "full_attention":
        x = a + "indexer."
        attn.update(index_q=get(x + "wq_b.weight"),
                    index_k=get(x + "wk.weight"),
                    index_k_norm=get(x + "k_norm.weight"),
                    index_k_bias=get(x + "k_norm.bias"),
                    index_w=get(x + "weights_proj.weight"))
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
        ff["router_bias"] = _f32(state[p + f + "gate.e_score_correction_bias"])
        for k in ("gate", "up", "down"):
            ff["shared_" + k] = get(f"{f}shared_experts.{k}_proj.weight")
    else:
        ff = {k: get(f"{f}{k}_proj.weight") for k in ("gate", "up", "down")}
    w["ff"] = ff
    return w


class _hashable(dict):
    """The model's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layer(cfg, i, w, x, experts, select_rows):
    kind = cfg["layer_types"][i]
    h = rms_norm(x, w["norm1"], cfg["rms_norm_eps"])
    out, chosen, scores = latent_attention(cfg, kind, w["attn"], h,
                                           select_rows)
    x = x + out
    h = rms_norm(x, w["norm2"], cfg["rms_norm_eps"])
    if not is_sparse(cfg, i):
        f = w["ff"]
        return x + swiglu(f["gate"], f["up"], f["down"], h), None, None, \
            chosen, scores
    y, pick, top_e = moe(cfg, w["ff"], h, experts)
    return x + y, pick, top_e, chosen, scores


_layer_jit = jax.jit(_layer, static_argnums=(0, 1, 4))


def forward(cfg: Dict[str, Any], state: Mapping[str, Any], ids,
            want_rows: Optional[Sequence[int]] = None,
            precision: str = "highest",
            experts_held: Optional[Tuple[int, int]] = None,
            vocab_held: Optional[Tuple[int, int]] = None,
            select_rows: Optional[Sequence[int]] = None
            ) -> Dict[str, np.ndarray]:
    """One sequence through the whole model under the causal mask.  ``cfg``
    holds the PUBLISHED counts (``n_routed_experts`` the router's width,
    ``vocab_size``); ``ids`` are ids of the vocabulary held (0 = its first
    row).  Returns ``logits [rows, V held]`` at ``want_rows`` (every token if
    None), per expert layer the scores its choice was made by ``router_s
    [layers, T, E]`` and the ids chosen ``top_e [layers, T, k]``, and per
    full-attention layer what the indexer chose at ``select_rows``:
    ``selected [layers, n, T]`` (bool) and its scores ``index_scores
    [layers, n, T]``.  One layer's weights are in float32 at a time."""
    experts = tuple(experts_held or (0, cfg["n_routed_experts"]))
    first, count = vocab_held or (0, cfg["vocab_size"])
    rows = None if select_rows is None else \
        jnp.asarray(np.asarray(select_rows, np.int32))
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = _f32(np.asarray(
            state["model.embed_tokens.weight"])[first:first + count])
        x = embed[ids]
        del embed
        router_s, top_e, selected, scores = [], [], [], []
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, state, i, precision, experts)
            x, s, e, chosen, sc = _layer_jit(_hashable(cfg), i, w, x,
                                             experts, rows)
            if s is not None:
                router_s.append(np.asarray(s))
                top_e.append(np.asarray(e))
            if chosen is not None:
                selected.append(np.asarray(chosen))
                scores.append(np.asarray(sc))
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
    if selected:
        out.update(selected=np.stack(selected),
                   index_scores=np.stack(scores))
    return out
