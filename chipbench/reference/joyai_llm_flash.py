"""The plain reference of the ``joyai_llm_flash`` decoder (JoyAI-LLM-Flash,
48B-A2.7B, https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json)
WITH its multi-token-prediction module: straightforward ``jax.numpy`` in
float32 under ``default_matmul_precision("highest")``, no kernel, no cache,
no batching: ONE causal forward over one sequence, one head and one expert
at a time, then the MTP module over the same sequence.  It imports nothing
of the program.

Equations (one sequence of ``T`` tokens ``t_0 .. t_{T-1}``; ``RMSNorm(x) = x
* rsqrt(mean(x^2) + rms_norm_eps) * w``; ``H`` = ``hidden_size``).  The
layers are DeepSeek-V3's.  Every layer: ``x <- x + Attn(RMSNorm_in(x))``,
``x <- x + FF(RMSNorm_post(x))``; after the last ``h_i = RMSNorm_final(x_i)``
and an untied head: ``L_i = W_head h_i``, the logits for ``t_{i+1}``.

*Latent attention*, every layer (``num_attention_heads`` heads):
- ``c_q = RMSNorm(W_qa h)`` (``q_lora_rank``); ``q_i = W_qb,i c_q = [q_i^n
  (qk_nope_head_dim) ; q_i^r (qk_rope_head_dim)]``;
- ``[c_kv' (kv_lora_rank) ; k^r' (qk_rope_head_dim)] = W_kva h``; ``c_kv =
  RMSNorm(c_kv')``; ``k^r`` is one for all heads;
- RoPE at ``rope_theta``, no scaling, on ``q_i^r`` and ``k^r`` with
  INTERLEAVED pairing (``rope_interleave``): dims ``(2j, 2j + 1)`` turn by
  ``pos * theta^(-2j / d)``;
- ``[k_i^n ; v_i (v_head_dim)] = W_kvb,i c_kv``;
- ``o_i(t) = sum_{s <= t} softmax_s((q_i^n(t) . k_i^n(s) + q_i^r(t) .
  k^r(s)) / sqrt(nope + rope)) v_i(s)``; ``Attn(h) = W_o [o_i]``.

*Feed-forward*: the first ``first_k_dense_replace`` layers a dense SwiGLU of
``intermediate_size``; the others ``s = sigmoid(W_r h)`` over
``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b``
(``noaux_tc`` with ``n_group`` 1, ``topk_group`` 1: no group limit; ``b`` =
``e_score_correction_bias``, which moves the choice and never the weights);
weights ``s_chosen / (sum s_chosen + 1e-20) * routed_scaling_factor``;
SwiGLU experts of ``moe_intermediate_size``, plus ``n_shared_experts``
shared ones (one SwiGLU of ``n_shared_experts * moe_intermediate_size``)
added unweighted.  ``experts_held = (first, count)``: only those experts'
parts are added (a chip's share; the shared expert is every chip's).

*The MTP module* (``num_nextn_predict_layers`` 1; tensors under
``model.layers.<num_hidden_layers>.``): ``h'_i = W_eh [RMSNorm_e(Emb(t_{i+1}))
; RMSNorm_h(h_i)]`` (``2H -> H``), one block of the kind of the expert
layers (its own latent attention over ``h'``, causal, and its own experts),
``RMSNorm_shared_head`` and the MAIN model's head: ``D_i``, the logits for
``t_{i+2}``.  Embedding and head are the main model's tensors.

Assumed (``configs/joyai-flash-guard/model.json`` repeats these): the order
of ``W_eh``'s halves (embedding first: DeepSeek-V3's public code); ``h_i``
is taken AFTER the final norm (what the head reads); the tensor names
(DeepSeek-V3's: ``self_attn.{q_a_proj, q_a_layernorm, q_b_proj,
kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}``, ``mlp.gate`` (+
``e_score_correction_bias``), ``mlp.experts.<e>.{gate,up,down}_proj``,
``mlp.shared_experts.*``; the module's ``enorm``, ``hnorm``, ``eh_proj``,
``shared_head.norm``); ``route_eps`` 1e-20.

``accept_walk`` derives, from a served sequence and this reference's own
drafts, which drafts a self-drafting loop that commits one token a step
plus one more where the draft was right accepts, and how many steps it
takes.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
ROUTE_EPS = 1e-20


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """``x [T, ..., D]`` rotated at ``positions [T]``, pairs ``(2j, 2j +
    1)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def latent_attention(cfg, w, h):
    """``Attn(h)`` of one layer, one head at a time (a head's ``[T, T]``
    scores are the largest thing held)."""
    T = h.shape[0]
    nh, nope, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r_kv, eps, theta = (cfg["kv_lora_rank"], cfg["rms_norm_eps"],
                        cfg["rope_theta"])
    at = jnp.arange(T)
    c_q = rms_norm(h @ w["q_a"].T, w["q_a_norm"], eps)
    q = (c_q @ w["q_b"].T).reshape(T, nh, nope + dr)
    q_rope = rope(q[..., nope:], at, theta)
    kv = h @ w["kv_a"].T
    c_kv = rms_norm(kv[:, :r_kv], w["kv_a_norm"], eps)
    k_rope = rope(kv[:, r_kv:], at, theta)  # [T, dr]
    kvb = (c_kv @ w["kv_b"].T).reshape(T, nh, nope + dv)
    seen = at[None, :] <= at[:, None]
    scale = 1.0 / np.sqrt(float(nope + dr))

    def head(args):
        qn, qr, kn, v = args
        s = jnp.where(seen, (qn @ kn.T + qr @ k_rope.T) * scale, NEG)
        return jax.nn.softmax(s, -1) @ v

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (
        q[..., :nope], q_rope, kvb[..., :nope], kvb[..., nope:])))
    return jnp.moveaxis(out, 0, 1).reshape(T, nh * dv) @ w["o"].T


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
    first, count = experts
    weights = jnp.zeros((x.shape[0], cfg["n_routed_experts"]), jnp.float32) \
        .at[jnp.arange(x.shape[0])[:, None], top_e].set(top_w)

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
    """The MTP module's block (``i == num_hidden_layers``) is an expert
    layer too."""
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
            "o": get(a + "o_proj.weight")}
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


def mtp_weights(cfg, state: Mapping[str, Any], precision: str
                ) -> Dict[str, Any]:
    """What the MTP module has besides its block."""
    p = f"model.layers.{cfg['num_hidden_layers']}."
    return {"enorm": _f32(state[p + "enorm.weight"]),
            "hnorm": _f32(state[p + "hnorm.weight"]),
            "eh_proj": _lower(_f32(state[p + "eh_proj.weight"]), precision),
            "norm": _f32(state[p + "shared_head.norm.weight"])}


class _hashable(dict):
    """The model's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layer(cfg, sparse: bool, w, x, experts):
    """One layer; ``sparse`` (not the layer's index: the expert layers and
    the module's block are then ONE compiled program a length)."""
    h = rms_norm(x, w["norm1"], cfg["rms_norm_eps"])
    x = x + latent_attention(cfg, w["attn"], h)
    h = rms_norm(x, w["norm2"], cfg["rms_norm_eps"])
    if not sparse:
        f = w["ff"]
        return x + swiglu(f["gate"], f["up"], f["down"], h), None, None
    y, pick, top_e = moe(cfg, w["ff"], h, experts)
    return x + y, pick, top_e


_layer_jit = jax.jit(_layer, static_argnums=(0, 1, 4))


def mtp_input(cfg, w, embed_next, h):
    """``h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]``."""
    eps = cfg["rms_norm_eps"]
    return jnp.concatenate([rms_norm(embed_next, w["enorm"], eps),
                            rms_norm(h, w["hnorm"], eps)], -1) \
        @ w["eh_proj"].T


def forward(cfg: Dict[str, Any], state: Mapping[str, Any], ids,
            want_rows: Optional[Sequence[int]] = None,
            precision: str = "highest",
            experts_held: Optional[Tuple[int, int]] = None,
            next_token: Optional[int] = None) -> Dict[str, np.ndarray]:
    """One sequence through the held model under the causal mask, then the
    MTP module over it.  ``cfg`` holds the PUBLISHED count of experts
    (``n_routed_experts``, the router's width) and the layers held
    (``num_hidden_layers``; the module's tensors lie under that index).
    Returns ``logits [rows, V]`` at ``want_rows`` (every token if None) and
    per expert layer the scores its choice was made by ``router_s [layers,
    T, E]`` and the ids chosen ``top_e [layers, T, k]``.  With a module
    (``num_nextn_predict_layers``) also ``draft_logits [rows, V]`` at the
    same rows — row ``i`` reads ``(h_i, t_{i+1})`` and gives the logits for
    ``t_{i+2}``; the last position's ``t_T`` is ``next_token`` — and the
    module's block as one more row of ``router_s`` / ``top_e``.  One
    layer's weights are in float32 at a time."""
    experts = tuple(experts_held or (0, cfg["n_routed_experts"]))
    n_layers, eps = cfg["num_hidden_layers"], cfg["rms_norm_eps"]
    hcfg = _hashable(cfg)
    rows = None if want_rows is None else \
        jnp.asarray(np.asarray(want_rows, np.int32))
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = _f32(state["model.embed_tokens.weight"])
        x = embed[ids]
        router_s, top_e = [], []

        def keep(s, e):
            if s is not None:
                router_s.append(np.asarray(s))
                top_e.append(np.asarray(e))

        for i in range(n_layers):
            w = layer_weights(cfg, state, i, precision, experts)
            x, s, e = _layer_jit(hcfg, is_sparse(cfg, i), w, x, experts)
            keep(s, e)
            del w
        h = rms_norm(x, _f32(state["model.norm.weight"]), eps)
        head = _lower(_f32(state["lm_head.weight"]), precision)
        pick = (lambda a: a) if rows is None else (lambda a: a[rows])
        out = {"logits": np.asarray(pick(h) @ head.T)}
        if cfg.get("num_nextn_predict_layers", 0):
            if next_token is None:
                raise ValueError("the MTP module reads t_{i+1}: the token "
                                 "after the last is next_token")
            after = jnp.concatenate(
                [ids[1:], jnp.asarray([next_token], jnp.int32)])
            m = mtp_weights(cfg, state, precision)
            x = mtp_input(cfg, m, embed[after], h)
            w = layer_weights(cfg, state, n_layers, precision, experts)
            x, s, e = _layer_jit(hcfg, True, w, x, experts)
            keep(s, e)
            del w
            out["draft_logits"] = np.asarray(
                pick(rms_norm(x, m["norm"], eps)) @ head.T)
        del embed, head
    if router_s:
        out.update(router_s=np.stack(router_s), top_e=np.stack(top_e))
    return out


def accept_walk(tokens: Sequence[int], drafts: Mapping[int, int],
                n_prompt: int) -> Tuple[List[Tuple[int, bool]], int]:
    """The token-at-a-time walk of a self-drafting loop over a served
    sequence.  ``tokens`` = prompt then the generated tokens (``tokens[j]``
    stands at position ``j``); ``drafts[i]`` = the draft the module made at
    position ``i`` (the argmax of ``D_i``), which is for ``tokens[i + 2]``.
    After the prefill the last committed token stands at ``p = n_prompt``
    (not yet run) beside the draft for ``p + 1``.  A step runs ``p`` and the
    draft, commits ``tokens[p + 1]``, and, where the draft was it, one more:
    ``p`` advances by 1 or 2.  Returns ``([(p, accepted)] a step, steps)``
    until every generated token is committed."""
    last = len(tokens) - 1
    p, walk = n_prompt, []
    while p < last:
        accepted = int(drafts[p - 1]) == int(tokens[p + 1])
        walk.append((p, accepted))
        p += 1 + accepted
    return walk, len(walk)
