"""The plain reference of the ``sdar_moe`` block (SDAR-30B-A3B-Chat,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat): straightforward
``jax.numpy`` in float32 under ``default_matmul_precision("highest")``, no
kernel, no cache, no batching.  It imports nothing of the program.

Equations (one sequence of ``T`` tokens, hidden ``H``):

- block: ``x <- x + Attn(RMSNorm(x))``, ``x <- x + MoE(RMSNorm(x))``; a final
  RMSNorm; an untied head.  RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``.
- Attn: ``q = x Wq -> [heads, D]``, ``k, v = x Wk, x Wv -> [kv_heads, D]``,
  RMSNorm over ``D`` on every head of q and k, RoPE (``rotate_half`` form,
  theta as published, no scaling) at the token's absolute position, each
  k/v head serves ``heads / kv_heads`` q heads,
  ``softmax(q k^T / sqrt(D) + mask) v``, then ``Wo``.  No bias.
- MoE: ``p = softmax(x Wr)`` over all experts, the ``k`` largest, weights
  ``p_e / sum_topk p`` (``norm_topk_prob``), ``y = sum_e w_e Wdown_e
  (silu(Wgate_e x) * Wup_e x)``.  Every expert is computed for every token
  and the unselected weighted by zero: the plainest form of the sum.
  ``experts_held = (first, count)`` leaves out what the experts outside
  that range would add (the chip's share of an expert-parallel layer); the
  router still scores all of them.
- Mask: the caller's ``visible[i, j]``.  ``block_causal(positions, L)`` is
  the model's own: key j is visible to query i iff ``j // L <= i // L``.
  ``replay_plan`` lays the forwards of one served block-diffusion
  generation out as ONE sequence under such a matrix (below).

Departures from the published code, each because this sandbox has no
network and the repository's ``generate.py`` could not be read again:

- the block length, the number of denoising steps, the confidence
  threshold, the mask token's id and greedy selection are inference-time
  settings, stated under ``assumed`` in the configuration's file;
- the logit row AT a masked position scores that position's token (no
  shift by one), as the model card's block-diffusion description has it;
- the published loop samples at temperature 1.0; here ``x0 = argmax`` so
  that a run repeats.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

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


def block_causal(positions: np.ndarray, block_length: int) -> np.ndarray:
    b = np.asarray(positions) // block_length
    return b[None, :] <= b[:, None]


def attention(cfg, w, x, positions, visible):
    T = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = (x @ w["q_proj"].T).reshape(T, nh, d)
    k = (x @ w["k_proj"].T).reshape(T, nkv, d)
    v = (x @ w["v_proj"].T).reshape(T, nkv, d)
    q = rope(rms_norm(q, w["q_norm"], cfg["rms_norm_eps"]), positions,
             cfg["rope_theta"])
    k = rope(rms_norm(k, w["k_norm"], cfg["rms_norm_eps"]), positions,
             cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    s = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(float(d))
    s = jnp.where(visible[None, :, :], s, NEG)
    out = jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, -1), v)
    return out.reshape(T, nh * d) @ w["o_proj"].T


def route(cfg, router_w, x):
    """(probabilities [T, E], ids of the top k [T, k], their weights)."""
    p = jax.nn.softmax(x @ router_w.T, -1)
    top_p, top_e = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return p, top_e, top_p


def moe(cfg, w, x, experts_held: Optional[Tuple[int, int]] = None):
    """``w["gate"|"up"]: [E, I, H]``, ``w["down"]: [E, H, I]`` as the
    checkpoint stores each expert's matrices, stacked."""
    p, top_e, top_p = route(cfg, w["router"], x)
    E = cfg["num_experts"]
    weights = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], top_e].set(top_p)
    if experts_held is not None:
        first, count = experts_held
        held = (jnp.arange(E) >= first) & (jnp.arange(E) < first + count)
        weights = weights * held[None, :]
    hidden = jax.nn.silu(jnp.einsum("th,eih->tei", x, w["gate"])) \
        * jnp.einsum("th,eih->tei", x, w["up"])
    y = jnp.einsum("tei,ehi->th", hidden * weights[:, :, None], w["down"])
    return y, p, top_e


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


def layer_weights(cfg, state: Mapping[str, Any], i: int, precision: str
                  ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Layer ``i`` of a checkpoint's state dict (the published names) in
    float32; ``state`` may load lazily, one tensor per access."""
    p = f"model.layers.{i}."
    get = lambda name: _lower(_f32(state[p + name]), precision)  # noqa: E731
    attn = {n: get(f"self_attn.{n}.weight")
            for n in ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
                      "k_norm")}
    E = cfg["num_experts"]
    experts = {kind: _lower(_f32(np.stack(
        [np.asarray(state[f"{p}mlp.experts.{e}.{kind}_proj.weight"])
         for e in range(E)])), precision)
               for kind in ("gate", "up", "down")}
    experts["router"] = get("mlp.gate.weight")
    return {"attn": attn, "moe": experts,
            "norm1": get("input_layernorm.weight"),
            "norm2": get("post_attention_layernorm.weight")}


def forward(cfg: Dict[str, Any], state: Mapping[str, Any], ids, positions,
            visible, want_rows: Optional[Sequence[int]] = None,
            precision: str = "highest",
            experts_held: Optional[Tuple[int, int]] = None
            ) -> Dict[str, np.ndarray]:
    """One sequence through the whole model.  Returns ``logits [rows, V]``
    at ``want_rows`` (every token if None), and per layer the router's
    probabilities ``router_p [layers, T, E]`` and the ids of its top k
    ``top_e [layers, T, k]``.  One layer's weights are in float32 at a
    time (a layer of the published model is 2.5 GB so)."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        visible = jnp.asarray(visible, bool)
        x = _f32(state["model.embed_tokens.weight"])[ids]
        router_p, top_e = [], []
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, state, i, precision)
            x, p, e = _layer_jit(_hashable(cfg), w, x, positions, visible,
                            experts_held)
            router_p.append(np.asarray(p))
            top_e.append(np.asarray(e))
            del w
        x = rms_norm(x, _f32(state["model.norm.weight"]),
                     cfg["rms_norm_eps"])
        if want_rows is not None:
            x = x[jnp.asarray(np.asarray(want_rows, np.int32))]
        head = _lower(_f32(state["lm_head.weight"]), precision)
        logits = np.asarray(x @ head.T)
    return {"logits": logits, "router_p": np.stack(router_p),
            "top_e": np.stack(top_e)}


class _hashable(dict):
    """The model's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layer(cfg, w, x, positions, visible, experts_held):
    x = x + attention(cfg, w["attn"],
                      rms_norm(x, w["norm1"], cfg["rms_norm_eps"]),
                      positions, visible)
    y, p, e = moe(cfg, w["moe"],
                  rms_norm(x, w["norm2"], cfg["rms_norm_eps"]),
                  experts_held)
    return x + y, p, e


_layer_jit = jax.jit(_layer, static_argnums=(0, 5))


# -- a served block-diffusion generation, replayed as one sequence ---------------


def replay_plan(prompt_ids: np.ndarray, forwards: List[Dict[str, Any]],
                block_length: int) -> Dict[str, Any]:
    """What one request's served generation asks of the model, as ONE
    sequence and a visibility matrix.

    ``forwards``: the trajectory's entries in order, each ``{"kind":
    "denoise" | "commit", "block": b, "tokens": [L]}`` — the block's state
    that went INTO that forward.  A commit's tokens are the block's final
    ones.  The prompt's whole blocks and every committed block, in order,
    are the committed sequence ``F``; under the block-causal mask a token
    of ``F`` sees ``F`` up to its own block's end, which is what the
    program's cache holds.  A denoise forward's ``L`` tokens stand at their
    block's positions, see ``F`` before their block and one another, and
    are seen by nobody else.  Returns ``ids``, ``positions``, ``visible``
    and ``rows[f]`` (the sequence rows of forward ``f``'s tokens)."""
    L = block_length
    base = len(prompt_ids) // L * L
    ids = [int(t) for t in prompt_ids[:base]]
    pos = list(range(base))
    group = [-1] * base  # -1: the committed sequence
    rows: List[List[int]] = []
    commits = [f for f in forwards if f["kind"] == "commit"]
    for n, f in enumerate(commits):
        assert f["block"] == n, "commits come in block order"
    committed_rows = {}
    for f in commits:
        start = len(ids)
        ids += [int(t) for t in f["tokens"]]
        pos += [base + f["block"] * L + j for j in range(L)]
        group += [-1] * L
        committed_rows[f["block"]] = list(range(start, start + L))
    for n, f in enumerate(forwards):
        if f["kind"] == "commit":
            rows.append(committed_rows[f["block"]])
            continue
        start = len(ids)
        ids += [int(t) for t in f["tokens"]]
        pos += [base + f["block"] * L + j for j in range(L)]
        group += [n] * L
        rows.append(list(range(start, start + L)))
    pos_a, group_a = np.asarray(pos), np.asarray(group)
    blk = pos_a // L
    key_committed = group_a[None, :] == -1
    same_group = group_a[None, :] == group_a[:, None]
    query_committed = group_a[:, None] == -1
    visible = np.where(
        query_committed,
        key_committed & (blk[None, :] <= blk[:, None]),
        (key_committed & (blk[None, :] < blk[:, None])) | same_group)
    return {"ids": np.asarray(ids, np.int32), "positions": pos_a,
            "visible": visible, "rows": rows}


def transfer(logits: np.ndarray, masked: np.ndarray, threshold: float,
             at_least: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One denoising step of one block: ``x0 = argmax``, confidence its
    softmax probability; the masked positions over ``threshold`` are
    filled, and never fewer than ``at_least`` of them (the most
    confident).  Returns (x0, confidence, filled)."""
    z = logits.astype(np.float64)
    lse = np.log(np.exp(z - z.max(-1, keepdims=True)).sum(-1)) + z.max(-1)
    x0 = z.argmax(-1)
    conf = np.exp(z.max(-1) - lse)
    conf_m = np.where(masked, conf, -np.inf)
    filled = masked & (conf_m > threshold)
    need = min(at_least, int(masked.sum()))
    if filled.sum() < need:
        order = np.argsort(-conf_m, kind="stable")
        filled = np.zeros_like(masked)
        filled[order[:need]] = True
    return x0, conf, filled
