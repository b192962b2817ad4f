"""The generative guard served by a latent-attention decoder that DRAFTS for
itself (``model_type: joyai_llm_flash``: DeepSeek-V3's layers — latent
attention in every layer, sparse experts behind a sigmoid router with a
selection bias beside a shared expert, a chip's share of the experts — and
its multi-token-prediction module as the drafter, so that a decode step
commits one or two tokens a row): the jailbreak family answered by
``engine.guard_classify``, whose wrapped call is ``generate``.  Everything
the benchmark knows of this family is here; the plain reference is
``chipbench/reference/joyai_llm_flash.py``.  The guard template, the
tokenizer's rules and the quantile draw of the weights are
``families/blockdiff_guard.py``'s, the comparison's helpers
``families/hybrid_ar_guard.py``'s, loaded by name.

The configuration's file gives the count of experts HELD here
(``n_routed_experts``: ``reduced``) and, under ``published``, the model's
own; ``held`` says which (``{"experts": [first, count]}``).  A checkpoint's
``config.json`` carries the published count (the router keeps its width),
its files the held experts under their published indices.  The layers held
are ``num_hidden_layers``; the module's tensors lie under that index, and
its embedding and head are the main model's, written once.

What is compared is what the program computed on the way to its tokens.  A
served result carries its trajectory (``models/generate.py``): an entry a
COMMITTED token — the chosen id, the top logits and the log-sum-exp at the
position that chose it, the experts chosen per layer, the drafter's block
last — and, on the entry of a step's first position, the draft the step
verified, whether it was accepted, and the drafter's top logits behind the
next draft.  The reference runs ONE causal forward over the prompt and the
served tokens, then its MTP module over the same sequence: prefill and then
two-position steps through both latent caches against the full forward.

``mtp_logit_rel_sq_err``, ``mtp_transfer_gap_max``,
``mtp_route_disagreement_share``
    as ``hybrid_ar_guard``'s ``ar_*`` three, at every committed position
    (the main logits come from the two-position steps as served; the
    drafter's block is one of the expert layers).
``mtp_draft_logit_rel_sq_err``
    the drafter's top logits and log-sum-exp behind every draft against the
    reference module's at the same position and ids.
``mtp_accept_disagreement_share``
    over the steps: the share whose accept bit is not the reference's
    (``the reference module's draft == the token chosen there``), counted
    where the reference module's best logit exceeds its second by more
    than ``accept_margin``.
"""

from __future__ import annotations

import json
import os
import types
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from chipbench import cells

base = cells.load_module("families", "blockdiff_guard")
hybrid = cells.load_module("families", "hybrid_ar_guard")

MODEL_KEYS = (
    "model_type", "attention_bias", "ep_size", "first_k_dense_replace",
    "head_dim", "hidden_act", "hidden_size", "intermediate_size",
    "kv_lora_rank", "max_position_embeddings", "moe_intermediate_size",
    "moe_layer_freq", "n_group", "n_routed_experts", "n_shared_experts",
    "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_nextn_predict_layers",
    "q_lora_rank", "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
    "rms_norm_eps", "rope_interleave", "rope_scaling", "rope_theta",
    "routed_scaling_factor", "scoring_func", "tie_word_embeddings",
    "topk_group", "topk_method", "v_head_dim", "vocab_size", "torch_dtype")

prompt_ids = base.prompt_ids


def published_model(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's numbers with the PUBLISHED count of experts: a
    checkpoint's ``config.json``, and what the reference is given beside
    the share."""
    model = dict(config["model"])
    model.update({k: v for k, v in (config.get("published") or {}).items()
                  if k == "n_routed_experts"})
    return model


def share(config: Dict[str, Any]) -> Tuple[int, int]:
    """``experts_held = (first, count)``."""
    m, held = config["model"], config.get("held") or {}
    experts = tuple(held.get("experts", (0, m["n_routed_experts"])))
    if experts[1] != m["n_routed_experts"]:
        raise SystemExit("chipbench: the configuration's held count is not "
                         "its n_routed_experts")
    return experts


# -- checkpoints from the seed ---------------------------------------------------


def shards(config: Dict[str, Any], seed: int) -> Iterator[Tuple[str, Any]]:
    """(file name, function that draws that file's tensors): one file for
    what stands outside the layers, one per layer, one for the MTP module;
    each from its own stream of the seed, so they can be drawn side by
    side.  ``weights`` in the configuration's file says how each scale was
    chosen."""
    m, a = config["model"], config["weights"]
    e_first, e_count = share(config)
    dtype = base._to_dtype(config)
    H, I = m["hidden_size"], m["moe_intermediate_size"]
    E = published_model(config)["n_routed_experts"]
    heads, r_q, r_kv = (m["num_attention_heads"], m["q_lora_rank"],
                        m["kv_lora_rank"])
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    n_layers = m["num_hidden_layers"]
    n_files = n_layers + 1 + m["num_nextn_predict_layers"]
    normal = base._normal
    std = a["std"]

    def gains(rng, n: int) -> np.ndarray:
        """A norm's weights: 1 + N(0, norm_std), each its own (all 1 and a
        norm of an already normed vector would do nothing: the module's
        hnorm reads what the final norm wrote)."""
        return (1.0 + a["norm_std"] * rng.standard_normal(n)).astype(dtype)

    def outside() -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x10a1, 0])
        return {"model.embed_tokens.weight": normal(
                    rng, dtype, a["embed_std"], m["vocab_size"], H),
                "lm_head.weight": normal(rng, dtype, a["head_std"],
                                         m["vocab_size"], H),
                "model.norm.weight": gains(rng, H)}

    def block(rng, i: int) -> Dict[str, np.ndarray]:
        """A layer's norms, attention and feed-forward (the MTP module's
        block, ``i == num_hidden_layers``, is an expert layer)."""
        p = f"model.layers.{i}."
        out = {p + "input_layernorm.weight": gains(rng, H),
               p + "post_attention_layernorm.weight": gains(rng, H)}
        s = p + "self_attn."
        out[s + "q_a_layernorm.weight"] = gains(rng, r_q)
        out[s + "kv_a_layernorm.weight"] = gains(rng, r_kv)
        for name, rows, cols, scale in (
                ("q_a_proj", r_q, H, 1.0),
                ("q_b_proj", heads * (nope + rope), r_q, a["q_b_gain"]),
                ("kv_a_proj_with_mqa", r_kv + rope, H, 1.0),
                ("kv_b_proj", heads * (nope + v), r_kv, 1.0),
                ("o_proj", H, heads * v, a["o_gain"])):
            out[f"{s}{name}.weight"] = normal(rng, dtype, std * scale, rows,
                                              cols)
        f = p + "mlp."
        if i < m["first_k_dense_replace"]:
            W = m["intermediate_size"]
            for k, rows, cols in (("gate", W, H), ("up", W, H),
                                  ("down", H, W)):
                out[f"{f}{k}_proj.weight"] = normal(rng, dtype, std, rows,
                                                    cols)
            return out
        # as hybrid_ar_guard's router: a row's own scale makes some experts
        # chosen more often; the selection bias is of the order of the gaps
        # between neighbouring scores
        scale = np.exp(a["router_row_log_std"] * rng.standard_normal(E))
        router = normal(rng, np.float32, a["router_std"], E, H)
        out[f + "gate.weight"] = (router * scale[:, None]).astype(dtype)
        out[f + "gate.e_score_correction_bias"] = (
            a["expert_bias_std"] * rng.standard_normal(E)).astype(np.float32)
        Is = I * m["n_shared_experts"]
        for k, rows, cols in (("gate", Is, H), ("up", Is, H),
                              ("down", H, Is)):
            out[f"{f}shared_experts.{k}_proj.weight"] = normal(
                rng, dtype, std, rows, cols)
        experts = normal(rng, dtype, std, e_count, 3, I * H)
        for n in range(e_count):
            q = f"{f}experts.{e_first + n}."
            out[q + "gate_proj.weight"] = experts[n, 0].reshape(I, H)
            out[q + "up_proj.weight"] = experts[n, 1].reshape(I, H)
            out[q + "down_proj.weight"] = experts[n, 2].reshape(H, I)
        return out

    def layer(i: int) -> Dict[str, np.ndarray]:
        return block(np.random.default_rng([int(seed), 0x10a1, i + 1]), i)

    def module() -> Dict[str, np.ndarray]:
        """The MTP module: ``W_eh``'s embedding half is ``eh_embed_gain * I``
        plus the draw, so that the drafter, like the main model, reads "the
        token, perturbed by context" (``weights`` in the file)."""
        rng = np.random.default_rng([int(seed), 0x10a1, n_layers + 1])
        p = f"model.layers.{n_layers}."
        eh = normal(rng, np.float32, a["eh_std"], H, 2 * H)
        eh[:, :H] += a["eh_embed_gain"] * np.eye(H, dtype=np.float32)
        return {p + "enorm.weight": gains(rng, H),
                p + "hnorm.weight": gains(rng, H),
                p + "eh_proj.weight": eh.astype(dtype),
                p + "shared_head.norm.weight": gains(rng, H),
                **block(rng, n_layers)}

    yield f"model-00001-of-{n_files:05d}.safetensors", outside
    for i in range(n_layers):
        yield (f"model-{i + 2:05d}-of-{n_files:05d}.safetensors",
               lambda i=i: layer(i))
    if m["num_nextn_predict_layers"]:
        yield f"model-{n_files:05d}-of-{n_files:05d}.safetensors", module


def generate_state(config: Dict[str, Any], seed: int
                   ) -> Dict[str, np.ndarray]:
    """Every tensor in one dict (toy sizes and tests)."""
    state: Dict[str, np.ndarray] = {}
    for _, draw in shards(config, seed):
        state.update(draw())
    return state


def _needs_the_decoder() -> None:
    """This family serves ``model_type: joyai_llm_flash``; a program
    without that decoder cannot run its cell, and says so before anything
    is built."""
    try:
        from semantic_router_tpu.models import joyai_llm_flash  # noqa: F401
    except ImportError:
        raise SystemExit(
            "chipbench: families/latent_mtp_ar_guard.py needs a program "
            "that serves model_type joyai_llm_flash (semantic_router_tpu."
            "models.joyai_llm_flash); this program does not")


def write_checkpoints(root: str, config: Dict[str, Any], seed: int
                      ) -> Dict[str, str]:
    """Sharded safetensors in the model's dtype under the published names,
    ``config.json`` with the published count of experts, and a WordLevel
    tokenizer of the whole vocabulary."""
    _needs_the_decoder()
    from safetensors.numpy import save_file
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    dirs = {t: os.path.join(root, t) for t in config["tasks"]}
    dirs["tokenizer"] = os.path.join(root, "tokenizer")

    def write(task_dir: str, name: str, draw) -> Dict[str, str]:
        tensors = draw()
        save_file(tensors, os.path.join(task_dir, name))
        return {k: name for k in tensors}

    for task in config["tasks"]:
        os.makedirs(dirs[task], exist_ok=True)
        with ThreadPoolExecutor(config["weights"]["writer_threads"]) as pool:
            maps = list(pool.map(lambda s: write(dirs[task], *s),
                                 shards(config, seed)))
        weight_map = {k: v for m in maps for k, v in m.items()}
        with open(os.path.join(dirs[task], "model.safetensors.index.json"),
                  "w") as f:
            json.dump({"metadata": {}, "weight_map": weight_map}, f)
        with open(os.path.join(dirs[task], "config.json"), "w") as f:
            json.dump(published_model(config), f)
    n_vocab = config["model"]["vocab_size"]
    own = base.template_ids(n_vocab)
    taken = set(own.values())
    vocab = {"[PAD]": 0, "[UNK]": base.UNK, **own}
    vocab.update({f"w{i}": i for i in range(2, n_vocab) if i not in taken})
    tok = Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    os.makedirs(dirs["tokenizer"], exist_ok=True)
    tok.save(os.path.join(dirs["tokenizer"], "tokenizer.json"))
    return dirs


# -- the system: warm-up, and the engine's public calls ----------------------------

warm = hybrid.warm
ENGINE_CALLS = base.ENGINE_CALLS


# -- the comparison with the plain reference ---------------------------------------

_lse = hybrid._lse


def _row_of(traj) -> Dict[int, int]:
    """Position -> its row among the reference's outputs (an entry a
    committed token, in order)."""
    return {int(e["position"]): f for f, e in enumerate(traj)}


def steps_of(traj) -> List[Dict[str, Any]]:
    """The entries of the steps' first positions (they carry ``drafted``
    and ``accepted``)."""
    return [e for e in traj if "accepted" in e]


class Reference:
    def __init__(self, config: Dict[str, Any], states: Dict[str, Any]
                 ) -> None:
        self.config, self.states = config, states
        self.model = published_model(config)
        self.experts = share(config)
        self.ref = cells.load_module("reference", "joyai_llm_flash")

    @classmethod
    def from_checkpoints(cls, config, ckpt_dirs) -> "Reference":
        # the closed system's parameters are still on the device (PERF.md
        # section 7); a layer of the reference in float32 beside them
        # leaves no room
        cells.load_module("families", "sparse_latent_ar_guard") \
            ._free_the_device()
        return cls(config, {t: base._Checkpoint(ckpt_dirs[t])
                            for t in config["tasks"]})

    def outputs(self, request, shapes, answers, precision: str = "highest"
                ) -> Dict[str, Dict[str, Any]]:
        """Per task: one causal forward over the prompt and the served
        tokens but the last, and the MTP module over the same sequence (its
        last position reads the last served token).  ``logits`` and
        ``draft_logits [entries, V]`` at the positions that chose a token,
        ``router_s`` / ``top_e`` at every position, the module's block
        last."""
        out = {}
        for task in self.config["tasks"]:
            if task not in answers:
                continue
            traj = answers[task].trajectory
            prompt = prompt_ids(request.text,
                                self.config["model"]["vocab_size"])
            if traj[0]["position"] != len(prompt) - 1:
                raise RuntimeError(
                    f"the program read {traj[0]['position'] + 1} prompt "
                    f"tokens where the reference reads {len(prompt)}")
            rows = [e["position"] for e in traj]
            if rows != list(range(rows[0], rows[0] + len(rows))):
                raise RuntimeError("a trajectory's committed positions are "
                                   f"not one after another: {rows}")
            served = [e["token"] for e in traj]
            ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            out[task] = self.ref.forward(
                self.model, self.states[task], ids, rows, precision,
                experts_held=self.experts, next_token=served[-1])
        return out

    def answers(self, request, shapes, answers, precision: str
                ) -> Dict[str, Any]:
        """The control: the same trajectory's inputs, with what the LOWER
        precision computes for them in the program's place — its logits at
        the served ids, its best token, its experts, its drafts' logits
        and its own accept bits (its draft against its own choice)."""
        out = {}
        raw = self.outputs(request, shapes, answers, precision)
        for task, low in raw.items():
            src = answers[task].trajectory
            row_of = _row_of(src)
            traj = []
            for f, e in enumerate(src):
                z = low["logits"][f].astype(np.float64)
                at = e["position"]
                rows = slice(0, at + 1) if e["kind"] == "prefill" \
                    else slice(at, at + 1)
                new = dict(e, token=int(z.argmax()), lse=_lse(z),
                           top_logits=z[e["top_ids"].astype(np.int64)],
                           experts=low["top_e"][:, rows])
                if "draft" in e and e["draft"]["position"] in row_of:
                    d = e["draft"]
                    zd = low["draft_logits"][row_of[d["position"]]] \
                        .astype(np.float64)
                    new["draft"] = dict(
                        d, token=int(zd.argmax()), lse=_lse(zd),
                        top_logits=zd[d["top_ids"].astype(np.int64)])
                if "accepted" in e:
                    before = low["draft_logits"][row_of[at - 1]]
                    new["drafted"] = int(before.argmax())
                    new["accepted"] = new["drafted"] == new["token"]
                traj.append(new)
            out[task] = types.SimpleNamespace(trajectory=traj)
        return out


def compare(config: Dict[str, Any], request, answers: Dict[str, Any],
            raw: Dict[str, Dict[str, Any]]
            ) -> Dict[str, Tuple[float, float]]:
    parts = {"mtp_" + k[3:]: v for k, v in hybrid.compare(
        config, request, answers, raw).items()}
    num = den = 0.0
    differ = counted = 0
    margin = config["accept_margin"]
    for task, ref in raw.items():
        traj = answers[task].trajectory
        row_of = _row_of(traj)
        for e in traj:
            d = e.get("draft")
            if d is not None and d["position"] in row_of:
                z = ref["draft_logits"][row_of[d["position"]]] \
                    .astype(np.float64)
                have = np.append(np.asarray(d["top_logits"], np.float64),
                                 float(d["lse"]))
                want = np.append(z[d["top_ids"].astype(np.int64)], _lse(z))
                num += float(((have - want) ** 2).sum())
                den += float((want ** 2).sum())
        for e in steps_of(traj):
            z = ref["draft_logits"][row_of[e["position"] - 1]]
            best, second = np.sort(z)[-1:-3:-1]
            if best - second > margin:
                counted += 1
                differ += (int(z.argmax()) == int(e["token"])) \
                    != bool(e["accepted"])
    if den:
        parts["mtp_draft_logit_rel_sq_err"] = (num, den)
    if counted:
        parts["mtp_accept_disagreement_share"] = (float(differ),
                                                  float(counted))
    return parts


def finish(total: Dict[str, Tuple[float, float]]) -> Dict[str, float]:
    numbers = {k: s / w for k, (s, w) in total.items() if w}
    for name in ("route", "accept"):
        key = f"mtp_{name}_disagreement_share"
        if key in total:
            numbers[f"mtp_{name}_counted"] = total[key][1]
    return numbers


def expected_numbers(config: Dict[str, Any]) -> List[str]:
    return ["mtp_logit_rel_sq_err", "mtp_draft_logit_rel_sq_err",
            "mtp_transfer_gap_max", "mtp_route_disagreement_share",
            "mtp_accept_disagreement_share"]
