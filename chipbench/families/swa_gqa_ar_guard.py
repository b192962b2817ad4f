"""The generative guard served by a decoder of window and full attention
layers (``model_type: laguna``: grouped-query attention in two geometries by
layer type — full layers over a whole K/V cache, sliding layers of another
head count over a ring — per-type RoPE, a sigmoid gate a head, sparse experts
behind a softmax router beside a gated shared expert; a chip's share of the
experts and of the vocabulary): the jailbreak family answered by
``engine.guard_classify``, whose wrapped call is ``generate``.  Everything the
benchmark knows of this family is here; the plain reference is
``chipbench/reference/laguna.py``.  The guard template, the tokenizer's rules
and the quantile draw of the weights are ``families/blockdiff_guard.py``'s,
the comparison ``families/hybrid_ar_guard.py``'s, the device's release before
the reference ``families/sparse_latent_ar_guard.py``'s, loaded by name.

The configuration's file gives the counts HELD here (``num_experts``,
``vocab_size``: both ``reduced``) and, under ``published``, the model's own;
``held`` says which (``{"experts": [first, count], "vocab": [first,
count]}``).  A checkpoint's ``config.json`` carries the published counts (the
router keeps its width), its files the held experts under their published
indices and the vocabulary's rows up to the end of the held slice; ids,
logits and the choice are over the slice.

What is compared is what the program computed on the way to its tokens, as
``hybrid_ar_guard`` compares it: the reference runs ONE causal forward over
the prompt and the served tokens, the window a mask — prefill and then the
loop's steps through the whole cache and the ring against the full forward
pass.  The numbers pool whatever requests the harness samples, short rows
(bucket 512) and long ones (8192) alike.

``mix_logit_rel_sq_err``, ``mix_transfer_gap_max``,
``mix_route_disagreement_share``
    as ``hybrid_ar_guard``'s ``ar_*`` three; the router's margin
    (``route_margin``) is in its LOGITS (the softmax keeps their order).
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
sparse = cells.load_module("families", "sparse_latent_ar_guard")

MODEL_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "max_position_embeddings", "attention_bias", "rms_norm_eps",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob",
    "decoder_sparse_step", "mlp_only_layers", "tie_word_embeddings", "gating",
    "sliding_window", "rope_parameters", "layer_types",
    "moe_apply_router_weight_on_input", "mlp_layer_types", "gating_types",
    "moe_routed_scaling_factor", "num_attention_heads_per_layer",
    "moe_router_logit_softcapping", "torch_dtype")
PUBLISHED_COUNTS = ("num_experts", "vocab_size")

prompt_ids = base.prompt_ids


def published_model(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's numbers with the PUBLISHED counts of experts and of the
    vocabulary: a checkpoint's ``config.json``, and what the reference is
    given beside the shares."""
    pub = config.get("published") or {}
    return dict(config["model"],
                **{k: pub[k] for k in PUBLISHED_COUNTS if k in pub})


def shares(config: Dict[str, Any]) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """``(experts_held, vocab_held)``, each ``(first, count)``."""
    m, held = config["model"], config.get("held") or {}
    experts = tuple(held.get("experts", (0, m["num_experts"])))
    vocab = tuple(held.get("vocab", (0, m["vocab_size"])))
    if experts[1] != m["num_experts"] or vocab[1] != m["vocab_size"]:
        raise SystemExit("chipbench: the configuration's held counts are "
                         "not its num_experts / vocab_size")
    return experts, vocab


# -- checkpoints from the seed ---------------------------------------------------

EXPERTS_A_DRAW = 16  # a layer's experts are drawn so many at a time


def shards(config: Dict[str, Any], seed: int) -> Iterator[Tuple[str, Any]]:
    """(file name, function that draws that file's tensors): one file for
    what stands outside the layers, one per layer; each from its own
    stream of the seed, so they can be drawn side by side.  ``weights`` in
    the configuration's file says how each scale was chosen."""
    m, a = config["model"], config["weights"]
    pub = published_model(config)
    (e_first, e_count), (v_first, v_count) = shares(config)
    dtype = base._to_dtype(config)
    H, D, nkv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    I, Is, E = (m["moe_intermediate_size"],
                m["shared_expert_intermediate_size"], pub["num_experts"])
    n_files = m["num_hidden_layers"] + 1
    normal = base._normal
    std = a["std"]

    def outside() -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x1a60, 0])
        rows = v_first + v_count  # up to the end of the slice held
        return {"model.embed_tokens.weight": normal(
                    rng, dtype, a["embed_std"], rows, H),
                "lm_head.weight": normal(rng, dtype, a["head_std"], rows, H),
                "model.norm.weight": np.ones(H, dtype)}

    def layer(i: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x1a60, i + 1])
        p = f"model.layers.{i}."
        nh = m["num_attention_heads_per_layer"][i]
        qk = a["qk_norm"][m["layer_types"][i]]
        out = {p + "input_layernorm.weight": np.ones(H, dtype),
               p + "post_attention_layernorm.weight": np.ones(H, dtype),
               p + "self_attn.q_norm.weight": np.full(D, qk, dtype),
               p + "self_attn.k_norm.weight": np.full(D, qk, dtype)}
        for name, rows, cols in (("q", nh * D, H), ("k", nkv * D, H),
                                 ("v", nkv * D, H), ("o", H, nh * D),
                                 ("g", nh, H)):
            out[f"{p}self_attn.{name}_proj.weight"] = normal(
                rng, dtype, std, rows, cols)
        f = p + "mlp."
        if m["mlp_layer_types"][i] == "dense":
            W = m["intermediate_size"]
            for k, rows, cols in (("gate", W, H), ("up", W, H),
                                  ("down", H, W)):
                out[f"{f}{k}_proj.weight"] = normal(rng, dtype, std, rows,
                                                    cols)
            return out
        # a row's own scale makes some experts' logits wider than others':
        # they are chosen more often, as in a trained router
        scale = np.exp(a["router_row_log_std"] * rng.standard_normal(E))
        router = normal(rng, np.float32, a["router_std"], E, H)
        out[f + "gate.weight"] = (router * scale[:, None]).astype(dtype)
        for k, rows, cols in (("gate", Is, H), ("up", Is, H),
                              ("down", H, Is)):
            out[f"{f}shared_expert.{k}_proj.weight"] = normal(
                rng, dtype, std, rows, cols)
        out[f + "shared_expert_gate.weight"] = normal(rng, dtype, std, 1, H)
        for n0 in range(0, e_count, EXPERTS_A_DRAW):
            n1 = min(e_count, n0 + EXPERTS_A_DRAW)
            experts = normal(rng, dtype, std, n1 - n0, 3, I * H)
            for n in range(n0, n1):
                q = f"{f}experts.{e_first + n}."
                out[q + "gate_proj.weight"] = experts[n - n0, 0].reshape(I, H)
                out[q + "up_proj.weight"] = experts[n - n0, 1].reshape(I, H)
                out[q + "down_proj.weight"] = experts[n - n0, 2].reshape(H, I)
        return out

    yield f"model-00001-of-{n_files:05d}.safetensors", outside
    for i in range(m["num_hidden_layers"]):
        yield (f"model-{i + 2:05d}-of-{n_files:05d}.safetensors",
               lambda i=i: layer(i))


def generate_state(config: Dict[str, Any], seed: int
                   ) -> Dict[str, np.ndarray]:
    """Every tensor in one dict (toy sizes and tests)."""
    state: Dict[str, np.ndarray] = {}
    for _, draw in shards(config, seed):
        state.update(draw())
    return state


def _needs_the_decoder() -> None:
    """This family serves ``model_type: laguna``; a program without that
    decoder cannot run its cell, and says so before anything is built."""
    try:
        from semantic_router_tpu.models import laguna  # noqa: F401
    except ImportError:
        raise SystemExit(
            "chipbench: families/swa_gqa_ar_guard.py needs a program that "
            "serves model_type laguna (semantic_router_tpu.models.laguna); "
            "this program does not")


def write_checkpoints(root: str, config: Dict[str, Any], seed: int
                      ) -> Dict[str, str]:
    """Sharded safetensors in the model's dtype under the tensor names the
    reference lists, ``config.json`` with the published counts, and a
    WordLevel tokenizer of the vocabulary held."""
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


class Reference:
    def __init__(self, config: Dict[str, Any], states: Dict[str, Any]
                 ) -> None:
        self.config, self.states = config, states
        self.model = published_model(config)
        self.experts, self.vocab = shares(config)
        self.ref = cells.load_module("reference", "laguna")

    @classmethod
    def from_checkpoints(cls, config, ckpt_dirs) -> "Reference":
        sparse._free_the_device()  # 11 GB of the program's are still there
        return cls(config, {t: base._Checkpoint(ckpt_dirs[t])
                            for t in config["tasks"]})

    def outputs(self, request, shapes, answers, precision: str = "highest"
                ) -> Dict[str, Dict[str, Any]]:
        """Per task: one causal forward over the prompt and the served
        tokens but the last.  ``logits [forwards, V]`` at the positions
        that chose a token, ``router_s [layers, T, E]`` (logits) and
        ``top_e [layers, T, k]`` at every position."""
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
            served = [e["token"] for e in traj]
            ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            out[task] = self.ref.forward(
                self.model, self.states[task], ids,
                [e["position"] for e in traj], precision,
                experts_held=self.experts, vocab_held=self.vocab)
        return out

    def answers(self, request, shapes, answers, precision: str
                ) -> Dict[str, Any]:
        """The control: the same trajectory's inputs, with what the LOWER
        precision computes for them in the program's place."""
        out = {}
        raw = self.outputs(request, shapes, answers, precision)
        for task, low in raw.items():
            traj = []
            for f, e in enumerate(answers[task].trajectory):
                z = low["logits"][f].astype(np.float64)
                at = e["position"]
                rows = slice(0, at + 1) if e["kind"] == "prefill" \
                    else slice(at, at + 1)
                traj.append(dict(
                    e, token=int(z.argmax()), lse=_lse(z),
                    top_logits=z[e["top_ids"].astype(np.int64)],
                    experts=low["top_e"][:, rows]))
            out[task] = types.SimpleNamespace(trajectory=traj)
        return out


def compare(config: Dict[str, Any], request, answers: Dict[str, Any],
            raw: Dict[str, Dict[str, Any]]
            ) -> Dict[str, Tuple[float, float]]:
    return {"mix_" + k[3:]: v for k, v in hybrid.compare(
        config, request, answers, raw).items()}


def finish(total: Dict[str, Tuple[float, float]]) -> Dict[str, float]:
    numbers = {k: s / w for k, (s, w) in total.items() if w}
    if "mix_route_disagreement_share" in total:
        numbers["mix_route_pairs_counted"] = \
            total["mix_route_disagreement_share"][1]
    return numbers


def expected_numbers(config: Dict[str, Any]) -> List[str]:
    return ["mix_logit_rel_sq_err", "mix_transfer_gap_max",
            "mix_route_disagreement_share"]
