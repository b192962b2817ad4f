"""The generative guard served by a dense decoder of linear-attention and full
layers (``model_type: olmo_hybrid``: three gated-delta-rule layers over a
float32 matrix state a head to one full-attention layer over a K/V cache, a
SwiGLU in every layer, the norm on each sub-layer's output): the jailbreak
family answered by ``engine.guard_classify``, whose wrapped call is
``generate``.  Everything the benchmark knows of this family is here; the
plain reference is ``chipbench/reference/olmo_hybrid.py``.  The guard
template, the tokenizer's rules and the quantile draw of the weights are
``families/blockdiff_guard.py``'s, the warm-up and the log-sum-exp
``families/hybrid_ar_guard.py``'s, the device's release before the reference
``families/sparse_latent_ar_guard.py``'s, loaded by name.

What is compared is what the program computed on the way to its tokens, as
``hybrid_ar_guard`` compares it, less its route number (a dense model routes
nothing): the reference runs ONE causal forward over the prompt and the
served tokens, the linear layers a token at a time — the chunked scan of the
prefill and then the loop's steps through the three kinds of cache against
the plain recurrence.

``lin_logit_rel_sq_err``
    at every position that chose a token, the program's top logits and
    log-sum-exp against the reference's at the same ids: sum of squared
    differences over the sum of the reference's squares.
``lin_transfer_gap_max``
    how far the reference's logit of the served token lies below the
    reference's best there; the widest of a request, averaged over the
    requests compared.
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
    "hidden_act", "max_position_embeddings", "attention_bias",
    "rms_norm_eps", "tie_word_embeddings", "layer_types",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "rope_parameters", "torch_dtype")

prompt_ids = base.prompt_ids


# -- checkpoints from the seed ---------------------------------------------------


def shards(config: Dict[str, Any], seed: int) -> Iterator[Tuple[str, Any]]:
    """(file name, function that draws that file's tensors): one file for
    what stands outside the layers, one per layer; each from its own stream
    of the seed, so they can be drawn side by side.  ``weights`` /
    ``assumed`` in the configuration's file say how each scale was chosen
    and which fault it exposes."""
    m, a = config["model"], config["weights"]
    dtype = base._to_dtype(config)
    H, W = m["hidden_size"], m["intermediate_size"]
    n, dk, dv = (m["linear_num_key_heads"], m["linear_key_head_dim"],
                 m["linear_value_head_dim"])
    taps = m["linear_conv_kernel_dim"]
    n_files = m["num_hidden_layers"] + 1
    normal = base._normal
    std = a["std"]

    def outside() -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x01f0, 0])
        V = m["vocab_size"]
        return {"model.embed_tokens.weight": normal(
                    rng, dtype, a["embed_std"], V, H),
                "lm_head.weight": normal(rng, dtype, a["head_std"], V, H),
                "model.norm.weight": np.ones(H, dtype)}

    def layer(i: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x01f0, i + 1])
        p = f"model.layers.{i}."
        out = {p + "post_attention_layernorm.weight": np.ones(H, dtype),
               p + "post_feedforward_layernorm.weight": np.ones(H, dtype)}
        for k, rows, cols in (("gate", W, H), ("up", W, H), ("down", H, W)):
            out[f"{p}mlp.{k}_proj.weight"] = normal(rng, dtype, std, rows,
                                                    cols)
        if m["layer_types"][i] == "full_attention":
            s = p + "self_attn."
            out[s + "q_norm.weight"] = np.full(H, a["qk_norm"], dtype)
            out[s + "k_norm.weight"] = np.full(H, a["qk_norm"], dtype)
            for k in "qkvo":
                out[f"{s}{k}_proj.weight"] = normal(rng, dtype, std, H, H)
            return out
        s = p + "linear_attn."
        for k, rows in (("q", n * dk), ("k", n * dk), ("v", n * dv),
                        ("g", n * dv)):
            out[f"{s}{k}_proj.weight"] = normal(rng, dtype, std, rows, H)
            if k != "g":
                out[f"{s}{k}_conv1d.weight"] = normal(
                    rng, dtype, a["conv_std"], rows, 1, taps)
        out[s + "o_proj.weight"] = normal(rng, dtype, std, H, n * dv)
        # the stream that enters layer i is the embedding plus 2 i outputs
        # of unit RMS: the a and b projections are scaled by it, so that
        # their outputs' deviations are the ones asked for at every depth
        stream = np.sqrt(a["embed_std"] ** 2 + 2.0 * i) * np.sqrt(H)
        out[s + "a_proj.weight"] = normal(rng, dtype, a["a_dev"] / stream,
                                          n, H)
        out[s + "b_proj.weight"] = normal(rng, dtype, a["b_dev"] / stream,
                                          n, H)
        # a head's decay a token: -log(alpha) log-uniform over the heads
        lo, hi = a["decay_min"], a["decay_max"]
        target = np.exp(rng.uniform(np.log(lo), np.log(hi), n))
        dt_bias = rng.uniform(-1.0, 1.0, n)
        out[s + "dt_bias"] = dt_bias.astype(np.float32)
        out[s + "A_log"] = np.log(
            target / np.log1p(np.exp(dt_bias))).astype(np.float32)
        out[s + "o_norm.weight"] = np.ones(dv, dtype)
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
    """This family serves ``model_type: olmo_hybrid``; a program without
    that decoder cannot run its cell, and says so before anything is
    built."""
    try:
        from semantic_router_tpu.models import olmo_hybrid  # noqa: F401
    except ImportError:
        raise SystemExit(
            "chipbench: families/linear_attn_ar_guard.py needs a program "
            "that serves model_type olmo_hybrid (semantic_router_tpu.models."
            "olmo_hybrid); this program does not")


def write_checkpoints(root: str, config: Dict[str, Any], seed: int
                      ) -> Dict[str, str]:
    """Sharded safetensors in the model's dtype under the tensor names the
    reference lists, ``config.json``, and a WordLevel tokenizer of the whole
    vocabulary."""
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
            json.dump(config["model"], f)
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
        self.ref = cells.load_module("reference", "olmo_hybrid")

    @classmethod
    def from_checkpoints(cls, config, ckpt_dirs) -> "Reference":
        sparse._free_the_device()  # 10 GB of the program's are still there
        return cls(config, {t: base._Checkpoint(ckpt_dirs[t])
                            for t in config["tasks"]})

    def outputs(self, request, shapes, answers, precision: str = "highest"
                ) -> Dict[str, Dict[str, Any]]:
        """Per task: one causal forward over the prompt and the served
        tokens but the last; ``logits [forwards, V]`` at the positions that
        chose a token."""
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
                self.config["model"], self.states[task], ids,
                [e["position"] for e in traj], precision)
        return out

    def answers(self, request, shapes, answers, precision: str
                ) -> Dict[str, Any]:
        """A control: the same trajectory's inputs, with what the reference
        at ``precision`` computes for them in the program's place — its
        logits at the served ids, its log-sum-exp, its best token."""
        out = {}
        raw = self.outputs(request, shapes, answers, precision)
        for task, low in raw.items():
            traj = []
            for f, e in enumerate(answers[task].trajectory):
                z = low["logits"][f].astype(np.float64)
                traj.append(dict(
                    e, token=int(z.argmax()), lse=_lse(z),
                    top_logits=z[e["top_ids"].astype(np.int64)]))
            out[task] = types.SimpleNamespace(trajectory=traj)
        return out


def compare(config: Dict[str, Any], request, answers: Dict[str, Any],
            raw: Dict[str, Dict[str, Any]]
            ) -> Dict[str, Tuple[float, float]]:
    num = den = gap = 0.0
    for task, ref in raw.items():
        for f, e in enumerate(answers[task].trajectory):
            z = ref["logits"][f].astype(np.float64)  # [V]
            have = np.append(np.asarray(e["top_logits"], np.float64),
                             float(e["lse"]))
            want = np.append(z[e["top_ids"].astype(np.int64)], _lse(z))
            num += float(((have - want) ** 2).sum())
            den += float((want ** 2).sum())
            gap = max(gap, float(z.max() - z[int(e["token"])]))
    if not den:
        return {}
    return {"lin_logit_rel_sq_err": (num, den),
            "lin_transfer_gap_max": (gap, 1.0)}


def finish(total: Dict[str, Tuple[float, float]]) -> Dict[str, float]:
    return {k: s / w for k, (s, w) in total.items() if w}


def expected_numbers(config: Dict[str, Any]) -> List[str]:
    return ["lin_logit_rel_sq_err", "lin_transfer_gap_max"]
