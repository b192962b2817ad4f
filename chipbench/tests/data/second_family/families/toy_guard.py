"""A second model family, brought as new files only: a toy dense Qwen3
serving the program's ``generative`` task kind — the jailbreak family
answered by ``engine.guard_classify``, which calls the wrapped ``generate``.

It proves that the harness's seam fits a generative family
(``chipbench/tests/test_second_family.py``); it is never a cell.  The
program gives out no logits from ``generate`` yet, so what is compared is
the greedy token ids, at the steps where the plain reference's top-two
margin exceeds the configuration's ``greedy_margin``.
"""

from __future__ import annotations

import functools
import json
import os
import re
import time
from typing import Any, Dict, List, Tuple

import numpy as np

MODEL_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
              "max_position_embeddings", "tie_word_embeddings",
              "eos_token_id")

UNK = 1
# the program's guard template (models/generate.py build_guard_prompt, role
# "user"), copied: the reference tokenizes it without the program
BEFORE = ("You are a safety classifier. Classify the user message below.\n"
          "Respond in EXACTLY this format:\n"
          "Safety: Safe, Unsafe, or Controversial\n"
          "Categories: comma-separated categories, or None\n"
          "\nuser message:\n")
AFTER = "\n\nClassification:\n"
# the tokenizer's Whitespace pre-tokenizer
PIECES = re.compile(r"\w+|[^\w\s]+")


def prompt_ids(text: str) -> np.ndarray:
    """Token ids of the guard prompt around ``text``: ``w<id>`` is token
    ``id``, every other piece is [UNK]."""
    out = []
    for piece in PIECES.findall(BEFORE + text + AFTER):
        out.append(int(piece[1:]) if re.fullmatch(r"w\d+", piece) else UNK)
    return np.asarray(out, np.int32)


def request_text(prompt: str) -> str:
    """The request's text inside a guard prompt (how a call of ``generate``
    finds the route that caused it)."""
    if prompt.startswith(BEFORE) and prompt.endswith(AFTER):
        return prompt[len(BEFORE):-len(AFTER)]
    return prompt


# -- checkpoints from the seed ---------------------------------------------------


def generate_state(config: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    m = config["model"]
    rng = np.random.default_rng(seed)
    H, I, D = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]

    def normal(std, *shape):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    state = {"model.embed_tokens.weight": normal(1.0, m["vocab_size"], H),
             "model.norm.weight": np.ones(H, np.float32),
             "lm_head.weight": normal(0.05, m["vocab_size"], H)}
    for i in range(m["num_hidden_layers"]):
        p = f"model.layers.{i}."
        state.update({
            p + "input_layernorm.weight": np.ones(H, np.float32),
            p + "post_attention_layernorm.weight": np.ones(H, np.float32),
            p + "self_attn.q_proj.weight": normal(0.05, nh * D, H),
            p + "self_attn.k_proj.weight": normal(0.05, nkv * D, H),
            p + "self_attn.v_proj.weight": normal(0.05, nkv * D, H),
            p + "self_attn.o_proj.weight": normal(0.05, H, nh * D),
            p + "self_attn.q_norm.weight": np.ones(D, np.float32),
            p + "self_attn.k_norm.weight": np.ones(D, np.float32),
            p + "mlp.gate_proj.weight": normal(0.05, I, H),
            p + "mlp.up_proj.weight": normal(0.05, I, H),
            p + "mlp.down_proj.weight": normal(0.05, H, I)})
    return state


def write_checkpoints(root: str, config: Dict[str, Any], seed: int
                      ) -> Dict[str, str]:
    from safetensors.numpy import save_file
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    dirs = {t: os.path.join(root, t) for t in config["tasks"]}
    dirs["tokenizer"] = os.path.join(root, "tokenizer")
    for task in config["tasks"]:
        os.makedirs(dirs[task], exist_ok=True)
        save_file(generate_state(config, seed),
                  os.path.join(dirs[task], "model.safetensors"))
        with open(os.path.join(dirs[task], "config.json"), "w") as f:
            json.dump(config["model"], f)
    vocab = {"[PAD]": 0, "[UNK]": UNK}
    vocab.update({f"w{i}": i for i in range(2, config["model"]["vocab_size"])})
    tok = Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    os.makedirs(dirs["tokenizer"], exist_ok=True)
    tok.save(os.path.join(dirs["tokenizer"], "tokenizer.json"))
    return dirs


# -- the system: warm-up, and the engine's public calls ----------------------------


def warm(engine, config: Dict[str, Any], shapes: Dict[str, Any]) -> None:
    """One guard call per padded prompt length of the cell's shape set: the
    prefill and decode programs of ``(1, prompt_tokens, cache)``."""
    template = len(prompt_ids(""))
    for task in config["tasks"]:
        for padded in shapes["prompt_tokens"]:
            t = time.perf_counter()
            text = " ".join(["w2"] * (padded - template))
            engine.guard_classify(task, text,
                                  max_new_tokens=shapes["max_new_tokens"])
            print(f"warmup {task} prompt_tokens={padded} "
                  f"{time.perf_counter() - t:.2f} s", flush=True)


def _generate_arguments(task, prompts, *_, **__):
    return [task], [request_text(p) for p in prompts]


def _generate_answers(tasks, texts, out):
    for text, res in zip(texts, out):
        yield text, tasks[0], res


ENGINE_CALLS = {"generate": (_generate_arguments, _generate_answers)}


# -- the comparison with the plain reference ---------------------------------------


def served_tokens(config: Dict[str, Any], answer) -> List[int]:
    """What the program decoded, its end-of-sequence token with it."""
    eos = [config["model"]["eos_token_id"]] if answer.finished else []
    return [int(t) for t in answer.token_ids] + eos


class Reference:
    def __init__(self, config: Dict[str, Any],
                 states: Dict[str, Dict[str, np.ndarray]]) -> None:
        import jax

        from chipbench import cells

        self.config, self.states = config, states
        # (state, ids, precision) -> logits, the model's numbers bound
        self._forward = jax.jit(functools.partial(
            cells.load_module("reference", "toy_qwen3").forward,
            config["model"]), static_argnums=2)

    @classmethod
    def from_checkpoints(cls, config, ckpt_dirs) -> "Reference":
        from safetensors.numpy import load_file

        return cls(config, {t: load_file(os.path.join(
            ckpt_dirs[t], "model.safetensors")) for t in config["tasks"]})

    def outputs(self, request, shapes, answers, precision: str = "highest"
                ) -> Dict[str, np.ndarray]:
        """Per task, the logits ``[steps, vocab]`` that predict each served
        token: ONE forward over the prompt with its served tokens."""
        out = {}
        for task in self.config["tasks"]:
            if task not in answers:
                continue
            prompt = prompt_ids(request.text)
            served = served_tokens(self.config, answers[task])
            ids = np.concatenate([prompt, np.asarray(served, np.int32)])
            logits = np.asarray(self._forward(
                self.states[task], ids, precision), np.float32)
            out[task] = logits[len(prompt) - 1:len(ids) - 1]
        return out

    def answers(self, request, shapes, answers, precision: str
                ) -> Dict[str, Any]:
        """The control: the token the lower precision puts first at each
        position of the same prompt and served tokens."""
        import types

        raw = self.outputs(request, shapes, answers, precision)
        return {t: types.SimpleNamespace(
            token_ids=v.argmax(-1).tolist(), finished=False)
            for t, v in raw.items()}


def compare(config: Dict[str, Any], request, answers: Dict[str, Any],
            raw: Dict[str, np.ndarray]) -> Dict[str, Tuple[float, float]]:
    parts: Dict[str, Tuple[float, float]] = {}
    wrong = steps = 0
    for task, logits in raw.items():
        served = served_tokens(config, answers[task])
        top2 = np.sort(logits, -1)[:, -2:]
        sure = (top2[:, 1] - top2[:, 0]) > config["greedy_margin"]
        best = logits.argmax(-1)
        for i, tok in enumerate(served[:len(best)]):
            if sure[i]:
                steps += 1
                wrong += int(tok != best[i])
    if steps:
        parts["guard_greedy_token_mismatch_share"] = (float(wrong),
                                                      float(steps))
    return parts


def finish(total: Dict[str, Tuple[float, float]]) -> Dict[str, float]:
    numbers = {k: s / w for k, (s, w) in total.items() if w}
    if "guard_greedy_token_mismatch_share" in total:
        numbers["guard_steps_compared"] = \
            total["guard_greedy_token_mismatch_share"][1]
    return numbers


def expected_numbers(config: Dict[str, Any]) -> List[str]:
    return ["guard_greedy_token_mismatch_share"]
