"""Seeded checkpoints for a configuration, in the form the program loads:
HF-style directories (``model.safetensors`` + ``config.json``) and one
WordLevel ``tokenizer.json``.

A copy of ``chip_smoke.py``'s generator (listed in PERF.md for a later PR
to delete the original), with three differences: the numbers come from the
configuration's ``model.json``, the embedding task gets a trunk of its own
draw (it is a different published model), and nothing is reused between
runs — every run makes its weights from ``--seed`` and removes them, so
set-up is the same work in every run.

The program's ``build_engine`` only loads checkpoints from disk, so the
weights go host -> disk -> host -> device; making them on the device in
one jitted call needs a change to the program (PERF.md, Open questions).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import numpy as np

WEIGHT_STD = 0.02


def _normal(rng: np.random.Generator, std: float, *shape: int) -> np.ndarray:
    out = rng.standard_normal(shape, dtype=np.float32)
    out *= np.float32(std)
    return out


def trunk_state(model: Dict[str, Any], rng: np.random.Generator
                ) -> Dict[str, np.ndarray]:
    """HF ModernBERT trunk state dict (torch layout: [out, in])."""
    H, I = model["hidden_size"], model["intermediate_size"]
    state = {
        "model.embeddings.tok_embeddings.weight":
            _normal(rng, WEIGHT_STD, model["vocab_size"], H),
        "model.embeddings.norm.weight": np.ones(H, np.float32),
        "model.final_norm.weight": np.ones(H, np.float32)}
    for i in range(model["num_hidden_layers"]):
        p = f"model.layers.{i}."
        if i > 0:
            state[p + "attn_norm.weight"] = np.ones(H, np.float32)
        state[p + "attn.Wqkv.weight"] = _normal(rng, WEIGHT_STD, 3 * H, H)
        state[p + "attn.Wo.weight"] = _normal(rng, WEIGHT_STD, H, H)
        state[p + "mlp_norm.weight"] = np.ones(H, np.float32)
        state[p + "mlp.Wi.weight"] = _normal(rng, WEIGHT_STD, 2 * I, H)
        state[p + "mlp.Wo.weight"] = _normal(rng, WEIGHT_STD, H, I)
    return state


def head_state(model: Dict[str, Any], rng: np.random.Generator,
               n_labels: int, classifier_std: float
               ) -> Dict[str, np.ndarray]:
    H = model["hidden_size"]
    return {"head.dense.weight": _normal(rng, WEIGHT_STD, H, H),
            "head.norm.weight": np.ones(H, np.float32),
            "classifier.weight": _normal(rng, classifier_std, n_labels, H),
            "classifier.bias": np.zeros(n_labels, np.float32)}


def generate_states(config: Dict[str, Any], seed: int
                    ) -> Dict[str, Dict[str, np.ndarray]]:
    """``{task: full state dict}`` from ``seed``.  Classifier tasks share
    ONE trunk (the same array objects: the engine's content fingerprint
    fuses them into one trunk group); an embedding task draws its own."""
    model = config["model"]
    rng = np.random.default_rng(seed)
    shared = None
    states: Dict[str, Dict[str, np.ndarray]] = {}
    for task, spec in config["tasks"].items():
        if spec["kind"] == "embedding":
            states[task] = trunk_state(model, rng)
            continue
        if shared is None:
            shared = trunk_state(model, rng)
        state = dict(shared)
        state.update(head_state(model, rng, len(spec["labels"]),
                                float(spec.get("classifier_std",
                                               WEIGHT_STD))))
        states[task] = state
    return states


def write_checkpoints(root: str, config: Dict[str, Any], seed: int
                      ) -> Dict[str, str]:
    """Write every task's checkpoint directory and the tokenizer under
    ``root``; returns ``{task | "tokenizer": directory}``."""
    from safetensors.numpy import save_file
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    model = config["model"]
    dirs = {t: os.path.join(root, t) for t in config["tasks"]}
    dirs["tokenizer"] = os.path.join(root, "tokenizer")
    for task, state in generate_states(config, seed).items():
        os.makedirs(dirs[task], exist_ok=True)
        cfg = dict(model)  # the checkpoint's config.json
        labels = config["tasks"][task].get("labels")
        if labels:
            cfg["id2label"] = {str(i): l for i, l in enumerate(labels)}
        save_file(state, os.path.join(dirs[task], "model.safetensors"))
        with open(os.path.join(dirs[task], "config.json"), "w") as f:
            json.dump(cfg, f)
    # one token per whitespace word, no specials: a text of n words is n
    # tokens with id = the word's number, which is how the traffic aims at
    # its buckets and how the reference tokenizes without the program
    vocab = {"[PAD]": 0, "[UNK]": 1}
    vocab.update({f"w{i}": i for i in range(2, model["vocab_size"])})
    tok = Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    os.makedirs(dirs["tokenizer"], exist_ok=True)
    tok.save(os.path.join(dirs["tokenizer"], "tokenizer.json"))
    return dirs


def load_state(directory: str) -> Dict[str, np.ndarray]:
    from safetensors.numpy import load_file

    return load_file(os.path.join(directory, "model.safetensors"))
