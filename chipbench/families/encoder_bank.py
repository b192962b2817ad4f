"""The encoder-bank family: ModernBERT / mmBERT trunks with sequence, token
and embedding heads, served by the engine's classifier bank.

Everything of the benchmark that knows this model family is here, found by
the ``"family"`` key of a configuration's file (``families/__init__.py``
has the contract): which keys are the model's published numbers, the seeded
checkpoints in the form ``build_engine`` loads, the warm-up, the engine's
public calls that the wrappers keep answers from, and the comparison with
the plain reference (``reference/modernbert.py``, which imports nothing of
the program).

Checkpoints: HF-style directories (``model.safetensors`` + ``config.json``)
and one WordLevel ``tokenizer.json``.  A copy of ``chip_smoke.py``'s
generator (listed in PERF.md for a later PR to delete the original), with
three differences: the numbers come from the configuration's ``model.json``,
the embedding task gets a trunk of its own draw (it is a different published
model), and nothing is reused between runs — every run makes its weights
from ``--seed`` and removes them, so set-up is the same work in every run.
The program's ``build_engine`` only loads checkpoints from disk, so the
weights go host -> disk -> host -> device; making them on the device in one
jitted call needs a change to the program (PERF.md, Open questions).

Numbers compared (each QUADRATIC in the error's size and a mean over many
elements: the served precision and the one below it differ by a factor of
two to three in the size of their rounding errors, which a widest
|difference| of a few probabilities cannot tell apart and a mean square
can):
- ``seq_logit_rel_sq_err``: sequence heads.  Probabilities fix logits up to
  a constant, so: |centred log-probabilities - centred reference logits|^2
  over |centred reference logits|^2, all sequence tasks of a request
  together, mean over the sampled requests.
- ``pii_score_mean_sq_diff``: token head, over the entity spans the program
  reported: mean of (span score - reference)^2, the reference's score being
  the least, over the span's tokens, of the probability of the span's type.
- ``embedding_one_minus_cos``: 1 - cosine(program, reference), mean over
  the sampled requests.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.correctness import pick_bucket, softmax

MODEL_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "max_position_embeddings", "rope_scaling",
              "global_attn_every_n_layers", "local_attention",
              "classifier_pooling")

WEIGHT_STD = 0.02
PII_THRESHOLD = 0.5  # the engine's default token threshold (router_config)


# -- checkpoints from the seed ---------------------------------------------------


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


# -- the system: warm-up, and the engine's public calls ----------------------------


def warm(engine, config: Dict[str, Any], shapes: Dict[str, Sequence[int]]
         ) -> None:
    """The cell's shape set (``buckets`` x ``rows``) through the engine's
    own warm-up, and nothing else.  A failed warm-up program raises (the
    program's own WarmupError)."""
    engine.warmup(tasks=list(config["tasks"]), buckets=list(shapes["buckets"]),
                  batch_sizes=list(shapes["rows"]))
    for row in engine.warmup_report():
        print(f"warmup {row['target']} bucket={row['bucket']} "
              f"rows={row['rows']} {row['seconds']:.2f} s"
              + (f" ERROR {row['error']}" if row["error"] else ""),
              flush=True)


def _one_text(task, text, *_, **__):
    return [task], [text]


def _many_texts(task, texts, *_, **__):
    return [task], list(texts)


def _many_tasks(tasks, texts, *_, **__):
    return list(tasks), list(texts)


def _single(tasks, texts, out):
    yield texts[0], tasks[0], out


def _per_text(tasks, texts, out):
    for text, res in zip(texts, out):
        yield text, tasks[0], res


def _per_task_and_text(tasks, texts, out):
    for t in tasks:
        for text, res in zip(texts, out.get(t, [])):
            yield text, t, res


# the engine's public calls that signals, cache and router reach it by:
# how a call's arguments give (tasks, texts), and how its result gives
# (text, task, answer) for every answer to keep
ENGINE_CALLS = {
    "classify": (_one_text, _single),
    "classify_batch": (_many_texts, _per_text),
    "classify_multi": (_many_tasks, _per_task_and_text),
    "token_classify": (_one_text, _single),
    "embed": (_many_texts, _per_text),
}


# -- the comparison with the plain reference ---------------------------------------


@dataclasses.dataclass
class Entity:
    type: str
    start: int
    end: int
    score: float


@dataclasses.dataclass
class SeqAnswer:
    probs: Dict[str, float]


@dataclasses.dataclass
class TokAnswer:
    entities: List[Entity]


def word_offsets(ids: Sequence[int]) -> List[Tuple[int, int]]:
    """Char [start, end) of each word of ``" ".join(f"w{i}")``."""
    out, pos = [], 0
    for i in ids:
        n = 1 + len(str(int(i)))
        out.append((pos, pos + n))
        pos += n + 1
    return out


def entity_type(label: str) -> str:
    return label[2:] if label[:2] in ("B-", "I-") else label


class Reference:
    """The reference over one configuration's seeded checkpoints: one
    jitted forward per (trunk, precision), compiled per bucket."""

    @classmethod
    def from_checkpoints(cls, config: Dict[str, Any],
                         ckpt_dirs: Dict[str, str]) -> "Reference":
        return cls(config, {t: load_state(ckpt_dirs[t])
                            for t in config["tasks"]})

    def __init__(self, config: Dict[str, Any],
                 states: Dict[str, Dict[str, np.ndarray]]) -> None:
        """``states``: ``{task: HF state dict}`` as the benchmark made them
        (``generate_states`` or its files read back)."""
        import jax

        self.config = config
        self.dims = dict(config["model"])
        self._jax = jax
        # classifier tasks share one trunk; an embedding task has its own
        self.groups: List[Dict[str, Any]] = []
        shared: Optional[Dict[str, Any]] = None
        for task, spec in config["tasks"].items():
            state = states[task]
            trunk = {k: v for k, v in state.items() if k.startswith("model.")}
            head = {k: v for k, v in state.items()
                    if not k.startswith("model.")}
            if spec["kind"] == "embedding":
                self.groups.append({"trunk": jax.device_put(trunk),
                                    "heads": {task: {"kind": "embedding"}},
                                    "states": {}})
                continue
            if shared is None:
                shared = {"trunk": jax.device_put(trunk), "heads": {},
                          "states": {}}
                self.groups.append(shared)
            shared["heads"][task] = {"kind": spec["kind"]}
            shared["states"][task] = jax.device_put(head)
        self._fns: Dict[Tuple[int, str], Any] = {}

    def _fn(self, gi: int, precision: str):
        from chipbench.reference import modernbert

        key = (gi, precision)
        if key not in self._fns:
            kinds = {t: h["kind"] for t, h in self.groups[gi]["heads"].items()}

            def run(trunk, states, ids, mask):
                heads = {t: {"kind": k, "state": states.get(t)}
                         for t, k in kinds.items()}
                return modernbert.forward(self.dims, trunk, heads, ids, mask,
                                          precision=precision)

            self._fns[key] = self._jax.jit(run)
        return self._fns[key]

    def outputs(self, request, shapes: Dict[str, Sequence[int]],
                answers: Dict[str, Any], precision: str = "highest"
                ) -> Dict[str, np.ndarray]:
        """Every task's raw output for one request, padded to its bucket
        of ``shapes`` the way the engine pads (ids 0, mask 0 beyond the
        text).  An encoder's outputs do not depend on what the program
        ``answers``-ed."""
        ids = request.ids
        n = len(ids)
        bucket = pick_bucket(n, shapes["buckets"])
        padded = np.zeros(bucket, np.int32)
        padded[:n] = ids
        mask = np.zeros(bucket, np.int32)
        mask[:n] = 1
        out: Dict[str, np.ndarray] = {}
        for gi, g in enumerate(self.groups):
            res = self._fn(gi, precision)(g["trunk"], g["states"], padded,
                                          mask)
            out.update({t: np.asarray(v) for t, v in res.items()})
        return out

    def answers(self, request, shapes: Dict[str, Sequence[int]],
                answers: Dict[str, Any], precision: str) -> Dict[str, Any]:
        """The reference's outputs in the shape of the program's answers —
        how the control stands in the program's place."""
        raw = self.outputs(request, shapes, answers, precision)
        ids = request.ids
        n = len(ids)
        offsets = word_offsets(ids)
        out: Dict[str, Any] = {}
        for task, spec in self.config["tasks"].items():
            if spec["kind"] == "embedding":
                out[task] = raw[task]
            elif spec["kind"] == "sequence":
                p = softmax(raw[task][:len(spec["labels"])])
                out[task] = SeqAnswer(dict(zip(spec["labels"], p.tolist())))
            else:
                p = softmax(raw[task][:n, :len(spec["labels"])])
                top = p.argmax(-1)
                ents = [Entity(entity_type(spec["labels"][j]), *offsets[i],
                               float(p[i, j]))
                        for i, j in enumerate(top)
                        if spec["labels"][j] != "O"
                        and p[i, j] >= PII_THRESHOLD]
                out[task] = TokAnswer(ents)
        return out


def _centered(x: np.ndarray) -> np.ndarray:
    return x - x.mean()


def compare(config: Dict[str, Any], request, answers: Dict[str, Any],
            ref_raw: Dict[str, np.ndarray]) -> Dict[str, Tuple[float, float]]:
    """One request's part of each number as (sum, weight): the answers
    (the program's, or the control's) against the reference's raw
    outputs.  ``finish`` turns the merged parts into the numbers."""
    parts: Dict[str, Tuple[float, float]] = {}
    ids = request.ids
    n = len(ids)
    seq_num = seq_den = 0.0
    for task, spec in config["tasks"].items():
        got = answers.get(task)
        if got is None:
            continue
        if spec["kind"] == "embedding":
            a, b = np.asarray(got, np.float64), \
                np.asarray(ref_raw[task], np.float64)
            cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
            parts["embedding_one_minus_cos"] = (1.0 - cos, 1.0)
        elif spec["kind"] == "sequence":
            # probabilities fix the logits up to a constant: compare the
            # centred log-probabilities with the centred reference logits
            logp = _centered(np.log(np.asarray(
                [got.probs[l] for l in spec["labels"]], np.float64)))
            ref = _centered(np.asarray(
                ref_raw[task][:len(spec["labels"])], np.float64))
            seq_num += float(((logp - ref) ** 2).sum())
            seq_den += float((ref ** 2).sum())
        else:
            ref_p = softmax(ref_raw[task][:n, :len(spec["labels"])])
            offsets = word_offsets(ids)
            starts = {s: i for i, (s, _) in enumerate(offsets)}
            ends = {e: i for i, (_, e) in enumerate(offsets)}
            cols: Dict[str, List[int]] = {}
            for j, l in enumerate(spec["labels"]):
                cols.setdefault(entity_type(l), []).append(j)
            sq = 0.0
            for ent in got.entities:
                i0, i1 = starts[ent.start], ends[ent.end]
                ref_score = float(
                    ref_p[i0:i1 + 1][:, cols[ent.type]].max(-1).min())
                sq += (float(ent.score) - ref_score) ** 2
            if got.entities:
                parts["pii_score_mean_sq_diff"] = (sq, len(got.entities))
    if seq_den:
        parts["seq_logit_rel_sq_err"] = (seq_num / seq_den, 1.0)
    return parts


def finish(total: Dict[str, Tuple[float, float]]) -> Dict[str, float]:
    """Means over what was compared (requests; spans for the token head),
    and how many spans that was."""
    numbers = {k: s / w for k, (s, w) in total.items() if w}
    if "pii_score_mean_sq_diff" in total:
        numbers["pii_spans_compared"] = total["pii_score_mean_sq_diff"][1]
    return numbers


def expected_numbers(config: Dict[str, Any]) -> List[str]:
    kinds = {spec["kind"] for spec in config["tasks"].values()}
    return [name for kind, name in (
        ("sequence", "seq_logit_rel_sq_err"),
        ("token", "pii_score_mean_sq_diff"),
        ("embedding", "embedding_one_minus_cos")) if kind in kinds]
