"""What decides ``correct``: the timed path's answers against the plain
reference, number by number, each with its own limit (``limits.json``,
where every limit carries the readings it was set from).

The answers compared are the ones the measured window produced — kept by
the benchmark's wrappers around the engine's public calls, at the window's
own batches and sequence lengths — for a sample of the completed requests
drawn from the seed, the longest among them.  The reference
(``reference/modernbert.py``) runs after the window, on the benchmark's
own weights and token ids, at ``highest`` precision.

Numbers (each QUADRATIC in the error's size and a mean over many
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

from . import checkpoints

HERE = os.path.dirname(os.path.abspath(__file__))
PII_THRESHOLD = 0.5  # the engine's default token threshold (router_config)


def load_limits() -> Dict[str, Dict[str, Any]]:
    with open(os.path.join(HERE, "limits.json")) as f:
        return json.load(f)["limits"]


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


def softmax(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    e = np.exp(x - x.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


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


def pick_bucket(n_tokens: int, buckets: Sequence[int]) -> int:
    for b in sorted(buckets):
        if n_tokens <= b:
            return b
    raise ValueError(f"{n_tokens} tokens fit no bucket of {list(buckets)}")


def sample_requests(completed: Sequence[Any], seed: int, k: int) -> List[Any]:
    """The longest completed request and k-1 others drawn from the seed."""
    if not completed:
        return []
    by_len = sorted(completed, key=lambda r: (r.n_tokens, r.index))
    rest = by_len[:-1]
    rng = np.random.default_rng([int(seed), 0xc0de])
    pick = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [by_len[-1]] + [rest[i] for i in sorted(pick)]


class Reference:
    """The reference over one configuration's seeded checkpoints: one
    jitted forward per (trunk, precision), compiled per bucket."""

    @classmethod
    def from_checkpoints(cls, config: Dict[str, Any],
                         ckpt_dirs: Dict[str, str]) -> "Reference":
        return cls(config, {t: checkpoints.load_state(ckpt_dirs[t])
                            for t in config["tasks"]})

    def __init__(self, config: Dict[str, Any],
                 states: Dict[str, Dict[str, np.ndarray]]) -> None:
        """``states``: ``{task: HF state dict}`` as the benchmark made them
        (``checkpoints.generate_states`` or its files read back)."""
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
        from .reference import modernbert

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

    def outputs(self, ids: np.ndarray, bucket: int,
                precision: str = "highest") -> Dict[str, np.ndarray]:
        """Every task's raw output for one request, padded to ``bucket``
        the way the engine pads (ids 0, mask 0 beyond the text)."""
        n = len(ids)
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

    def answers(self, ids: np.ndarray, bucket: int, precision: str
                ) -> Dict[str, Any]:
        """The reference's outputs in the shape of the program's answers —
        how the control stands in the program's place."""
        raw = self.outputs(ids, bucket, precision)
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


def compare(config: Dict[str, Any], ids: np.ndarray,
            answers: Dict[str, Any], ref_raw: Dict[str, np.ndarray]
            ) -> Dict[str, Tuple[float, float]]:
    """One request's part of each number as (sum, weight): the answers
    (the program's, or the control's) against the reference's raw
    outputs.  ``finish`` turns the merged parts into the numbers."""
    parts: Dict[str, Tuple[float, float]] = {}
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


def merge(total: Dict[str, Tuple[float, float]],
          one: Dict[str, Tuple[float, float]]) -> None:
    for k, (s, w) in one.items():
        s0, w0 = total.get(k, (0.0, 0.0))
        total[k] = (s0 + s, w0 + w)


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


def judge(config: Dict[str, Any], numbers: Dict[str, float],
          limits: Dict[str, Dict[str, Any]]) -> Tuple[bool, List[str]]:
    """Every expected number within its limit; a number that could not be
    read (no answer captured, no span to compare) is not correct."""
    ok, lines = True, []
    for name in expected_numbers(config):
        limit = float(limits[name]["limit"])
        value = numbers.get(name)
        good = value is not None and value <= limit
        ok = ok and good
        lines.append(f"compare {name}: "
                     f"{'not read' if value is None else repr(value)} "
                     f"limit {limit!r} {'ok' if good else 'NOT CORRECT'}")
    return ok, lines
