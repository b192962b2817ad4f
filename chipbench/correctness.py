"""What decides ``correct``, the part no model family owns: which of the
window's requests are compared, how their parts are added up, and the
judgement of every number against its own limit.

The answers compared are the ones the measured window produced — kept by
the benchmark's wrappers around the engine's public calls, at the window's
own batches and sequence lengths — for a sample of the completed requests
drawn from the seed, the longest among them.  The reference, the numbers
and what they mean belong to the configuration's family
(``families/<family>.py``); every limit carries the readings it was set
from (``limits.json``, or ``limits.json`` beside the configuration's file
for the numbers that configuration brings).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _limits_of(path: str) -> Dict[str, Dict[str, Any]]:
    with open(path) as f:
        return json.load(f)["limits"]


def load_limits(config: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The shared ``limits.json`` plus the configuration's own
    ``limits.json``, if it brings one.  A name in both is an error: an
    addition can set a new number's limit and loosen none that is there."""
    limits = _limits_of(os.path.join(HERE, "limits.json"))
    own = os.path.join(config["dir"], "limits.json")
    if os.path.exists(own):
        added = _limits_of(own)
        both = sorted(set(limits) & set(added))
        if both:
            raise SystemExit(f"chipbench: {own} sets a limit that "
                             f"limits.json already has: {both}")
        limits.update(added)
    return limits


def softmax(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    e = np.exp(x - x.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


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


def merge(total: Dict[str, Tuple[float, float]],
          one: Dict[str, Tuple[float, float]]) -> None:
    for k, (s, w) in one.items():
        s0, w0 = total.get(k, (0.0, 0.0))
        total[k] = (s0 + s, w0 + w)


def judge(expected: Sequence[str], numbers: Dict[str, float],
          limits: Dict[str, Dict[str, Any]]) -> Tuple[bool, List[str]]:
    """Every expected number within its limit; a number that could not be
    read (no answer captured, nothing to compare) is not correct."""
    ok, lines = True, []
    for name in expected:
        limit = float(limits[name]["limit"])
        value = numbers.get(name)
        good = value is not None and value <= limit
        ok = ok and good
        lines.append(f"compare {name}: "
                     f"{'not read' if value is None else repr(value)} "
                     f"limit {limit!r} {'ok' if good else 'NOT CORRECT'}")
    return ok, lines
