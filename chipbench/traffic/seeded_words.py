"""The general traffic generator: every mix is a data file of parameters
(``workloads/<traffic>.json``) that this one generator reads.

Every seed gets the SAME multiset of prompt lengths and (open loop) of
inter-arrival gaps — the law's quantiles at evenly spaced probabilities —
in another order, and its own token ids.  So runs with different seeds do
the same work; what the seed changes is which request meets which.

Length laws (``lengths``): ``loguniform`` (min, max), ``lognormal``
(median, sigma, min, max), ``fixed`` (values) and ``mixture`` (parts, each
a law with a ``weight``).  Arrivals (``arrivals``): ``closed`` (clients)
or ``open`` with ``process`` ``poisson`` or ``gamma`` (shape; the same
mean rate, burstier below 1) at ``rate_per_s``.

A request is ``n_tokens`` distinct-looking words ``w<id>``: with the
benchmark's WordLevel tokenizer a word is one token whose id is the
number, so the reference needs no tokenizer of the program's.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    n_tokens: int
    ids: np.ndarray              # token ids, the reference's input
    text: str                    # what the program receives
    due_s: Optional[float]       # open loop: seconds after window start


@dataclasses.dataclass
class Traffic:
    loop: str                    # "open" | "closed"
    clients: int                 # closed: concurrent callers; open: senders
    requests: List[Request]
    warmup: List[Request]        # sent before the window, never measured


def _law_quantiles(law: Dict[str, Any], n: int) -> List[int]:
    """n lengths at the law's evenly spaced quantiles."""
    kind = law["law"]
    u = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        vals = list(law["values"])
        return [int(vals[i % len(vals)]) for i in range(n)]
    if kind == "loguniform":
        lo, hi = math.log(law["min"]), math.log(law["max"])
        return [int(round(math.exp(lo + (hi - lo) * x))) for x in u]
    if kind == "lognormal":
        nd = statistics.NormalDist()
        mu, sigma = math.log(law["median"]), float(law["sigma"])
        return [int(min(max(round(math.exp(mu + sigma * nd.inv_cdf(x))),
                            law["min"]), law["max"])) for x in u]
    if kind == "mixture":
        out: List[int] = []
        total = sum(p["weight"] for p in law["parts"])
        left = n
        for i, part in enumerate(law["parts"]):
            k = left if i == len(law["parts"]) - 1 else \
                min(left, round(n * part["weight"] / total))
            out.extend(_law_quantiles(part, k) if k else [])
            left -= k
        return out
    raise ValueError(f"unknown length law {kind!r}")


def _gap_quantiles(arrivals: Dict[str, Any], n: int) -> np.ndarray:
    """n inter-arrival gaps (seconds) at the process's quantiles."""
    rate = float(arrivals["rate_per_s"])
    u = (np.arange(n) + 0.5) / n
    process = arrivals.get("process", "poisson")
    if process == "poisson":
        return -np.log1p(-u) / rate
    if process == "gamma":
        from scipy.stats import gamma

        shape = float(arrivals["shape"])
        return gamma.ppf(u, a=shape, scale=1.0 / (shape * rate))
    raise ValueError(f"unknown arrival process {process!r}")


def _requests(lengths: List[int], rng: np.random.Generator, vocab: int,
              due: Optional[np.ndarray]) -> List[Request]:
    out = []
    for i, n in enumerate(lengths):
        ids = rng.integers(2, vocab, n)
        out.append(Request(i, int(n), ids.astype(np.int32),
                           " ".join(f"w{t}" for t in ids),
                           None if due is None else float(due[i])))
    return out


def generate(params: Dict[str, Any], seed: int, seconds: float,
             vocab_size: int) -> Traffic:
    arrivals = params["arrivals"]
    rng = np.random.default_rng([int(seed), 0x7a11])
    if arrivals["mode"] == "closed":
        clients = int(arrivals["clients"])
        # more than the window can consume; any prefix of a shuffled
        # quantile set is a fair sample of the law
        n = max(clients, math.ceil(float(arrivals["pool_per_s"]) * seconds))
        due = None
    elif arrivals["mode"] == "open":
        clients = int(arrivals.get("senders", 64))
        n = max(1, round(float(arrivals["rate_per_s"]) * seconds))
        gaps = rng.permutation(_gap_quantiles(arrivals, n))
        times = np.cumsum(gaps)
        # the multiset's mean gap is 1/rate only in the limit: scale the
        # schedule so that the n arrivals fill the window exactly
        due = (times - gaps[0] / 2) * (seconds / times[-1])
    else:
        raise ValueError(f"unknown arrivals mode {arrivals['mode']!r}")
    lengths = rng.permutation(_law_quantiles(params["lengths"], n)).tolist()
    requests = _requests(lengths, rng, vocab_size, due)
    n_warm = int(params.get("warmup_requests", 2 * clients))
    warm_rng = np.random.default_rng([int(seed), 0x3a93])
    warm_lengths = warm_rng.permutation(
        _law_quantiles(params["lengths"], max(n_warm, 1))).tolist()[:n_warm]
    warmup = _requests(warm_lengths, warm_rng, vocab_size, None)
    return Traffic(arrivals["mode"], clients, requests, warmup)
