"""The control of `correct`: the family's reference put in the program's
place and computed in bfloat16 (parameters and activations) — the nearest
precision below the one the configurations state.  It has to come out NOT
correct.  It goes through the configuration's family (``Reference(config,
generate_states(config, seed))``, ``outputs``, ``answers``, ``compare``,
``finish``), so a family that brings those brings its control.

On the chip, at the cell's own size (the readings limits.json is set from):

    python3 -m chipbench.tests.control_lower_precision \\
        --config mmbert32k-bank --seeds 101,102,103 --tokens 8000,3000

``test_harness.py`` runs the same function on the CPU at full depth and
width with a short sequence and a small vocabulary.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from chipbench import cells, correctness

Request = collections.namedtuple("Request", "ids n_tokens")


def control_numbers(config: Dict[str, Any], seed: int,
                    tokens: Sequence[int], buckets: Sequence[int],
                    vocab_size: Optional[int] = None) -> Dict[str, float]:
    """One seed: weights from the seed, one request per entry of
    ``tokens``; the bfloat16 reference's answers against the `highest`
    reference's outputs, through the run's own ``compare``."""
    if vocab_size:
        config = dict(config, model=dict(config["model"],
                                         vocab_size=vocab_size))
    family = cells.load_family(config)
    ref = family.Reference(config, family.generate_states(config, seed))
    rng = np.random.default_rng([seed, 0xc7])
    shapes = {"buckets": list(buckets)}
    parts: Dict[str, Any] = {}
    for n in tokens:
        req = Request(rng.integers(2, config["model"]["vocab_size"], n
                                   ).astype(np.int32), n)
        raw = ref.outputs(req, shapes, {}, "highest")
        low = ref.answers(req, shapes, {}, "bfloat16")
        correctness.merge(parts, family.compare(config, req, low, raw))
    return family.finish(parts)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tokens", required=True)
    args = ap.parse_args(argv)
    bench = cells.load_benchmark()
    config = cells.load_config(bench, args.config)
    limits = correctness.load_limits(config)
    expected = cells.load_family(config).expected_numbers(config)
    buckets = config["engine"]["seq_len_buckets"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        numbers = control_numbers(
            config, seed, [int(n) for n in args.tokens.split(",")], buckets)
        ok, _ = correctness.judge(expected, numbers, limits)
        print(f"control {args.config} seed {seed}: "
              f"{json.dumps(numbers)} -> "
              f"{'PASSES THE LIMITS (bad)' if ok else 'not correct'} "
              f"({time.perf_counter() - t:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
