"""The control of `correct`: the reference put in the program's place and
computed in bfloat16 (parameters and activations) — the nearest precision
below the one the configurations state.  It has to come out NOT correct.

On the chip, at the cell's own size (the readings limits.json is set from):

    python3 -m chipbench.tests.control_lower_precision \\
        --config mmbert32k-bank --seeds 101,102,103 --tokens 8000,3000

``test_harness.py`` runs the same function on the CPU at full depth and
width with a short sequence and a small vocabulary.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from chipbench import cells, checkpoints, correctness


def control_numbers(config: Dict[str, Any], seed: int,
                    tokens: Sequence[int], buckets: Sequence[int],
                    vocab_size: Optional[int] = None) -> Dict[str, float]:
    """One seed: weights from the seed, one request per entry of
    ``tokens``; the bfloat16 reference's answers against the `highest`
    reference's outputs, through the run's own ``compare``."""
    if vocab_size:
        config = dict(config, model=dict(config["model"],
                                         vocab_size=vocab_size))
    ref = correctness.Reference(config,
                                checkpoints.generate_states(config, seed))
    rng = np.random.default_rng([seed, 0xc7])
    parts: Dict[str, Any] = {}
    for n in tokens:
        ids = rng.integers(2, config["model"]["vocab_size"], n
                           ).astype(np.int32)
        bucket = correctness.pick_bucket(n, buckets)
        raw = ref.outputs(ids, bucket, "highest")
        low = ref.answers(ids, bucket, "bfloat16")
        correctness.merge(parts, correctness.compare(config, ids, low, raw))
    return correctness.finish(parts)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tokens", required=True)
    args = ap.parse_args(argv)
    bench = cells.load_benchmark()
    config = cells.load_config(bench, args.config)
    limits = correctness.load_limits()
    buckets = config["engine"]["seq_len_buckets"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        numbers = control_numbers(
            config, seed, [int(n) for n in args.tokens.split(",")], buckets)
        ok, _ = correctness.judge(config, numbers, limits)
        print(f"control {args.config} seed {seed}: "
              f"{json.dumps(numbers)} -> "
              f"{'PASSES THE LIMITS (bad)' if ok else 'not correct'} "
              f"({time.perf_counter() - t:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
