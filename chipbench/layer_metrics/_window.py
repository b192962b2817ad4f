"""What several readers share: differences of the program's cumulative
step counters over an interval."""

from __future__ import annotations

from typing import Callable, Dict, Tuple

Key = Tuple[str, int, str]


def step_delta(steps, select: Callable[[Key], bool] = lambda k: True
               ) -> Dict[str, float]:
    """Sum over the selected (group, bucket, variant) programs of
    after - before, per field."""
    before, after = steps
    out = {"executes": 0.0, "execute_s": 0.0, "rows_real": 0.0,
           "rows_padded": 0.0, "compiles": 0.0}
    for key, row in after.items():
        if not select(key):
            continue
        base = before.get(key, {})
        for f in out:
            out[f] += row[f] - base.get(f, 0.0)
    return out


def is_fused(key: Key) -> bool:
    return key[0].startswith("trunk:")


def is_embedding(key: Key) -> bool:
    return key[0] == "task:embedding"
