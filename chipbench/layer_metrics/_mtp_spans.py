"""What the readers of the self-drafting guard cell share beyond
``_ar_spans``: the drafts of the traced ``gen.decode`` steps, from the
``drafted`` / ``accepted`` / ``committed_tokens`` facts of their
``engine.gen.forward`` markers.  On a program that writes none (any
token-at-a-time generator) every function here gives None."""

from __future__ import annotations

from typing import List, Optional

from chipbench.layer_metrics import _ar_spans


def steps(run) -> List[dict]:
    """The markers of the traced decode steps that verified drafts."""
    return [m for _, m in _ar_spans.forwards(run, _ar_spans.DECODE)
            if int(m.get("drafted", 0))]


def ratio(run, over: str, under: str) -> Optional[float]:
    """Sum of fact ``over`` by sum of fact ``under`` over those steps."""
    marks = steps(run)
    total = sum(int(m[under]) for m in marks)
    return sum(int(m[over]) for m in marks) / total if total else None
