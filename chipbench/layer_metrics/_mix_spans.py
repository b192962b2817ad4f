"""What the readers of the mixed-length guard cell share beyond
``_ar_spans``: the traced steps, queue waits and prefill markers BY PROMPT
BUCKET (one task's short and long prompts alternate in one window), and the
attention cores' share of their roofline over the traced long prefills.

The program writes ``bucket`` on every ``engine.step``; since the PR that
added this cell also on the generative items' ``engine.queue_wait`` events
and on the ``engine.gen.forward`` markers, whose prefill carries
``cache_bytes_full`` / ``cache_bytes_window``.  On a program without them —
that PR's parent — every function here gives None or nothing.
"""

from __future__ import annotations

import bisect
import functools
from typing import Any, Dict, List, Optional, Tuple

from chipbench import cells, stats
from chipbench.layer_metrics import _ar_spans, _gen_spans
from chipbench.reduce_trace import HOST_PLANE, NS

QUEUE_WAIT, TOKENIZE = "engine.queue_wait", "engine.tokenize"


def bucket_of(run, which: str) -> int:
    """The cell's ``short`` (smallest) or ``long`` (largest) bucket."""
    buckets = sorted(run["config"]["engine"]["seq_len_buckets"])
    return buckets[0] if which == "short" else buckets[-1]


def steps(run, flavour: str, which: str) -> List[Any]:
    want = bucket_of(run, which)
    return [st for st in _gen_spans.steps(run) or ()
            if st.facts.get("flavour") == flavour
            and int(st.facts.get("bucket", -1)) == want]


def step_mean_ms(run, flavour: str, which: str) -> Optional[float]:
    mine = steps(run, flavour, which)
    if not mine:
        return None
    return sum(st.end - st.start for st in mine) / len(mine) * 1e3


def rows_mean(run, which: str) -> Optional[float]:
    """Real rows of a generation of one bucket (its prefill step's)."""
    mine = steps(run, _ar_spans.PREFILL, which)
    if not mine:
        return None
    return sum(int(st.facts.get("rows", 0)) for st in mine) / len(mine)


@functools.lru_cache(maxsize=2)
def _events(path: str) -> Tuple[list, Dict[str, int]]:
    """``(queue waits [(end, seconds, bucket, trace_id)] that carry a
    bucket, tokens by trace_id)`` of the host plane."""
    from jax.profiler import ProfileData

    waits, tokens = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == QUEUE_WAIT:
                    st = dict(e.stats)
                    if "bucket" in st:
                        waits.append((
                            (e.start_ns + e.duration_ns) * NS,
                            float(st.get("wait_us", 0)) * 1e-6,
                            int(st["bucket"]), str(st.get("trace_id", ""))))
                elif e.name == TOKENIZE:
                    st = dict(e.stats)
                    if st.get("trace_id"):
                        tokens[str(st["trace_id"])] = int(st.get("tokens", 0))
    return sorted(waits), tokens


def _waits(run):
    tr = run.get("trace")
    if not tr or not tr.get("path"):
        return [], {}
    return _events(tr["path"])


def queue_wait_ms(run, which: str) -> Optional[float]:
    """The median ``engine.queue_wait`` of the traced items of one bucket,
    ms: what a prompt of that length waited in the batcher for its group's
    turn, behind whatever ran before it."""
    want = bucket_of(run, which)
    mine = [secs for _, secs, bucket, _ in _waits(run)[0] if bucket == want]
    return stats.percentile(mine, 50) * 1e3 if mine else None


def forwards(run, flavour: str, which: Optional[str] = None
             ) -> List[Tuple[Any, Dict[str, Any]]]:
    """Each traced ``gen.*`` step of ``flavour`` (of one bucket, or of
    both) with ITS ``engine.gen.forward`` marker.  Two generations of two
    buckets run side by side on two threads, so a step's marker is not the
    next one written (``_gen_spans.forwards``'s rule): it is the next one
    of the step's own BUCKET, written before that bucket's next step opens
    — a bucket's generations come one after another."""
    mine = _gen_spans.steps(run)
    if not mine:
        return []
    marks = _gen_spans._markers(run["trace"]["path"])
    out = []
    for name in (which,) if which else ("short", "long"):
        want = bucket_of(run, name)
        of = [st for st in mine if int(st.facts.get("bucket", -1)) == want]
        its = [m for m in marks if int(m[1].get("bucket", -1)) == want]
        starts = [m[0] for m in its]
        for n, st in enumerate(of):
            i = bisect.bisect_left(starts, st.end - 1e-6)
            before = of[n + 1].start if n + 1 < len(of) else float("inf")
            if st.facts.get("flavour") == flavour and i < len(its) \
                    and its[i][0] <= before \
                    and its[i][1].get("flavour") == flavour:
                out.append((st, its[i][1]))
    return out


def window_cache_share(run) -> Optional[float]:
    """The rings' bytes over all cache bytes of the traced long prefills,
    %."""
    ring = whole = 0
    for _, mark in forwards(run, _ar_spans.PREFILL, "long"):
        if "cache_bytes_window" in mark:
            ring += int(mark["cache_bytes_window"])
            whole += int(mark["cache_bytes_window"]) \
                + int(mark["cache_bytes_full"])
    return ring / whole * 100.0 if whole else None


def gmm_roofline(run, flavour: str) -> Optional[float]:
    """``_ar_spans.gmm_roofline``'s reckoning (``opcount/moe_gmm.py``
    unchanged) over the traced forwards of one flavour and BOTH buckets,
    each step with its own marker (``forwards``): the least time for their
    routed pairs and touched experts over the device time of the ops under
    ``moe/gmm`` inside those same forwards' program runs, %."""
    tr = run.get("trace")
    if not tr or not tr.get("peaks"):
        return None
    oc = cells.load_module("opcount", "moe_gmm")
    pairs = touched = secs = 0.0
    n = 0
    for step, mark in forwards(run, flavour):
        if step.device is None or "pairs" not in mark:
            continue
        s = _gen_spans.scope_seconds(run, oc.SCOPE, within=step.device)
        if not s:
            continue
        pairs += float(mark["pairs"])
        touched += float(mark["experts_touched"])
        secs, n = secs + s, n + 1
    if not n or secs <= 0:
        return None
    cost = oc.forward_cost(pairs, touched, run["config"]["model"])
    least, bound = cells.load_module("opcount", "flash_attention") \
        .least_seconds(cost["flops"], cost["bytes"], tr["peaks"])
    print(f"moe gmm roofline ({flavour}, both buckets): {n} forwards, "
          f"{pairs:.0f} pairs, {touched:.0f} experts touched: "
          f"{cost['flops']:.3e} operations, {cost['bytes']:.3e} bytes, least "
          f"{least:.4f} s ({bound}-bound), measured {secs:.4f} s", flush=True)
    return least / secs * 100.0


def _lengths_by_step(run, which: str) -> List[Tuple[Any, List[int]]]:
    """Each traced prefill step of one bucket whose program run was found
    on the device, with its rows' real lengths: the items the batcher
    handed it are the ``engine.queue_wait`` events of that bucket written
    since the bucket's step before (one generation a group at a time), and
    an item's length is its ``engine.tokenize`` marker's, joined by
    ``trace_id``.  A step whose items were not all seen (it began with the
    session) is left out."""
    want = bucket_of(run, which)
    waits, tokens = _waits(run)
    ends = [w[0] for w in waits if w[2] == want]
    ids = [w[3] for w in waits if w[2] == want]
    out, since = [], 0
    for st in steps(run, _ar_spans.PREFILL, which):
        upto = bisect.bisect_right(ends, st.start + 1e-3)
        mine = [tokens.get(t) for t in ids[since:upto]]
        since = upto
        if st.device is not None and len(mine) == int(st.facts.get(
                "rows", -1)) and all(mine):
            out.append((st, mine))
    return out


def flash_roofline(run, kind: str) -> Optional[float]:
    """The least time for the causal (``full_attention``) or banded
    (``sliding_attention``) pairs of the traced LONG prefills' real rows,
    each at its real length (``opcount/gqa_attention.py``), over the device
    time of the ops under that layer type's core scope inside those same
    prefills' program runs, %."""
    tr = run.get("trace")
    if not tr or not tr.get("peaks"):
        return None
    oc = cells.load_module("opcount", "gqa_attention")
    model = run["config"]["model"]
    layers = model["layer_types"].count(kind)
    flops = nbytes = secs = 0.0
    rows = 0
    for st, lengths in _lengths_by_step(run, "long"):
        s = _gen_spans.scope_seconds(run, oc.SCOPES[kind], within=st.device)
        if not s:
            continue
        for n in lengths:
            cost = oc.row_cost(n, kind, model)
            flops += layers * cost["flops"]
            nbytes += layers * cost["bytes"]
        secs, rows = secs + s, rows + len(lengths)
    if not rows or secs <= 0:
        return None
    least, bound = cells.load_module("opcount", "flash_attention") \
        .least_seconds(flops, nbytes, tr["peaks"])
    print(f"flash roofline ({kind}, long prefills): {rows} rows in "
          f"{layers} layers: {flops:.3e} operations, {nbytes:.3e} bytes, "
          f"least {least:.4f} s ({bound}-bound), measured {secs:.4f} s",
          flush=True)
    return least / secs * 100.0
