"""Where the device's idle time goes in a cell that generates: every gap
between two programs of a generation, and between two generations, cut at
the edges the program writes on the profiler's clock and put down to a
named phase of the host.  Shared by the ``gen_turn_ms.*``, ``gen_gap_ms.*``
and ``gen_idle_accounted_share`` readers beside this file.

The steps and their program runs come from ``_program_spans`` (``engine.step``
with its stages, the run each step launched).  Around them the program
(``semantic_router_tpu/observability/batchtrace.py``) writes
``engine.gen.turn`` (``group``, ``after``, ``block``) from a forward's step
close to the next one's open, ``engine.gen.done`` (``group``, counts, the
host clock's sums) at a generation's end, ``engine.tokenize`` (``trace_id``,
``tok_us``) at the end of a tokenization on a caller's thread, and the
batcher ``engine.queue_wait`` (``trace_id``, ``wait_us``) when an item
leaves the queue; the last three mark ENDS and carry lengths.

A pair of consecutive forwards k, k+1 of one generation, both found on the
device, with the turn between them seen::

    program k ends | readback ends | step k ends | step k+1 begins | program k+1 begins
         readback_tail      demux         between            launch

A pair of consecutive generations A, B of one group, A's end marker and B's
prefill step both seen (``e`` = the first enqueue among B's items, clipped
to the gap; tokenization = the union of the ``engine.tokenize`` intervals of
B's items' routes, counted apart wherever it lies)::

    A's last program ends | its step ends | engine.gen.done | e | B's prefill step begins | its program begins
          last_step_tail        finish          callers    queue_wait         prefill_head

Each row sums to the device's gap between the two programs.

**Two clocks.**  The steps' edges are the host's, a program's first and last
op the device's, and a trace lays the device's timeline beside the host's
to within a millisecond or three, not better (the first traced guard cells
read a program's first op 0.4 ms BEFORE the step that launched it began).
The four pieces that have an edge on each clock (``readback_tail``,
``launch``, ``last_step_tail``, ``prefill_head``) are therefore taken after
shifting the device's edges by ``offset``, estimated from what cannot be
otherwise: no program begins before the host enqueued it
(``DoEnqueueProgram``, which carries its ``run_id``), and none ends after
the host learned of it (``CompleteCallbacks`` of that ``run_id``, or the end
of its step's ``readback`` stage).  Over a session's hundreds of runs the
first gives the least the offset can be and the second the most; the
estimate is their middle, and half their distance is what it may be wrong
by (printed).  The sums of a row, the host-only pieces and the idle seconds
do not depend on it.

On a program without ``engine.gen.turn`` — the parent of the PR that added it —
``account()`` is None and every reader returns None.
"""

from __future__ import annotations

import bisect
import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from chipbench import stats as stats_mod
from chipbench.layer_metrics import _program_spans
from chipbench.reduce_trace import HOST_PLANE, NS

TURN = "engine.gen.turn"
TURN_STAGE = TURN + "."
DONE = "engine.gen.done"
TOKENIZE = "engine.tokenize"
QUEUE_WAIT = _program_spans.QUEUE_WAIT
PREFILL = "gen.prefill"
TURN_PIECES = ("readback_tail", "demux", "between", "launch")
GAP_PIECES = ("last_step_tail", "finish", "callers", "tokenize",
              "queue_wait", "prefill_head")
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
EDGE = 1e-6  # two annotations of one thread, a microsecond apart at most

Interval = Tuple[float, float]
Event = Tuple[float, float, Dict[str, Any]]


@dataclass
class Account:
    # one entry a traced pair / a traced gap: its pieces in seconds, the
    # interval of the device they tile, and what names it
    turns: List[Dict[str, Any]] = field(default_factory=list)
    gaps: List[Dict[str, Any]] = field(default_factory=list)
    accounted_s: float = 0.0  # idle seconds of the device inside those
    idle_s: float = 0.0  # ... and between its first and last op
    in_programs_s: float = 0.0  # ... and between the ops of one program run
    # the device's clock behind the host's: (at least, at most), seconds
    offset_bounds: Tuple[float, float] = (0.0, 0.0)


@functools.lru_cache(maxsize=2)
def _host_events(path: str) -> Dict[str, List[Event]]:
    """The host plane's events of the names read here, each list by start;
    under ``ENQUEUE`` and ``COMPLETE`` those that carry a ``run_id``, and
    under ``"modules"`` the first device's program runs with theirs."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Event]] = {n: [] for n in (
        TURN, DONE, TOKENIZE, QUEUE_WAIT, TURN_STAGE, ENQUEUE, COMPLETE,
        "modules")}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    name = TURN_STAGE if e.name.startswith(TURN_STAGE) \
                        else e.name
                    if name in out:
                        out[name].append((
                            e.start_ns * NS,
                            (e.start_ns + e.duration_ns) * NS,
                            dict(e.stats)))
        elif _program_spans.DEVICE_PLANE.match(plane.name) \
                and not out["modules"]:
            for line in plane.lines:
                if line.name == _program_spans.MODULES_LINE:
                    out["modules"] = [
                        (e.start_ns * NS, (e.start_ns + e.duration_ns) * NS,
                         dict(e.stats)) for e in line.events]
    for events in out.values():
        events.sort(key=lambda ev: ev[0])
    return out


def _offset_bounds(host, steps, within: Interval = (float("-inf"),
                                                    float("inf"))
                   ) -> Optional[Tuple[float, float]]:
    """(at least, at most) of what must be added to the device's times to
    stand on the host's clock, from the program runs that began inside
    ``within``; None where either side has no witness."""
    runs = {int(st["run_id"]): (s, t) for s, t, st in host["modules"]
            if "run_id" in st and within[0] <= s < within[1]}

    def against(events, edge: int) -> List[float]:
        return [s - runs[int(st["run_id"])][edge] for s, _, st in events
                if int(st.get("run_id", -1)) in runs]

    least = against(host[ENQUEUE], 0)
    most = against(host[COMPLETE], 1) + [
        st.stages["readback"][1] - st.device[1] for st in steps
        if st.device is not None and "readback" in st.stages
        and within[0] <= st.device[0] < within[1]]
    if not least or not most:
        return None
    return max(least), min(most)


def _generations(ps) -> Dict[str, List[List[_program_spans.Step]]]:
    """The ``gen.*`` steps as generations, by group and in order: a prefill
    step begins one (the session's first may lack its beginning)."""
    by_group: Dict[str, List[List[_program_spans.Step]]] = {}
    for st in ps.steps:  # by start
        flavour = str(st.facts.get("flavour", ""))
        if not flavour.startswith("gen."):
            continue
        gens = by_group.setdefault(str(st.facts.get("group", "")), [])
        if flavour == PREFILL or not gens:
            gens.append([])
        gens[-1].append(st)
    return by_group


def _first_within(events: List[Event], starts: List[float], a: float,
                  b: float) -> Optional[Event]:
    """The first event that begins at or after ``a`` and ends by ``b``."""
    i = bisect.bisect_left(starts, a - EDGE)
    if i < len(events) and events[i][1] <= b + EDGE:
        return events[i]
    return None


def _turn_pairs(gen, turns, turn_starts, offset: float
                ) -> List[Dict[str, Any]]:
    out = []
    for a, b in zip(gen, gen[1:]):
        if a.device is None or b.device is None \
                or "readback" not in a.stages:
            continue
        turn = _first_within(turns, turn_starts, a.end, b.start)
        if turn is None:
            continue
        read_end = a.stages["readback"][1]
        out.append({
            "after": turn[2].get("after", a.facts.get("flavour")),
            "interval": (a.device[1], b.device[0]),
            "readback_tail": read_end - a.device[1] - offset,
            "demux": a.end - read_end, "between": b.start - a.end,
            "launch": b.device[0] + offset - b.start})
    return out


def _gap(last, done: Event, nxt, waits, tokenizations, offset: float
         ) -> Dict[str, Any]:
    """The pieces between generation A (``last`` step, ``done`` marker)
    and generation B (``nxt``, its prefill step)."""
    ended, began = done[1], nxt.start
    mine = [w for w in waits if ended - EDGE <= w[1] <= began + EDGE]
    ids = {str(w[2].get("trace_id", "")) for w in mine} - {""}
    first = min((w[1] - float(w[2].get("wait_us", 0)) * 1e-6 for w in mine),
                default=began)
    first = min(max(first, ended), began)
    toks = [(t - float(st.get("tok_us", 0)) * 1e-6, t)
            for _, t, st in tokenizations
            if not ids or str(st.get("trace_id", "")) in ids]
    tok_callers = stats_mod.union_length(toks, (ended, first))
    tok_queued = stats_mod.union_length(toks, (first, began))
    return {"interval": (last.device[1], nxt.device[0]),
            "rows": int(nxt.facts.get("rows", 0)), "items": len(mine),
            # one after another (a lock) if this is the union, not more
            "tokenize_lengths": sum(
                max(0.0, min(t, began) - max(s, ended)) for s, t in toks),
            "last_step_tail": last.end - last.device[1] - offset,
            "finish": ended - last.end,
            "callers": first - ended - tok_callers,
            "tokenize": tok_callers + tok_queued,
            "queue_wait": began - first - tok_queued,
            "prefill_head": nxt.device[0] + offset - began}


def account(run) -> Optional[Account]:
    ps = _program_spans.load(run)
    if ps is None:
        return None
    return _account(run["trace"]["path"])


@functools.lru_cache(maxsize=2)
def _account(path: str) -> Optional[Account]:
    ps = _program_spans._load(path)
    host = _host_events(path)
    turns, dones = host[TURN], host[DONE]
    if not turns:
        return None  # a program that does not write its turns
    turn_starts = [t[0] for t in turns]
    done_starts = [d[0] for d in dones]
    acc = Account()
    bounds = _offset_bounds(host, ps.steps)
    if bounds is not None:
        acc.offset_bounds = bounds
    offset = sum(acc.offset_bounds) / 2
    for group, gens in _generations(ps).items():
        # the batcher names a generative group by its key, the steps by
        # their task: ``__generate__:<task>:...`` against ``gen:<task>``
        key = "__generate__:" + group.partition(":")[2] + ":"
        waits = [w for w in host[QUEUE_WAIT]
                 if str(w[2].get("group", "")).startswith(key)]
        for gen in gens:
            acc.turns += _turn_pairs(gen, turns, turn_starts, offset)
        for a, b in zip(gens, gens[1:]):
            last, nxt = a[-1], b[0]
            if last.device is None or nxt.device is None:
                continue
            done = _first_within(dones, done_starts, last.end, nxt.start)
            if done is not None:
                acc.gaps.append(_gap(last, done, nxt, waits,
                                     host[TOKENIZE], offset))
    if ps.ops:
        busy = [(s, t) for s, t, _, _ in ps.ops]
        idle = stats_mod.gaps(busy, (busy[0][0], max(t for _, t in busy)))
        acc.idle_s = sum(t - s for s, t in idle)
        # the idle stretches are disjoint and in order: those that touch a
        # row's interval are a slice of them
        starts, ends = [g[0] for g in idle], [g[1] for g in idle]

        def idle_within(a: float, b: float) -> float:
            lo, hi = bisect.bisect_right(ends, a), bisect.bisect_left(starts, b)
            return sum(min(t, b) - max(s, a) for s, t in idle[lo:hi])

        acc.accounted_s = sum(idle_within(*row["interval"])
                              for row in acc.turns + acc.gaps)
        acc.in_programs_s = sum(idle_within(s, t)
                                for s, t, _ in host["modules"])
    _describe(acc, host, ps.steps)
    return acc


def _mean_ms(rows, piece: str) -> float:
    return sum(r[piece] for r in rows) / len(rows) * 1e3


def _describe(acc: Account, host, steps) -> None:
    """The account in words, once a run: a reader's one number hides which
    forward a turn followed and which gap was the long one."""
    for after in sorted({r["after"] for r in acc.turns}):
        rows = [r for r in acc.turns if r["after"] == after]
        print(f"generation host time: {len(rows)} turns after {after}: "
              + ", ".join(f"{p} {_mean_ms(rows, p):.3f}"
                          for p in TURN_PIECES) + " ms", flush=True)
    copies = [t - s for s, t, _ in host[TURN_STAGE]]
    if copies:
        print(f"generation host time: {len(copies)} stages inside turns, "
              f"mean {sum(copies) / len(copies) * 1e3:.3f} ms", flush=True)
    for g in acc.gaps:
        print(f"generation host time: gap of "
              f"{(g['interval'][1] - g['interval'][0]) * 1e3:.2f} ms before "
              f"{g['rows']} rows ({g['items']} items): "
              + ", ".join(f"{p} {g[p] * 1e3:.2f}" for p in GAP_PIECES)
              + f" (the tokenizations' lengths sum to "
              f"{g['tokenize_lengths'] * 1e3:.2f})", flush=True)
    lo, hi = acc.offset_bounds
    print(f"generation host time: the device's clock stands "
          f"{lo * 1e3:.3f} to {hi * 1e3:.3f} ms behind the host's (no "
          f"program before its enqueue, none ending after its completion "
          f"was seen); the pieces with an edge on each clock are taken "
          f"at {(lo + hi) / 2 * 1e3:.3f}", flush=True)
    if host["modules"]:  # does the offset hold still over the session?
        a, b = host["modules"][0][0], host["modules"][-1][0]
        halves = [_offset_bounds(host, steps, w)
                  for w in ((a, (a + b) / 2), ((a + b) / 2, b + 1))]
        print("generation host time: the offset's bounds in the session's "
              "two halves: " + "; ".join(
                  "none" if h is None else
                  f"{h[0] * 1e3:.3f} to {h[1] * 1e3:.3f} ms"
                  for h in halves), flush=True)
    print(f"generation host time: {acc.accounted_s:.4f} s of "
          f"{acc.idle_s:.4f} s idle between the first and the last op "
          f"accounted for, in {len(acc.turns)} turns and {len(acc.gaps)} "
          f"gaps; {acc.in_programs_s:.4f} s lie between the ops of one "
          f"program run", flush=True)


# -- what the readers call ---------------------------------------------------------


def turn_ms(run, piece: str) -> Optional[float]:
    acc = account(run)
    return _mean_ms(acc.turns, piece) if acc and acc.turns else None


def gap_ms(run, piece: str) -> Optional[float]:
    acc = account(run)
    return _mean_ms(acc.gaps, piece) if acc and acc.gaps else None


def accounted_share(run) -> Optional[float]:
    acc = account(run)
    if acc is None or acc.idle_s <= 0:
        return None
    return acc.accounted_s / acc.idle_s * 100.0
