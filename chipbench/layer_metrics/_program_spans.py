"""What the program's own profiler annotations say, read once per run and
shared by the readers beside this file.

The program (``semantic_router_tpu/observability/batchtrace.py``) writes,
while a profiler session runs, ``router.route.done`` (``trace_id``,
``route_us``) and ``engine.queue_wait`` (``trace_id``, ``group``,
``wait_us``) — each marks the END of its interval and carries its length,
so a route that began before the session is still seen — and
``engine.step`` (``group``, ``flavour``, ``bucket``, ``rows``,
``padded_rows``, ``tokens_real``) around ``engine.step.stack`` / ``.h2d`` /
``.dispatch`` / ``.readback`` / ``.demux``, all on the host plane and on the
same clock as the device plane's ops; ``jax.named_scope`` names
(``trunk``, ``pool``, ``heads``, ``token_heads``, ...) sit in each device
op's ``tf_op``.  A tree without them — the parent of the PR that added
them — gives ``load(run) is None``, and every reader then returns None.

Two things ``jax.profiler.ProfileData`` does not give are taken from the
file itself: which program run a step launched (the ``run_id`` that
``DoEnqueueProgram`` on the host and the ``XLA Modules`` event on the device
share, reached from the step's dispatch span through the ``_p`` -> ``_c``
links PjRt leaves), and an op's ``tf_op``, which is a stat of the event's
metadata and not of the event (a few lines of protobuf wire format, below).
"""

from __future__ import annotations

import bisect
import functools
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from chipbench import stats as stats_mod
from chipbench.layer_metrics._window import step_delta
from chipbench.reduce_trace import DEVICE_PLANE, HOST_PLANE, NS, OPS_LINE

STEP = "engine.step"
QUEUE_WAIT = "engine.queue_wait"
ROUTE_DONE = "router.route.done"
MODULES_LINE = "XLA Modules"
# the scopes the program names, outermost first match wins
SCOPES = ("embed_tokens", "trunk", "pool", "heads", "token_heads",
          "matryoshka")
HEAD_SCOPES = ("pool", "heads", "token_heads")

Interval = Tuple[float, float]


@dataclass
class Step:
    start: float
    end: float
    facts: Dict[str, Any]
    stages: Dict[str, Interval] = field(default_factory=dict)
    # [first device op's start, last device op's end] of the program run
    # this step launched; None if the run was not found on the device
    device: Optional[Interval] = None


@dataclass
class ProgramSpans:
    window: Interval  # first to last event of the session, seconds
    steps: List[Step]
    waits: List[Tuple[str, float, float]]  # (trace_id, start, end)
    routes: Dict[str, Interval]  # completed: trace_id -> (start, end)
    # device 0, by start: (start, end, scope, the op's name = its HLO text)
    ops: List[Tuple[float, float, str, str]]


def load(run) -> Optional[ProgramSpans]:
    trace = run.get("trace")
    if not trace or not trace.get("path"):
        return None
    return _load(trace["path"])


@functools.lru_cache(maxsize=2)
def _load(path: str) -> Optional[ProgramSpans]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lo, hi = float("inf"), float("-inf")
    # per host line: the program's spans, and PjRt's linked events
    lines: List[Dict[str, list]] = []
    consumers: Dict[Tuple[int, int], Tuple[int, float, float]] = {}
    modules: Dict[int, Interval] = {}
    ops: List[Tuple[float, float, str, str]] = []
    device_seen = False
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                row = {"spans": [], "producers": [], "runs": []}
                for e in line.events:
                    s, t = e.start_ns * NS, (e.start_ns + e.duration_ns) * NS
                    lo, hi = min(lo, s), max(hi, t)
                    name = e.name
                    if name == ROUTE_DONE or name.startswith("engine."):
                        row["spans"].append((name, s, t, dict(e.stats)))
                        continue
                    st = dict(e.stats)
                    if "_c" in st:  # keyed by (context type, id)
                        consumers[(int(st.get("_ct", 0)), int(st["_c"]))] \
                            = (len(lines), s, t)
                    if "_p" in st:
                        row["producers"].append(
                            (s, t, (int(st.get("_pt", 0)), int(st["_p"]))))
                    if "run_id" in st:
                        row["runs"].append((s, t, int(st["run_id"])))
                lines.append(row)
        elif DEVICE_PLANE.match(plane.name) and not device_seen:
            device_seen = True  # steps are attributed on the first device
            scope_of = _scopes_by_op_name(path, plane.name)
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        st = dict(e.stats)
                        if "run_id" in st:
                            modules[int(st["run_id"])] = (
                                e.start_ns * NS,
                                (e.start_ns + e.duration_ns) * NS)
                elif line.name == OPS_LINE:
                    for e in line.events:
                        s = e.start_ns * NS
                        t = (e.start_ns + e.duration_ns) * NS
                        lo, hi = min(lo, s), max(hi, t)
                        ops.append((s, t, scope_of.get(e.name, ""), e.name))
    steps = [step for i in range(len(lines))
             for step in _steps_of(i, lines, consumers, modules)]
    if not steps:
        return None
    ops.sort()
    waits, routes = [], {}
    for row in lines:
        for name, s, t, st in row["spans"]:
            tid = str(st.get("trace_id", ""))
            if name == QUEUE_WAIT:
                waits.append((tid, t - float(st.get("wait_us", 0)) * 1e-6,
                              t))
            elif name == ROUTE_DONE and tid:
                routes[tid] = (t - float(st.get("route_us", 0)) * 1e-6, t)
    return ProgramSpans((lo, hi), sorted(steps, key=lambda x: x.start),
                        waits, routes, ops)


def _steps_of(i: int, lines, consumers, modules) -> Iterator[Step]:
    """The ``engine.step`` spans of host line ``i`` with their stages (the
    stage spans inside them on the same thread) and their program run."""
    spans = lines[i]["spans"]
    for name, s, t, st in spans:
        if name != STEP:
            continue
        step = Step(s, t, st)
        for n2, s2, t2, _ in spans:
            if n2.startswith(STEP + ".") and s <= s2 and t2 <= t:
                step.stages[n2[len(STEP) + 1:]] = (s2, t2)
        if "dispatch" in step.stages:
            runs = _run_ids(i, step.stages["dispatch"], lines, consumers, 0)
            found = [modules[r] for r in runs if r in modules]
            if found:
                step.device = (min(a for a, _ in found),
                               max(b for _, b in found))
        yield step


def _run_ids(i: int, within: Interval, lines, consumers, depth: int
             ) -> List[int]:
    """``run_id`` of every program enqueued inside ``within`` on line ``i``,
    or inside what an event there handed on to (``_p`` of a producer is the
    ``_c`` of its consumer, which may be on another line)."""
    a, b = within
    out = [r for s, t, r in lines[i]["runs"] if a <= s and t <= b]
    if depth < 4:
        for s, t, pid in lines[i]["producers"]:
            if a <= s and t <= b and pid in consumers:
                j, cs, ct = consumers[pid]
                if (j, cs, ct) != (i, a, b):
                    out += _run_ids(j, (cs, ct), lines, consumers,
                                    depth + 1)
    return sorted(set(out))


# -- what readers share ----------------------------------------------------------


def stage_mean_ms(run, stage: str) -> Optional[float]:
    ps = load(run)
    if ps is None:
        return None
    lengths = [st.stages[stage][1] - st.stages[stage][0]
               for st in ps.steps if stage in st.stages]
    return sum(lengths) / len(lengths) * 1e3 if lengths else None


def rows_per_route(run, flavour: str) -> Optional[float]:
    """The window's ``trunk_rows_per_route`` (the step counters' real rows
    over the routes completed, as the metric of that name reads it) split
    by the flavours' shares of the ``rows`` that the traced window's
    ``engine.step`` spans carry.  A 10 s trace holds a handful of steps and
    loses those that straddle its edges, so rows seen over routes completed
    would read a third low; the shares do not care."""
    ps = load(run)
    if ps is None or not run["completed"]:
        return None
    seen = sum(int(st.facts.get("rows", 0)) for st in ps.steps)
    if not seen:
        return None
    mine = sum(int(st.facts.get("rows", 0)) for st in ps.steps
               if st.facts.get("flavour") == flavour)
    per_route = step_delta(run["steps"])["rows_real"] / len(run["completed"])
    return mine / seen * per_route


def idle_by_cause(ps: ProgramSpans) -> Dict[str, float]:
    """Seconds of device idle time between the session's first and last
    event, by cause.  Each idle interval is cut at the steps' edges; a
    piece inside a step goes to the innermost (latest-begun) step covering
    it: ``step_head`` until that step's program begins on the device,
    ``step_tail`` from then on; a piece under no step is
    ``between_steps``."""
    busy = [(s, t) for s, t, _, _ in ps.ops]
    out = {"step_head": 0.0, "step_tail": 0.0, "between_steps": 0.0}
    cuts = sorted({x for st in ps.steps for x in (
        st.start, st.end, st.device[0] if st.device else st.end)})
    for a, b in stats_mod.gaps(busy, ps.window):
        edges = [a] + cuts[bisect.bisect_right(cuts, a):
                           bisect.bisect_left(cuts, b)] + [b]
        for x, y in zip(edges, edges[1:]):
            mid = (x + y) / 2
            cover = [st for st in ps.steps if st.start <= mid < st.end]
            if not cover:
                out["between_steps"] += y - x
                continue
            st = max(cover, key=lambda c: c.start)
            head_end = st.device[0] if st.device else st.end
            out["step_head" if mid < head_end else "step_tail"] += y - x
    return out


def idle_share(run, cause: str) -> Optional[float]:
    """One cause's part of the line's idle share (1 - busy_s / window_s),
    in %.  ``step_head`` and ``step_tail`` are their seconds over the
    line's window; ``between_steps`` is what is left of the line's idle
    share, so the three sum to it: the idle time under no step, and the
    stretch of the harness's window at the session's edges that holds no
    event at all (0.13 s of 10 in the first trace read)."""
    ps = load(run)
    if ps is None:
        return None
    tr = run["trace"]
    window_s = tr["window"][1] - tr["window"][0]
    if window_s <= 0:
        return None
    parts = idle_by_cause(ps)
    in_steps = {c: parts[c] / window_s * 100.0
                for c in ("step_head", "step_tail")}
    if cause in in_steps:
        return in_steps[cause]
    line_idle = (1.0 - tr["busy_s"] / window_s) * 100.0
    return max(0.0, line_idle - sum(in_steps.values()))


def kernel_in_steps(ps: ProgramSpans, pattern: str
                    ) -> List[Tuple[Step, float, int]]:
    """(step, seconds, calls) of the device ops whose name matches
    ``pattern`` inside the program run that each traced ``engine.step``
    span launched: a kernel's time beside the step's own facts (``rows``,
    ``tokens_real``), so that time and work come from ONE set of steps.  A
    step whose program run was not found on the device, or ran no such op,
    is left out on both sides."""
    rx = re.compile(pattern)
    starts = [op[0] for op in ps.ops]
    out = []
    for st in ps.steps:
        if st.device is None:
            continue
        a, b = st.device
        mine = [t - s for s, t, _, name in
                ps.ops[bisect.bisect_left(starts, a):
                       bisect.bisect_right(starts, b)]
                if t <= b and rx.search(name)]
        if mine:
            out.append((st, sum(mine), len(mine)))
    return out


def scope_ms_per_route(run, scopes) -> Optional[float]:
    ps = load(run)
    if ps is None or not run["trace"]["completed"]:
        return None
    if not any(scope for _, _, scope, _ in ps.ops):
        return None  # a program without named scopes
    secs = sum(t - s for s, t, scope, _ in ps.ops if scope in scopes)
    return secs / len(run["trace"]["completed"]) * 1e3


# -- an op's tf_op, from the file ------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped over."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, wire, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, wire, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire}")


def _scopes_by_op_name(path: str, plane_name: str) -> Dict[str, str]:
    """Event name -> the first of ``SCOPES`` on its ``tf_op`` path, for the
    events of one plane.  XSpace.planes = 1; XPlane: name 2,
    event_metadata 4, stat_metadata 5 (map entries: key 1, value 2);
    XEventMetadata: name 2, stats 5; XStatMetadata: id 1, name 2; XStat:
    metadata_id 1, str_value 5, ref_value 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, wire, plane in _fields(space):
        if number != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for n, w, v in _fields(plane):
            if n == 2 and w == 2:
                name = bytes(v).decode()
            elif n == 4 and w == 2:
                events.append(v)
            elif n == 5 and w == 2:
                for n2, w2, v2 in _fields(v):
                    if n2 == 2 and w2 == 2:
                        meta = {a: c for a, _, c in _fields(v2)}
                        stat_names[meta.get(1, 0)] = bytes(
                            meta.get(2, b"")).decode()
        if name != plane_name:
            continue
        tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        out = {}
        for entry in events:
            for n2, w2, v2 in _fields(entry):
                if n2 != 2 or w2 != 2:
                    continue
                ev_name, tf_op = "", ""
                for n3, w3, v3 in _fields(v2):
                    if n3 == 2 and w3 == 2:
                        ev_name = bytes(v3).decode()
                    elif n3 == 5 and w3 == 2:
                        stat = {a: c for a, _, c in _fields(v3)}
                        if stat.get(1) in tf_op_ids:
                            tf_op = bytes(stat[5]).decode() if 5 in stat \
                                else stat_names.get(stat.get(7), "")
                out[ev_name] = _scope(tf_op)
        return out
    return {}


def _scope(tf_op: str) -> str:
    for part in tf_op.split("/"):
        if part in SCOPES:
            return part
    return ""
