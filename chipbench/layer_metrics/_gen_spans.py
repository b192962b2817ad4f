"""What the readers of the generative cell share: the ``gen.*`` steps of the
traced window, the ``engine.gen.forward`` marker that follows each (what only
a forward's readback knew: routed pairs, experts touched, expert load), and
the device ops by their whole ``tf_op`` path (``_program_spans`` keeps only
the first of the scopes it knows; the generative program's are
``layers_<i>/attn``, ``layers_<i>/moe``, ``moe/gmm``, ``lm_head``).

On a program without these annotations — the parent of the PR that added
them — every function here gives None or nothing.
"""

from __future__ import annotations

import bisect
import functools
import re
from typing import Any, Dict, List, Optional, Tuple

from chipbench import cells
from chipbench.layer_metrics import _program_spans
from chipbench.reduce_trace import HOST_PLANE, NS

FORWARD = "engine.gen.forward"


def steps(run) -> Optional[List[_program_spans.Step]]:
    ps = _program_spans.load(run)
    if ps is None:
        return None
    mine = [st for st in ps.steps
            if str(st.facts.get("flavour", "")).startswith("gen.")]
    return mine or None


def step_mean_ms(run, flavours) -> Optional[float]:
    mine = [st for st in steps(run) or ()
            if st.facts.get("flavour") in flavours]
    if not mine:
        return None
    return sum(st.end - st.start for st in mine) / len(mine) * 1e3


@functools.lru_cache(maxsize=2)
def _markers(path: str) -> List[Tuple[float, Dict[str, Any]]]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == FORWARD:
                    out.append((e.start_ns * NS, dict(e.stats)))
    return sorted(out, key=lambda m: m[0])


def forwards(run) -> List[Tuple[_program_spans.Step, Dict[str, Any]]]:
    """Each traced ``gen.*`` step with the marker written right after it
    (the generation's one thread writes step, marker, step, ...)."""
    mine = steps(run)
    if not mine:
        return []
    marks = _markers(run["trace"]["path"])
    starts = [m[0] for m in marks]
    out = []
    for n, st in enumerate(mine):
        i = bisect.bisect_left(starts, st.end - 1e-6)
        before = mine[n + 1].start if n + 1 < len(mine) else float("inf")
        if i < len(marks) and marks[i][0] <= before \
                and marks[i][1].get("flavour") == st.facts.get("flavour"):
            out.append((st, marks[i][1]))
    return out


@functools.lru_cache(maxsize=2)
def _tf_ops(path: str) -> Dict[str, str]:
    """Device 0's event name -> its ``tf_op``, from the file (the same few
    fields of the wire format that ``_program_spans`` reads)."""
    f = _program_spans._fields
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    for number, wire, plane in f(space):
        if number != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for n, w, v in f(plane):
            if n == 2 and w == 2:
                name = bytes(v).decode()
            elif n == 4 and w == 2:
                events.append(v)
            elif n == 5 and w == 2:
                for n2, w2, v2 in f(v):
                    if n2 == 2 and w2 == 2:
                        meta = {a: c for a, _, c in f(v2)}
                        stat_names[meta.get(1, 0)] = bytes(
                            meta.get(2, b"")).decode()
        if not _program_spans.DEVICE_PLANE.match(name):
            continue
        ids = {k for k, v in stat_names.items() if v == "tf_op"}
        out = {}
        for entry in events:
            for n2, w2, v2 in f(entry):
                if n2 != 2 or w2 != 2:
                    continue
                ev_name, tf_op = "", ""
                for n3, w3, v3 in f(v2):
                    if n3 == 2 and w3 == 2:
                        ev_name = bytes(v3).decode()
                    elif n3 == 5 and w3 == 2:
                        stat = {a: c for a, _, c in f(v3)}
                        if stat.get(1) in ids:
                            tf_op = bytes(stat[5]).decode() if 5 in stat \
                                else stat_names.get(stat.get(7), "")
                out[ev_name] = tf_op
        return out
    return {}


@functools.lru_cache(maxsize=8)
def _scope_ops(path: str, scope: str) -> Tuple[List[float], List[float]]:
    """(starts, lengths), by start, of device 0's ops whose ``tf_op`` path
    holds ``/<scope>/``."""
    ps = _program_spans._load(path)
    tf = _tf_ops(path)
    pattern, its_path = cells.load_module("opcount", "moe_gmm").UNSCOPED
    rx = re.compile(pattern)
    needle = f"/{scope}/"
    mine = [(s, t - s) for s, t, _, name in ps.ops if needle in
            f"/{its_path if rx.search(name) else tf.get(name, '')}/"]
    return [m[0] for m in mine], [m[1] for m in mine]


def scope_seconds(run, scope: str, within=None) -> Optional[float]:
    """Seconds of device ops whose ``tf_op`` path holds ``/<scope>/``,
    all of the traced window or those begun inside ``within = (a, b)``."""
    ps = _program_spans.load(run)
    if ps is None or not ps.ops:
        return None
    starts, lengths = _scope_ops(run["trace"]["path"], scope)
    if within is None:
        return sum(lengths)
    return sum(lengths[bisect.bisect_left(starts, within[0]):
                       bisect.bisect_right(starts, within[1])])


def scope_ms_per_route(run, scope: str) -> Optional[float]:
    done = (run.get("trace") or {}).get("completed")
    if not done or steps(run) is None:
        return None
    secs = scope_seconds(run, scope)
    return secs / len(done) * 1e3 if secs else None
