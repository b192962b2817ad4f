"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData``.

What a v5e trace holds (looked at by hand, PERF.md section 3): one plane
per chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one
event per executed program, ``jit_both_fn(<id>)``), ``XLA Ops`` (one event
per executed leaf HLO op, named by its whole HLO text; they do not overlap,
so their union is their sum), ``Async XLA Ops`` (copies and slices in
flight, overlapping the ops: not counted as busy) and ``TC Overlay``; host
threads are lines of ``/host:CPU``, where ``jax.profiler.TraceAnnotation``
spans appear under the name they were given.  The benchmark's wrappers write
``chipbench.<span>`` annotations in a traced run, which is what idle gaps
are attributed to.

``python -m chipbench.reduce_trace <file>`` prints what a trace holds.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Any, Dict, List, Tuple

from . import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "chipbench."
NS = 1e-9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * NS, (e.start_ns + e.duration_ns) * NS)
            for e in line.events]


def reduce(path: str) -> Dict[str, Any]:
    """Per-device op intervals, the device-busy union, summed op seconds by
    name, the longest idle gaps with what the host was doing in them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, Any]] = {}
    host_spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            work = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    work.extend(_events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans.extend(ev for ev in _events(line)
                                  if ev[0].startswith(ANNOTATION_PREFIX))
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane "
                         f"({[p.name for p in data.planes]})")
    starts = [s for work in devices.values() for _, s, _ in work]
    ends = [e for work in devices.values() for _, _, e in work]
    if not starts:
        raise ValueError(f"{path}: no operation ran on a device")
    span = (min(starts), max(ends))
    n = len(devices)
    busy = sum(stats.union_length((s, e) for _, s, e in work)
               for work in devices.values()) / n
    op_seconds: Dict[str, float] = {}
    op_calls: Dict[str, int] = {}
    for work in devices.values():
        for name, s, e in work:
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / n
            op_calls[name] = op_calls.get(name, 0) + 1
    first = next(iter(devices.values()))
    idle = sorted(stats.gaps(((s, e) for _, s, e in first), span),
                  key=lambda g: g[0] - g[1])[:10]
    return {"path": path, "devices": sorted(devices), "span": span,
            "busy_s": busy, "op_seconds": op_seconds, "op_calls": op_calls,
            "work_events": sum(len(work) for work in devices.values()),
            "idle_gaps": [[_host_activity(g, host_spans), g[1] - g[0]]
                          for g in idle],
            "host_spans": len(host_spans)}


def _host_activity(gap: Tuple[float, float],
                   host_spans: List[Tuple[str, float, float]]) -> str:
    """The chipbench annotation that covers most of the gap, innermost
    first (an engine call inside a route names the engine call)."""
    cover: Dict[str, float] = {}
    for name, s, e in host_spans:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > 0:
            cover[name] = cover.get(name, 0.0) + o
    if not cover:
        return "no_chipbench_span"
    inner = [k for k in cover if not k.endswith("router.route")] or \
        list(cover)
    return max(inner, key=lambda k: cover[k])[len(ANNOTATION_PREFIX):]


def short_name(event_name: str) -> str:
    """An op event is named by its whole HLO text; the breakdown groups
    ops by what is left of `=` without its number, and the output shape:
    ``%attn.43 = f32[96,8192,64]{...} custom-call(...)`` ->
    ``attn f32[96,8192,64]``."""
    lhs, sep, rhs = event_name.partition(" = ")
    if not sep:
        return event_name[:120]
    base = re.sub(r"[.\d]+$", "", lhs.lstrip("%"))
    shape = re.split(r"[{ ]", rhs.lstrip("("), maxsplit=1)[0]
    return f"{base} {shape}"[:120]


def top_ops(reduced: Dict[str, Any], k: int = 10) -> List[List[Any]]:
    grouped: Dict[str, float] = {}
    for name, secs in reduced["op_seconds"].items():
        key = short_name(name)
        grouped[key] = grouped.get(key, 0.0) + secs
    return [[name, secs] for name, secs in sorted(
        grouped.items(), key=lambda kv: -kv[1])[:k]]


def seconds_matching(reduced: Dict[str, Any], pattern: str
                     ) -> Tuple[float, int]:
    """(summed seconds, calls) of the device ops whose name matches."""
    rx = re.compile(pattern)
    names = [n for n in reduced["op_seconds"] if rx.search(n)]
    return (sum(reduced["op_seconds"][n] for n in names),
            sum(reduced["op_calls"][n] for n in names))


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines[:40]:
            evs = list(line.events)
            names: Dict[str, float] = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0.0) + e.duration_ns * NS
            top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            print(f"  line {line.name!r}: {len(evs)} events; top "
                  + "; ".join(f"{n} {s:.4f}s" for n, s in top))
            for e in evs[:1]:
                try:
                    print(f"    first event stats: "
                          f"{[(k, str(v)[:80]) for k, v in e.stats][:12]}")
                except Exception as exc:
                    print(f"    (stats unreadable: {exc})")


if __name__ == "__main__":
    target = sys.argv[1]
    describe(target if target.endswith(".pb") else find_xplane(target))
