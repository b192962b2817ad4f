"""JAX profiler + XLA dump hooks (SURVEY §5 tracing/profiling).

Reference role: the reference wires pprof/trace endpoints into its Go
runtime; the TPU-native equivalent is the JAX/XLA toolchain —
``jax.profiler`` traces (viewable in TensorBoard/Perfetto, includes XLA
op timelines and TPU HLO steps) and ``--xla_dump_to`` HLO dumps. This
module owns the process-wide profiler state; the management API exposes
it at /debug/profiler/* (write-gated).

XLA dump caveat: XLA reads XLA_FLAGS once at backend init, so a dump
directory can only be enabled for the NEXT process start —
``configure_xla_dump`` therefore reports whether it took effect live or
must be exported before relaunch.
"""

from __future__ import annotations

import glob
import os
import threading
import time
from typing import Any, Dict, Optional


class ProfilerControl:
    """Serialized start/stop around the process-global jax.profiler."""

    def __init__(self, base_dir: str = "/tmp/srt-profiles") -> None:
        self.base_dir = base_dir
        self._lock = threading.Lock()
        self._active_dir: Optional[str] = None
        self._started_at = 0.0

    def start(self, log_dir: str = "") -> Dict[str, Any]:
        with self._lock:
            if self._active_dir is not None:
                return {"error": "profiler already running",
                        "dir": self._active_dir, "status": 409}
            target = log_dir or os.path.join(
                self.base_dir, time.strftime("%Y%m%d-%H%M%S"))
            os.makedirs(target, exist_ok=True)
            import jax

            jax.profiler.start_trace(target)
            self._active_dir = target
            self._started_at = time.time()
            return {"started": True, "dir": target}

    def stop(self, force: bool = False) -> Dict[str, Any]:
        with self._lock:
            if self._active_dir is None:
                return {"error": "profiler not running", "status": 409}
            import jax

            # a failed stop (full disk, profiler-internal error) keeps
            # the session marked active so the operator can RETRY stop().
            # But when jax's own session is already gone (stop_trace got
            # far enough to terminate it before raising), a retry can
            # never succeed — detect that, or accept force=True, and
            # clear the marker so the profiler doesn't wedge permanently.
            try:
                jax.profiler.stop_trace()
            except Exception as exc:
                msg = str(exc).lower()
                session_gone = ("no profile" in msg or "not started" in msg
                                or "no active" in msg
                                or "not running" in msg)
                if force or session_gone:
                    target, self._active_dir = self._active_dir, None
                    return {"error": f"stop_trace failed: {exc}"[:300],
                            "dir": target, "cleared": True,
                            "status": 500}
                return {"error": f"stop_trace failed: {exc}"[:300],
                        "dir": self._active_dir, "retryable": True,
                        "hint": "retry stop, or stop?force=1 to clear",
                        "status": 500}
            target, self._active_dir = self._active_dir, None
            files = sorted(
                os.path.relpath(p, target)
                for p in glob.glob(os.path.join(target, "**", "*"),
                                   recursive=True) if os.path.isfile(p))
            return {"stopped": True, "dir": target, "files": files,
                    "duration_s": round(time.time() - self._started_at, 3)}

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "running": self._active_dir is not None,
                "dir": self._active_dir,
                "elapsed_s": round(time.time() - self._started_at, 3)
                if self._active_dir else 0.0,
                "xla_dump": _current_xla_dump(),
            }


def _current_xla_dump() -> Optional[str]:
    for part in os.environ.get("XLA_FLAGS", "").split():
        if part.startswith("--xla_dump_to="):
            return part.split("=", 1)[1]
    return None


def configure_xla_dump(dump_dir: str) -> Dict[str, Any]:
    """Add --xla_dump_to to XLA_FLAGS. Effective immediately only for
    NOT-yet-compiled programs in a NOT-yet-initialized backend; once a
    backend exists the setting applies to the next process start, and the
    response says so rather than pretending."""
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(p for p in flags.split()
                     if not p.startswith("--xla_dump_to="))
    os.environ["XLA_FLAGS"] = (flags + f" --xla_dump_to={dump_dir}").strip()
    os.makedirs(dump_dir, exist_ok=True)
    # private-API probe guarded: jax._src carries no stability promise,
    # and a half-applied endpoint (flags mutated, then AttributeError →
    # 500) would be worse than the conservative answer
    try:
        import jax

        live = not jax._src.xla_bridge._backends  # type: ignore
    except Exception:
        live = False
    return {"configured": True, "dir": dump_dir,
            "effective": "now" if live else "next process start"}


def trace_span(name: str, **facts):
    """Named region in the profiler timeline, on the same clock as the
    device's ops: ``with trace_span("engine.step", rows=4): ...``.  The
    name is a constant; what varies rides in ``facts`` and becomes the
    event's stats.  The profiler session (``ProfilerControl.start``, or
    any ``jax.profiler.start_trace``) is the switch: with none running
    this is an object and a flag test, and nothing is encoded."""
    import jax

    return jax.profiler.TraceAnnotation(name, **facts)


default_profiler = ProfilerControl()
