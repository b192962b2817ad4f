"""Counts compilations through JAX's monitoring events.

``backend_compile_duration`` fires once per program the process had to
get, whether XLA compiled it or the persistent cache delivered it (its
duration is then the load); the cache's own hit and miss events tell the
two apart.  Either one after the first timed request makes a run not
correct: the measured window must drive only programs that set-up warmed.
The event names the program (``fun_name``, the jitted function's name), so
a run can say WHICH program it first met where."""

from __future__ import annotations

import threading
from typing import List, NamedTuple, Tuple

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Compile(NamedTuple):
    when: float     # on the installed clock, when the program was there
    seconds: float  # how long compiling or loading it took
    name: str


class CompileWatch:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._events: List[Compile] = []
        self._clock = None
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self, clock) -> "CompileWatch":
        import jax.monitoring

        self._clock = clock
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)
        return self

    def _on_count(self, event: str, **_: object) -> None:
        with self._lock:
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1
            elif event == CACHE_MISS_EVENT:
                self.cache_misses += 1

    def _on_event(self, event: str, duration: float, fun_name: str = "?",
                  **_: object) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self._events.append(Compile(self._clock(), float(duration),
                                            str(fun_name)))

    def total(self) -> Tuple[int, float]:
        with self._lock:
            return len(self._events), sum(c.seconds for c in self._events)

    def since(self, t: float) -> List[Compile]:
        with self._lock:
            return [c for c in self._events if c.when >= t]
