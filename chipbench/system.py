"""The system under test, built the way its entry points build it:
``load_config`` -> ``build_engine`` -> ``build_router``, warmed on the
cell's shape set, with the benchmark's spans put around ``Router.route``
and the engine's public calls from here (nothing in the program is
edited).  No HTTP server, no mock backend.

From the program this takes only the system and its counters:
``engine.warmup_report()``, the step samples of ``runtimestats``, the
batcher's ``queue_wait`` histogram."""

from __future__ import annotations

import contextlib
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import cells


class Spans:
    """In-memory spans of the benchmark's own wrappers: (name, key, start,
    end) on ``time.perf_counter()``; ``key`` is the request text the call
    served, which is how an engine call finds the route that caused it
    (signal evaluators run on pool threads, so no thread-local can)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.rows: List[Tuple[str, str, float, float]] = []
        # what the timed path answered, by request text, for `correct`
        self.answers: Dict[str, Dict[str, Any]] = {}
        self.annotate = False  # --trace 1: also write profiler annotations

    def add(self, name: str, key: str, start: float, end: float) -> None:
        with self._lock:
            self.rows.append((name, key, start, end))

    def answer(self, text: str, task: str, value: Any) -> None:
        with self._lock:
            self.answers.setdefault(text, {})[task] = value

    @contextlib.contextmanager
    def span(self, name: str, key: str):
        ann = None
        if self.annotate:
            import jax.profiler

            ann = jax.profiler.TraceAnnotation(f"chipbench.{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, key, t0, time.perf_counter())
            if ann is not None:
                ann.__exit__(None, None, None)


def _wrap_engine(engine, spans: Spans, calls: Dict[str, Any]) -> None:
    """Instance-level wrappers around the engine's public calls that the
    configuration's family names: a span per call and the call's answers
    kept by text."""
    for name, (arguments, answers) in calls.items():
        inner = getattr(engine, name)

        def wrapped(*args, _inner=inner, _name=name, _arguments=arguments,
                    _answers=answers, **kwargs):
            tasks, texts = _arguments(*args, **kwargs)
            with spans.span(f"engine.{_name}", texts[0] if texts else ""):
                out = _inner(*args, **kwargs)
            for text, task, value in _answers(tasks, texts, out):
                spans.answer(text, task, value)
            return out

        setattr(engine, name, wrapped)


class System:
    def __init__(self, engine, router, spans: Spans, config: Dict[str, Any],
                 engine_built: float) -> None:
        self.engine, self.router, self.spans = engine, router, spans
        self.config = config
        # ``time.perf_counter()`` when ``build_engine`` had returned
        self.engine_built = engine_built

    # -- the entry point under test ---------------------------------------

    def route(self, req) -> Tuple[bool, str]:
        """One ``Router.route()`` call.  Not ok: the route did not come
        back as a routing decision, or a family the engine backs was
        answered fail-open (an error, or not from the engine)."""
        body = {"model": "auto",
                "messages": [{"role": "user", "content": req.text}]}
        with self.spans.span("router.route", req.text):
            result = self.router.route(body)
        if result.kind != "route" or not result.model:
            return False, f"kind={result.kind!r} model={result.model!r}"
        report = result.report
        if report is None:
            return False, "no dispatch report"
        for fam in self.config["required_families"]:
            if fam not in report.results:
                return False, f"family {fam!r} missing"
        for fam in self.config["engine_families"]:
            row = report.results.get(fam)
            if row is None:
                continue
            if row.error:
                return False, f"family {fam!r} error {row.error!r}"
            if row.source not in ("engine", "fused_bank"):
                return False, f"family {fam!r} source {row.source!r}"
        return True, ""

    # -- the program's counters -------------------------------------------

    def step_counters(self) -> Dict[Tuple[str, int, str], Dict[str, float]]:
        """``runtimestats`` per (group, bucket, variant): steps, seconds,
        rows — cumulative; the window is the difference of two reads."""
        rs = self.engine._runtime_stats
        rs.flush()
        return {(p["group"], p["bucket"], p["variant"]): {
            "executes": p["executes"], "execute_s": p["execute_s_total"],
            "compiles": p["compiles"], "rows_real": p["rows_real"],
            "rows_padded": p["rows_padded"]} for p in rs.programs()}

    def queue_wait_totals(self) -> Optional[Tuple[float, int]]:
        """(sum of seconds, count) of the batcher's queue_wait histogram."""
        try:
            h = self.engine.batcher._series().batcher_queue_wait
            return float(sum(h._sums.values())), int(sum(h._totals.values()))
        except AttributeError:
            return None

    def close(self) -> None:
        self.router.shutdown()
        self.engine.shutdown()


def _on_a_thread_of_its_own(fn, *args) -> None:
    """``fn(*args)`` on a new thread, waited for; what it raises is raised
    here.  JAX records the Python stack with every operation it traces,
    and on the chip's host a trace costs more the deeper its caller stands:
    one frame more between ``run.main`` and ``engine.warmup`` made each of
    16 warm-up programs 1.4 s slower, ten frames made the kernels' lowering
    three times as long (PERF.md section 6, PR 27).  A thread starts with an
    empty stack, so the warm-up's seconds do not depend on how deep in the
    harness this is called from."""
    with ThreadPoolExecutor(1, thread_name_prefix="chipbench-warm") as pool:
        pool.submit(fn, *args).result()


def write_router_config(config: Dict[str, Any], ckpt_dirs: Dict[str, str],
                        work_dir: str) -> str:
    """The configuration's static router_config.yaml with the run's
    checkpoint directory filled in."""
    with open(os.path.join(config["dir"], "router_config.yaml")) as f:
        text = f.read()
    root = os.path.dirname(ckpt_dirs["tokenizer"])
    path = os.path.join(work_dir, "router_config.yaml")
    with open(path, "w") as f:
        f.write(text.replace("@CHECKPOINTS@", root))
    return path


def build(config: Dict[str, Any], config_path: str,
          shapes: Dict[str, Sequence[int]]) -> System:
    """Engine + router from the config file, warmed by the configuration's
    family on ``shapes`` (the workload's shape set) and on nothing else."""
    from semantic_router_tpu.config import load_config
    from semantic_router_tpu.runtime.bootstrap import (
        build_engine,
        build_router,
    )

    family = cells.load_family(config)
    cfg = load_config(config_path)
    t0 = time.perf_counter()
    engine = build_engine(cfg)
    if engine is None:
        raise RuntimeError("build_engine returned no engine")
    print(f"setup build_engine: {time.perf_counter() - t0:.2f} s; tasks "
          f"{sorted(engine.tasks())}; trunk groups "
          f"{engine.trunk_group_info()}", flush=True)
    engine_built = t0 = time.perf_counter()
    _on_a_thread_of_its_own(family.warm, engine, config, shapes)
    print(f"setup warmup: {time.perf_counter() - t0:.2f} s", flush=True)
    router = build_router(cfg, engine=engine)
    spans = Spans()
    _wrap_engine(engine, spans, family.ENGINE_CALLS)
    return System(engine, router, spans, config, engine_built)
