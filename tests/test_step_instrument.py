"""The one step instrument (observability.batchtrace + profiler): under a
profiler session every device step is an ``engine.step`` annotation around
its five stage annotations with the step's facts as stats, every queued
item an ``engine.queue_wait`` carrying its route's trace id; sampled or
not, traced or not, a step runs the same jitted program with no device
sync of its own; warm-up compiles only programs the runners can reach."""

import contextlib
import glob
import logging
import os
import re
import threading
import time

import jax
import pytest

from semantic_router_tpu.engine.testing import (
    make_embedding_engine,
    make_shared_trunk_engine,
)
from semantic_router_tpu.observability import batchtrace
from semantic_router_tpu.observability.tracing import Tracer

PII = ("pii", ["O", "B-PER", "I-PER"])
SEQ_TASKS = ["intent", "fact_check"]


def _profiled(log_dir, fn):
    """Run ``fn`` under a profiler session; the program's annotations as
    (line index, name, start_ns, end_ns, stats) rows."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    rows = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("engine.", "router.route")):
                    rows.append((i, e.name, e.start_ns,
                                 e.start_ns + e.duration_ns,
                                 dict(e.stats)))
    return out, rows


def _steps(rows):
    """[(step row, [stage rows inside it on its thread])]."""
    out = []
    for line, name, s, e, stats in rows:
        if name != batchtrace.STEP_ANNOTATION:
            continue
        inner = sorted((r for r in rows
                        if r[0] == line and r[1].startswith(
                            batchtrace.STEP_ANNOTATION + ".")
                        and s <= r[2] and r[3] <= e), key=lambda r: r[2])
        out.append(((line, name, s, e, stats), inner))
    return out


def _unfused(tracer):
    eng = make_shared_trunk_engine(fuse=False)
    eng.classify("intent", "warm the shape first")

    def go():
        with tracer.span("router.route") as root:
            eng.classify("intent", "per task path, one row")
        return [root.trace_id], 1

    return eng, go, {"group": "task:intent", "flavour": "intent",
                     "rows": 1, "padded_rows": 1}


def _fused_unpacked(tracer):
    eng = make_shared_trunk_engine(token_tasks=[PII])
    eng.token_classify("pii", "warm the shape first")

    def go():
        with tracer.span("router.route") as root:
            eng.token_classify("pii", "one row rides unpacked")
        return [root.trace_id], 1

    return eng, go, {"group": "trunk:trunk0", "flavour": "tok",
                     "rows": 1, "padded_rows": 1}


def _fused_packed(tracer):
    eng = make_shared_trunk_engine(token_tasks=[PII])
    texts = ["two short prompts", "share one packed row"]
    eng.classify_multi(SEQ_TASKS, texts)

    def go():
        with tracer.span("router.route") as root:
            eng.classify_multi(SEQ_TASKS, texts)
        return [root.trace_id], 2

    return eng, go, {"group": "trunk:trunk0", "flavour": "seq",
                     "rows": 1, "padded_rows": 1}


def _embedding(tracer):
    eng = make_embedding_engine()
    eng.embed("embedding", ["warm the shape first"])

    def go():
        with tracer.span("router.route") as root:
            eng.embed("embedding", ["an embedding step"])
        return [root.trace_id], 1

    return eng, go, {"group": "task:embedding", "flavour": "embed",
                     "rows": 1, "padded_rows": 1}


@pytest.mark.parametrize("make", [_unfused, _fused_unpacked, _fused_packed,
                                  _embedding])
def test_profiler_session_holds_step_stages_and_queue_waits(make, tmp_path):
    tracer = Tracer(sample_rate=1.0)
    eng, go, facts = make(tracer)
    try:
        (trace_ids, n_items), rows = _profiled(tmp_path, go)
    finally:
        eng.shutdown()
    steps = _steps(rows)
    # one engine.step per device step the request traces saw
    executed = [s for s in tracer.spans(batchtrace.STEP_SPAN)]
    assert len(steps) == len(executed) == 1
    (_, _, s, e, stats), inner = steps[0]
    # the five stages, nested, in order, one after the other
    assert [r[1] for r in inner] == [
        batchtrace.STAGE_ANNOTATIONS[n] for n in batchtrace.STAGES]
    assert s <= inner[0][2] and inner[-1][3] <= e
    for a, b in zip(inner, inner[1:]):
        assert a[3] <= b[2]
    # the step's facts are stats, not part of the name
    for k, v in facts.items():
        assert stats[k] == v, (k, stats)
    assert stats["bucket"] == 32 and stats["tokens_real"] > 0
    # one queue wait per item, each with its route's trace id
    waits = [r for r in rows if r[1] == batchtrace.QUEUE_WAIT_ANNOTATION]
    assert len(waits) == n_items
    for w in waits:
        assert w[4]["trace_id"] in trace_ids
        assert w[4]["wait_us"] >= 0 and w[4]["group"]
        assert w[3] <= s  # it left the queue before its step began


def test_router_route_annotation_joins_items_to_routes(tmp_path):
    from semantic_router_tpu.config.schema import (
        DomainRule,
        NamedRule,
        RouterConfig,
        SignalsConfig,
    )
    from semantic_router_tpu.router.pipeline import Router

    engine = make_shared_trunk_engine()
    cfg = RouterConfig(
        default_model="backend-model",
        signals=SignalsConfig(
            domains=[DomainRule(name=lbl) for lbl in
                     ("business", "law", "health", "computer science",
                      "other")],
            fact_check=[NamedRule(name="fact_check")]))
    router = Router(cfg, engine=engine, tracer=Tracer(sample_rate=0.0))

    def body(text):
        return {"model": "auto",
                "messages": [{"role": "user", "content": text}]}

    try:
        router.route(body("warm every shape"))
        results, rows = _profiled(tmp_path, lambda: [
            router.route(body(f"which court hears this appeal #{i}"))
            for i in range(4)])
    finally:
        router.shutdown()
        engine.shutdown()
    routes = {r[4]["trace_id"]: r for r in rows
              if r[1] == batchtrace.ROUTE_ANNOTATION}
    assert set(routes) == {res.trace_id for res in results}
    # ... and a marker at each route's end that carries its length, for
    # the routes a session does not see begin
    done = {r[4]["trace_id"]: r for r in rows
            if r[1] == batchtrace.ROUTE_DONE_ANNOTATION}
    assert set(done) == set(routes)
    for tid, (_, _, s, e, _) in routes.items():
        began = done[tid][3] * 1e-9 - done[tid][4]["route_us"] * 1e-6
        assert abs(began - s * 1e-9) < 5e-3 and e <= done[tid][3]
    waits = [r for r in rows if r[1] == batchtrace.QUEUE_WAIT_ANNOTATION]
    assert {w[4]["trace_id"] for w in waits} == set(routes)
    for w in waits:  # an item waits inside the route that sent it
        _, _, s, e, _ = routes[w[4]["trace_id"]]
        assert s <= w[2] and w[3] <= e
    assert len(_steps(rows)) >= 1


def _together(eng, calls):
    """Run the engine calls on threads so that their items ride ONE step:
    the picker is held (in-flight cap 0) until every item is queued."""
    b = eng.batcher
    b._inflight_cap = lambda key: 0
    out = [None] * len(calls)
    threads = [threading.Thread(
        target=lambda i=i, c=c: out.__setitem__(i, c()))
        for i, c in enumerate(calls)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 30.0
    while b.queue_depths()["pending_items"] < len(calls):
        assert time.monotonic() < deadline, "items never queued"
        time.sleep(0.001)
    del b._inflight_cap  # the class's own again
    with b._wake:
        b._wake.notify()
    for t in threads:
        t.join(60.0)
    return out


@pytest.fixture
def spied_engine(monkeypatch):
    """Shared-trunk engine with a token head, every flavour compiled,
    whose group programs and ``jax.block_until_ready`` record their
    calls."""
    eng = make_shared_trunk_engine(token_tasks=[PII])
    eng.classify_multi(SEQ_TASKS, ["warm seq"])
    eng.token_classify("pii", "warm tok")
    _together(eng, [lambda: eng.classify_multi(SEQ_TASKS, ["warm both"]),
                    lambda: eng.token_classify("pii", "warm both")])
    g = next(iter(eng._groups_by_gid.values()))
    calls, syncs = [], []
    fns = dict(g.fns)
    for key, fn in g.fns.items():
        if callable(fn):
            fns[key] = (lambda *a, _k=key, _f=fn:
                        (calls.append(_k), _f(*a))[1])
    g.fns = fns
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (syncs.append(1), x)[1])
    yield eng, calls, syncs
    eng.shutdown()


TEXT = "Maria Keller asked whether tracing changes the numbers"


def _seq(eng, tracer):
    with _span(tracer):
        out = eng.classify_multi(SEQ_TASKS, [TEXT])
    return {task: out[task][0].probs for task in SEQ_TASKS}


def _tok(eng, tracer):
    with _span(tracer):
        out = eng.token_classify("pii", TEXT)
    return [(s.start, s.end, s.type, s.score) for s in out.entities]


def _span(tracer):
    return contextlib.nullcontext() if tracer is None \
        else tracer.span("router.route")


@pytest.mark.parametrize("asks,program", [
    ((_seq,), "seq"), ((_tok,), "tok"), ((_seq, _tok), "both")])
def test_sampling_never_changes_the_program(spied_engine, asks, program):
    eng, calls, syncs = spied_engine
    got = {}
    for label, tracer in (("sampled", Tracer(sample_rate=1.0)),
                          ("unsampled", Tracer(sample_rate=0.0)),
                          ("untraced", None)):
        del calls[:]
        got[label] = _together(
            eng, [lambda ask=ask: ask(eng, tracer) for ask in asks])
        if tracer is not None:
            names = {s.name for s in tracer.spans("batch.")}
            assert "batch.ride" in names
            assert ("batch.dispatch" in names) == (label == "sampled")
        # the same jitted function, whatever the trace says
        assert calls == [program], (label, calls)
    assert syncs == []  # no step fences the device
    assert not hasattr(batchtrace.BatchStep, "fence")
    # bit-identical, not merely close
    assert got["sampled"] == got["unsampled"] == got["untraced"]


def test_warmup_compiles_only_programs_the_runners_reach():
    eng = make_shared_trunk_engine(token_tasks=[PII])
    compiled = []

    class Names(logging.Handler):
        def emit(self, record):
            m = re.match(r"Compiling (\S+) with global shapes",
                         record.getMessage())
            if m:
                compiled.append(m.group(1))

    handler = Names()
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles(True):
            eng.warmup(buckets=[32], batch_sizes=[1, 2])
            g = next(iter(eng._groups_by_gid.values()))
            reachable = {f"jit({fn.__name__})" for fn in g.fns.values()
                         if callable(fn)}
            assert compiled and set(compiled) <= reachable, compiled
            # ... and a sampled trace at a warmed shape compiles nothing
            del compiled[:]
            tracer = Tracer(sample_rate=1.0)
            with tracer.span("router.route"):
                eng.classify_multi(SEQ_TASKS, ["a sampled request"])
                eng.token_classify("pii", "a sampled token request")
            assert compiled == []
    finally:
        logger.removeHandler(handler)
        eng.shutdown()
