"""Trace smoke (make trace-smoke, tier-1): boot the routing pipeline over
a fake shared-trunk engine, push 50 mixed-signal requests through it, and
assert every request's trace survived the fused batcher — a batch.ride
span linked to a batch.execute step span, with the per-stage spans the
acceptance criteria name (queue wait, tokenization/cache-hit, and the
step's host stages: stack, h2d, dispatch, readback, demux)."""

import pytest

from semantic_router_tpu.config.schema import (
    DomainRule,
    NamedRule,
    RouterConfig,
    SignalsConfig,
)
from semantic_router_tpu.engine.testing import make_shared_trunk_engine
from semantic_router_tpu.observability.flightrec import FlightRecorder
from semantic_router_tpu.observability.metrics import (
    MetricSeries,
    MetricsRegistry,
)
from semantic_router_tpu.observability.tracing import Tracer
from semantic_router_tpu.router.pipeline import Router

N_REQUESTS = 50

TEXTS = [
    "what is the capital of france",
    "sue them for breach of contract immediately",
    "does this medicine interact with alcohol",
    "design a distributed consensus algorithm step by step",
    "this answer was wrong, fix the numbers please",
]


@pytest.fixture(scope="module")
def stack():
    """Router over a shared-trunk fake engine whose three sequence tasks
    (intent, fact_check, user_feedback) back three learned signal
    families — the K-signal fan-out rides ONE fused batch."""
    engine = make_shared_trunk_engine(
        metrics=MetricSeries(MetricsRegistry()))
    cfg = RouterConfig(
        default_model="backend-model",
        signals=SignalsConfig(
            domains=[DomainRule(name=lbl) for lbl in
                     ("business", "law", "health", "computer science",
                      "other")],
            fact_check=[NamedRule(name="fact_check")],
            user_feedbacks=[NamedRule(name="positive"),
                            NamedRule(name="negative")],
        ),
    )
    # full detail: every trace keeps its per-stage children, not just
    # the default 10% sample
    tracer = Tracer(capacity=N_REQUESTS * 40, sample_rate=1.0)
    router = Router(cfg, engine=engine,
                    metrics=MetricSeries(MetricsRegistry()),
                    tracer=tracer, flightrec=FlightRecorder())
    yield router, tracer
    router.shutdown()
    engine.shutdown()


def _body(text: str) -> dict:
    return {"model": "auto",
            "messages": [{"role": "user", "content": text}]}


class TestTraceSmoke:
    def test_every_trace_rides_a_linked_batch(self, stack):
        router, tracer = stack
        trace_ids = []
        for i in range(N_REQUESTS):
            res = router.route(_body(f"{TEXTS[i % len(TEXTS)]} #{i}"))
            assert res.kind == "route"
            trace_ids.append(res.trace_id)

        steps = {(s.trace_id, s.span_id): s
                 for s in tracer.spans("batch.execute")}
        assert steps, "no batch.execute step spans were emitted"
        for tid in trace_ids:
            spans = tracer.trace(tid)
            names = {s.name for s in spans}
            # the acceptance stage set, per request trace
            assert {"router.route", "signals.evaluate", "batch.wait",
                    "batch.tokenize", "batch.ride", "batch.stack",
                    "batch.h2d", "batch.dispatch", "batch.readback",
                    "batch.demux"} <= names, \
                f"trace {tid} missing stages: {sorted(names)}"
            rides = [s for s in spans if s.name == "batch.ride"]
            assert rides, f"trace {tid} has no batch.ride span"
            for ride in rides:
                assert ride.links, "batch.ride span carries no span link"
                link = ride.links[0]
                step = steps.get((link["trace_id"], link["span_id"]))
                assert step is not None, \
                    "ride links to a step span that was never recorded"
                assert step.name == "batch.execute"
                assert step.attributes["kind"] == "fused"

    def test_mixed_task_steps_report_task_mix(self, stack):
        router, tracer = stack
        fused = [s for s in tracer.spans("batch.execute")
                 if s.attributes.get("kind") == "fused"]
        assert fused
        mixes = [s.attributes.get("task_mix", "") for s in fused]
        assert any("intent" in m and "fact_check" in m for m in mixes), \
            f"no step saw the mixed-task fan-out: {mixes[:5]}"

    def test_flight_recorder_captured_ride_spans(self, stack):
        router, tracer = stack
        dump = router.flightrec.dump()
        assert dump["slowest"]
        names = {s["name"] for rec in dump["slowest"]
                 for s in rec["spans"]}
        assert "batch.ride" in names
