"""Cross-batch trace propagation (observability.batchtrace + tracing/otlp/
metrics extensions): span links + monotonic timing + hardened ids, context
capture across the batching boundary, per-stage attribution on fused
batches, OpenMetrics exemplars, and the slow-request flight recorder."""

import re
import threading
import time

import pytest

from semantic_router_tpu.observability import batchtrace
from semantic_router_tpu.observability.flightrec import FlightRecorder
from semantic_router_tpu.observability.metrics import (
    Histogram,
    MetricSeries,
    MetricsRegistry,
)
from semantic_router_tpu.observability.otlp import span_to_otlp
from semantic_router_tpu.observability.tracing import (
    Span,
    Tracer,
    active_span,
    new_span_id,
    new_trace_id,
)


def fresh_series() -> MetricSeries:
    return MetricSeries(MetricsRegistry())


class TestSpanTiming:
    def test_duration_is_monotonic_under_clock_steps(self):
        """An NTP step between start and end skews the exported epoch
        pair but can never produce a negative duration: duration_s reads
        the perf_counter pair."""
        s = Span("x", new_trace_id(), new_span_id())
        s.start_t = time.time() + 3600.0  # clock stepped back after start
        time.sleep(0.01)
        s.end()
        assert s.end_t < s.start_t  # epoch pair IS skewed...
        assert s.duration_s > 0.0  # ...duration is not

    def test_epoch_pair_still_exported(self):
        t = Tracer()
        with t.span("x"):
            time.sleep(0.005)
        (s,) = t.spans("x")
        d = span_to_otlp(s)
        assert int(d["endTimeUnixNano"]) >= int(d["startTimeUnixNano"])
        assert s.duration_s >= 0.005


class TestIdHardening:
    def test_ids_are_hex_of_right_width(self):
        assert re.fullmatch(r"[0-9a-f]{32}", new_trace_id())
        assert re.fullmatch(r"[0-9a-f]{16}", new_span_id())
        assert len({new_trace_id() for _ in range(256)}) == 256

    def test_extract_validates_span_id(self):
        good_trace = "a" * 32
        # malformed parent span id (wrong width / non-hex / all-zero)
        for bad in ("zz", "b" * 15, "B" * 16, "0" * 16, ""):
            tid, parent = Tracer.extract(
                {"traceparent": f"00-{good_trace}-{bad}-01"})
            assert tid != good_trace and parent == ""
        tid, parent = Tracer.extract(
            {"traceparent": f"00-{good_trace}-{'b' * 16}-01"})
        assert tid == good_trace and parent == "b" * 16

    def test_extract_rejects_zero_or_nonhex_trace(self):
        for bad in ("0" * 32, "g" * 32, "a" * 31):
            tid, parent = Tracer.extract(
                {"traceparent": f"00-{bad}-{'b' * 16}-01"})
            assert tid != bad and re.fullmatch(r"[0-9a-f]{32}", tid)


class TestSpanLinks:
    def test_links_round_trip_to_otlp(self):
        s = Span("batch.ride", new_trace_id(), new_span_id())
        s.add_link("c" * 32, "d" * 16)
        s.end()
        d = span_to_otlp(s)
        assert d["links"] == [{"traceId": "c" * 32, "spanId": "d" * 16}]
        # spans without links keep the old shape (no empty links field)
        bare = Span("x", new_trace_id(), new_span_id())
        bare.end()
        assert "links" not in span_to_otlp(bare)


class TestCapture:
    def test_capture_outside_any_span_is_none(self):
        assert batchtrace.capture() is None

    def test_capture_inside_span_carries_ids_and_tracer(self):
        t = Tracer(sample_rate=1.0)
        with t.span("root") as root:
            ctx = batchtrace.capture()
        assert ctx is not None
        assert ctx.tracer is t
        assert ctx.trace_id == root.trace_id
        assert ctx.span_id == root.span_id
        assert ctx.sampled is True

    def test_sample_rate_zero_marks_unsampled(self):
        t = Tracer(sample_rate=0.0)
        with t.span("root"):
            ctx = batchtrace.capture()
        assert ctx is not None and ctx.sampled is False

    def test_sampling_is_deterministic_per_trace(self):
        t = Tracer(sample_rate=0.5)
        with t.span("root") as root:
            a = batchtrace.capture()
            b = batchtrace.capture()
        assert a.sampled == b.sampled

    def test_active_span_restored_across_nesting_and_tracers(self):
        t1, t2 = Tracer(), Tracer()
        with t1.span("outer"):
            outer = active_span()
            with t2.span("inner"):
                assert active_span()[0] is t2
            assert active_span() == outer
        assert active_span() is None

    def test_activate_reestablishes_context_on_worker_thread(self):
        t = Tracer()
        seen = {}

        def worker(ctx):
            with batchtrace.activate(ctx, "signal.test"):
                seen["ctx"] = batchtrace.capture()

        with t.span("root") as root:
            ctx = batchtrace.capture()
            th = threading.Thread(target=worker, args=(ctx,))
            th.start()
            th.join()
        assert seen["ctx"].trace_id == root.trace_id
        (child,) = t.spans("signal.test")
        assert child.parent_id == root.span_id


class TestFusedBatchTracing:
    """Acceptance shape: a request fanning K learned signals through the
    fused batcher yields ONE trace with per-stage spans and a batch.ride
    link to the shared batch.execute step span."""

    @pytest.fixture(scope="class")
    def engine(self):
        from semantic_router_tpu.engine.testing import (
            make_shared_trunk_engine,
        )

        eng = make_shared_trunk_engine(metrics=fresh_series())
        yield eng
        eng.shutdown()

    TASKS = ["intent", "fact_check", "user_feedback"]

    def test_mixed_task_batch_yields_linked_stage_spans(self, engine):
        t = Tracer(sample_rate=1.0)
        with t.span("router.route") as root:
            engine.classify_multi(self.TASKS,
                                  ["trace this request end to end"])
            tid = root.trace_id
        names = {s.name for s in t.trace(tid)}
        assert {"batch.wait", "batch.tokenize", "batch.ride"} | {
            f"batch.{n}" for n in batchtrace.STAGES} <= names
        (ride,) = [s for s in t.trace(tid) if s.name == "batch.ride"]
        (step,) = [s for s in t.spans("batch.execute")
                   if {"trace_id": s.trace_id, "span_id": s.span_id}
                   in ride.links]
        # the step span records the fused batch's identity + stage times
        assert step.attributes["kind"] == "fused"
        mix = step.attributes["task_mix"]
        for task in self.TASKS:
            assert f"{task}:1" in mix
        assert step.attributes["batch_size"] >= 1
        assert 0 < step.attributes["fill_ratio"] <= 1
        for stage in batchtrace.STAGES:
            assert step.attributes[f"stage.{stage}_ms"] >= 0

    def test_stage_spans_parent_under_ride(self, engine):
        t = Tracer(sample_rate=1.0)
        with t.span("router.route") as root:
            engine.classify_multi(self.TASKS, ["check span parentage"])
            tid = root.trace_id
        spans = {s.name: s for s in t.trace(tid)}
        ride = spans["batch.ride"]
        stages = [spans[f"batch.{n}"] for n in batchtrace.STAGES]
        assert all(st.parent_id == ride.span_id for st in stages)
        # the host stages in the order the runner goes through them,
        # one after the other inside the ride
        for a, b in zip(stages, stages[1:]):
            assert a.end_pc <= b.start_pc
        assert ride.start_pc <= stages[0].start_pc
        assert stages[-1].end_pc <= ride.end_pc
        assert spans["batch.wait"].parent_id == root.span_id

    def test_unsampled_trace_keeps_continuity_drops_detail(self, engine):
        """sample_rate=0: continuity spans (wait/ride + step link) still
        emit — only the per-stage children are sampled away."""
        t = Tracer(sample_rate=0.0)
        with t.span("router.route") as root:
            engine.classify_multi(self.TASKS, ["unsampled request"])
            tid = root.trace_id
        names = {s.name for s in t.trace(tid)}
        assert {"batch.wait", "batch.ride"} <= names
        (ride,) = [s for s in t.trace(tid) if s.name == "batch.ride"]
        assert ride.links  # still linked to its batch.execute step
        # no stage spans, and the step carries no stage attrs
        assert not {f"batch.{n}" for n in batchtrace.STAGES} & names
        step = next(s for s in t.spans("batch.execute")
                    if s.trace_id == ride.links[0]["trace_id"])
        assert not any(k.startswith("stage.") for k in step.attributes)

    def test_untraced_submit_yields_no_spans(self, engine):
        t = Tracer()
        engine.classify("intent", "no span active on this thread")
        assert t.spans("batch.") == []

    def test_fused_results_identical_with_and_without_tracing(self, engine):
        text = "does tracing change the math"
        t = Tracer(sample_rate=1.0)
        with t.span("router.route"):
            traced = engine.classify_multi(self.TASKS, [text])
        plain = engine.classify_multi(self.TASKS, [text])
        for task in self.TASKS:
            assert traced[task][0].label == plain[task][0].label
            assert traced[task][0].confidence == pytest.approx(
                plain[task][0].confidence, abs=1e-4)

    def test_traditional_batch_also_rides(self):
        from semantic_router_tpu.engine.testing import make_test_engine

        eng = make_test_engine()
        try:
            t = Tracer(sample_rate=1.0)
            with t.span("router.route") as root:
                eng.classify("intent", "per-task path rides too")
                tid = root.trace_id
            names = {s.name for s in t.trace(tid)}
            assert {"batch.wait", "batch.ride"} | {
                f"batch.{n}" for n in batchtrace.STAGES} <= names
        finally:
            eng.shutdown()


class TestExemplars:
    def test_disabled_by_default(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        h.observe(0.05, exemplar="a" * 32)
        assert "trace_id" not in "\n".join(h.expose())

    def test_enabled_emits_openmetrics_exemplar(self):
        reg = MetricsRegistry()
        reg.enable_exemplars()
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        h.observe(0.05, exemplar="a" * 32, task="x")
        h.observe(5.0, exemplar="b" * 32, task="x")  # +Inf bucket
        text = reg.expose()
        m = re.search(
            r'h_seconds_bucket\{le="0\.1",task="x"\} 1 '
            r'# \{trace_id="a{32}"\} 0\.05 [0-9.]+', text)
        assert m, text
        assert re.search(r'le="\+Inf".* # \{trace_id="b{32}"\} 5\.0', text)

    def test_enable_applies_to_existing_histograms(self):
        reg = MetricsRegistry()
        h = reg.histogram("pre_existing_seconds")
        reg.enable_exemplars()
        h.observe(0.2, exemplar="c" * 32)
        assert 'trace_id="' + "c" * 32 in reg.expose()

    def test_disabling_reverts_to_clean_classic_exposition(self):
        """Exemplars recorded while the knob was ON must not leak into
        the classic 0.0.4 exposition after it turns off (a strict
        parser would fail the whole scrape), and the OpenMetrics
        counter family strips its _total suffix only when on."""
        reg = MetricsRegistry()
        reg.enable_exemplars()
        c = reg.counter("llm_things_total")
        c.inc(kind="x")
        h = reg.histogram("h2_seconds", buckets=(0.1,))
        h.observe(0.05, exemplar="d" * 32)
        on = reg.expose()
        assert "# TYPE llm_things counter" in on
        assert 'trace_id="' + "d" * 32 in on
        reg.enable_exemplars(False)
        off = reg.expose()
        assert "# TYPE llm_things_total counter" in off
        assert "trace_id" not in off

    def test_routing_latency_exemplar_reaches_metrics_page(self):
        from semantic_router_tpu.config.schema import RouterConfig
        from semantic_router_tpu.router.pipeline import Router

        reg = MetricsRegistry()
        reg.enable_exemplars()
        r = Router(RouterConfig(default_model="m"),
                   metrics=MetricSeries(reg), tracer=Tracer(),
                   flightrec=FlightRecorder())
        try:
            res = r.route({"model": "auto", "messages": [
                {"role": "user", "content": "exemplar me"}]})
            text = reg.expose()
            assert f'trace_id="{res.trace_id}"' in text
        finally:
            r.shutdown()

    def test_knob_parses_from_config(self):
        from semantic_router_tpu.config.schema import RouterConfig

        assert RouterConfig().metrics_exemplars_enabled() is False
        cfg = RouterConfig.from_dict(
            {"observability": {"metrics": {"exemplars": True}}})
        assert cfg.metrics_exemplars_enabled() is True
        cfg2 = RouterConfig.from_dict({"observability": {
            "tracing": {"sample_rate": 0.25},
            "flight_recorder": {"slowest_n": 4, "threshold_ms": 250}}})
        assert cfg2.tracing_sample_rate() == 0.25
        assert cfg2.flight_recorder_config() == {
            "slowest_n": 4, "threshold_s": 0.25}


class TestFlightRecorder:
    def _spans(self):
        s = Span("router.route", new_trace_id(), new_span_id())
        s.end()
        return [s]

    def test_keeps_slowest_n(self):
        fr = FlightRecorder(slowest_n=2)
        for i, d in enumerate([0.1, 0.5, 0.3, 0.01]):
            fr.consider(f"r{i}", f"{i:032x}", d, self._spans)
        dump = fr.dump()
        assert [r["duration_s"] for r in dump["slowest"]] == [0.5, 0.3]
        assert dump["considered"] == 4

    def test_threshold_breaches_ring(self):
        fr = FlightRecorder(slowest_n=0, threshold_s=0.2,
                            breach_capacity=2)
        for i, d in enumerate([0.3, 0.1, 0.4, 0.5]):
            fr.consider(f"r{i}", f"{i:032x}", d, self._spans)
        dump = fr.dump()
        assert [r["request_id"] for r in dump["breaches"]] == ["r2", "r3"]
        assert dump["slowest"] == []

    def test_record_carries_span_tree_and_meta(self):
        fr = FlightRecorder(slowest_n=1)
        fr.consider("req", "t" * 32, 0.2, self._spans,
                    meta={"model": "m", "kind": "route"})
        rec = fr.dump()["slowest"][0]
        assert rec["meta"]["model"] == "m"
        assert rec["spans"][0]["name"] == "router.route"
        assert rec["spans"][0]["duration_s"] >= 0

    def test_span_provider_only_runs_on_admission(self):
        fr = FlightRecorder(slowest_n=1)
        calls = []

        def provider():
            calls.append(1)
            return []

        fr.consider("a", "1" * 32, 1.0, provider)
        fr.consider("b", "2" * 32, 0.001, provider)  # slower than root? no
        assert len(calls) == 1

    def test_configure_and_clear(self):
        fr = FlightRecorder(slowest_n=8)
        for i in range(8):
            fr.consider(f"r{i}", f"{i:032x}", 0.1 + i, self._spans)
        fr.configure(slowest_n=2, threshold_s=0.0)
        assert len(fr.dump()["slowest"]) == 2
        assert fr.threshold_s is None  # 0 disables the threshold
        fr.clear()
        assert fr.dump()["slowest"] == []

    def test_pipeline_feeds_recorder(self):
        from semantic_router_tpu.config.schema import RouterConfig
        from semantic_router_tpu.router.pipeline import Router

        fr = FlightRecorder(slowest_n=4)
        r = Router(RouterConfig(default_model="m"),
                   metrics=fresh_series(), tracer=Tracer(), flightrec=fr)
        try:
            res = r.route({"model": "auto", "messages": [
                {"role": "user", "content": "record my flight"}]})
            dump = fr.dump()
            assert dump["slowest"], "route() never reached the recorder"
            rec = dump["slowest"][0]
            assert rec["trace_id"] == res.trace_id
            names = {s["name"] for s in rec["spans"]}
            assert "router.route" in names and "signals.evaluate" in names
        finally:
            r.shutdown()

    def test_management_endpoint_dumps(self):
        from semantic_router_tpu.config.schema import RouterConfig
        from semantic_router_tpu.router.server import RouterServer
        from semantic_router_tpu.runtime.registry import RuntimeRegistry
        import json
        import urllib.request

        reg = RuntimeRegistry.isolated()
        cfg = RouterConfig(default_model="m")
        from semantic_router_tpu.router.pipeline import Router

        router = Router(cfg, metrics=reg.metric_series(),
                        tracer=reg.tracer, flightrec=reg.flightrec)
        srv = RouterServer(router, cfg, port=0, registry=reg).start()
        try:
            router.route({"model": "auto", "messages": [
                {"role": "user", "content": "dump me via the api"}]})
            with urllib.request.urlopen(
                    srv.url + "/debug/flightrec", timeout=10) as resp:
                dump = json.loads(resp.read())
            assert dump["slowest"]
            assert dump["slowest"][0]["spans"]
        finally:
            srv.stop()
            router.shutdown()
