"""Resilience subsystem units (ISSUE 5): priority classes, the live
cost model over runtime-stats EWMAs, admission token buckets, and the
degradation ladder's deterministic escalation / hysteresis / knob
side-effects — everything the chaos e2e then proves end to end."""

import pytest

from semantic_router_tpu.observability.metrics import (
    MetricsRegistry,
)
from semantic_router_tpu.observability.runtimestats import RuntimeStats
from semantic_router_tpu.resilience import (
    CostModel,
    DegradationController,
    PriorityResolver,
    TokenBucket,
    rank_of,
)
from semantic_router_tpu.runtime.events import (
    DEGRADATION_LEVEL_CHANGED,
    ENGINE_FAILED,
    ENGINE_READY,
    SLO_ALERT_FIRING,
    SLO_ALERT_RESOLVED,
    EventBus,
)
from semantic_router_tpu.signals.base import RequestContext


def ctx_with(headers=None, model="", groups=""):
    h = dict(headers or {})
    if groups:
        h["x-authz-user-groups"] = groups
    return RequestContext.from_openai_body(
        {"model": model, "messages": [
            {"role": "user", "content": "hello"}]}, h)


class TestPriority:
    def test_header_wins_when_trusted(self):
        r = PriorityResolver.from_config({})
        assert r.resolve(ctx_with({"x-vsr-priority": "critical"})) \
            == "critical"
        assert r.resolve(ctx_with({"x-vsr-priority": "LOW"})) == "low"

    def test_unknown_header_falls_through(self):
        r = PriorityResolver.from_config({})
        assert r.resolve(ctx_with({"x-vsr-priority": "root"})) == "normal"

    def test_untrusted_header_ignored(self):
        r = PriorityResolver.from_config(
            {"priority": {"trust_header": False,
                          "default": "low"}})
        assert r.resolve(ctx_with({"x-vsr-priority": "critical"})) == "low"

    def test_model_and_group_maps(self):
        r = PriorityResolver.from_config({"priority": {
            "model_classes": {"batch-model": "low"},
            "group_classes": {"oncall": "critical"}}})
        assert r.resolve(ctx_with(model="batch-model")) == "low"
        assert r.resolve(ctx_with(groups="dev,oncall")) == "critical"
        assert r.resolve(ctx_with()) == "normal"

    def test_rank_of_unknown_is_default(self):
        assert rank_of("critical") == 0
        assert rank_of("nonsense") == rank_of("normal")


class TestCostModel:
    def _stats_with_steps(self):
        rs = RuntimeStats(MetricsRegistry())
        # warm the program registry: compile step + warm executes
        rs.record_step("task:t0", 128, "split", 4, 4, 0.5,
                       compiled=True)
        for _ in range(10):
            rs.record_step("task:t0", 128, "split", 4, 4, 0.004)
            rs.record_step("trunk:g0", 128, "fused", 4, 4, 0.010)
        rs.flush()
        return rs

    def test_request_cost_from_rows(self):
        cm = CostModel(self._stats_with_steps(), ttl_s=0.0)
        per_row = cm.cost_per_row_s()
        # 0.004*10 + 0.010*10 warm device-seconds over 84 real rows
        # (the cold compile step contributes its rows, not its seconds)
        assert per_row == pytest.approx(0.14 / 84, rel=1e-6)
        assert cm.request_cost_s(3) == pytest.approx(3 * per_row)

    def test_default_before_telemetry(self):
        cm = CostModel(None, default_request_cost_s=0.007)
        assert cm.request_cost_s() == 0.007


class TestTokenBucket:
    def test_spend_and_refill(self):
        b = TokenBucket(refill_per_s=1.0, burst_s=2.0)  # capacity 2.0
        assert b.try_take(1.5, now=100.0)
        assert not b.try_take(1.0, now=100.0)  # 0.5 left
        assert b.try_take(1.0, now=100.6)      # refilled to ~1.1
        assert b.wait_s(5.0) > 0

    def test_capacity_clamps(self):
        b = TokenBucket(refill_per_s=1.0, burst_s=1.0)
        b.try_take(0.0, now=0.0)
        assert b.try_take(1.0, now=1000.0)  # never above capacity
        assert not b.try_take(0.5, now=1000.0)


def make_controller(**cfg):
    bus = EventBus()
    c = DegradationController(MetricsRegistry())
    c.bind(events=bus)
    base = {"enabled": True, "escalate_ticks": 1, "hysteresis_ticks": 2}
    base.update(cfg)
    c.configure(base)
    return bus, c


class TestLadder:
    def test_monotone_escalation_on_fast_alert(self):
        bus, c = make_controller()
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        levels = [c.tick() for _ in range(6)]
        assert levels == [1, 2, 3, 4, 4, 4]  # one rung per tick, capped
        changes = bus.recent(50, stage=DEGRADATION_LEVEL_CHANGED)
        assert len(changes) == 4
        assert all(e.detail["direction"] == "escalate" for e in changes)

    def test_max_level_clamp(self):
        bus, c = make_controller(max_level=2)
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        levels = [c.tick() for _ in range(4)]
        assert levels == [1, 2, 2, 2]

    def test_slow_alert_holds_without_escalating(self):
        """The hysteresis band: a slow-severity burn (or mid-range queue
        pressure) neither escalates nor counts as healthy — no flapping
        on the boundary."""
        bus, c = make_controller()
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        assert c.tick() == 1
        # downgrade to slow: the level must HOLD, not flap 1→0→1
        bus.emit(SLO_ALERT_RESOLVED, objective="o")
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="slow")
        assert [c.tick() for _ in range(5)] == [1, 1, 1, 1, 1]

    def test_recovery_needs_hysteresis_ticks(self):
        bus, c = make_controller(hysteresis_ticks=3)
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        c.tick()
        c.tick()
        assert c.level() == 2
        bus.emit(SLO_ALERT_RESOLVED, objective="o")
        # 3 healthy ticks per rung down: 2 + 3 + 3 ticks to reach L0
        levels = [c.tick() for _ in range(6)]
        assert levels == [2, 2, 1, 1, 1, 0]

    def test_queue_pressure_escalates(self):
        rs = RuntimeStats(MetricsRegistry())
        rs.register_provider("b0", lambda: {"pending_items": 100,
                                            "pool_saturation": 0.2})
        bus, c = make_controller(queue_high_watermark=64)
        c.bind(runtimestats=rs)
        assert c.tick() == 1
        rs.register_provider("b0", lambda: {"pending_items": 0,
                                            "pool_saturation": 0.0})
        assert [c.tick() for _ in range(2)] == [1, 0]

    def test_engine_failure_jumps_to_fail_static(self):
        bus, c = make_controller()
        bus.emit(ENGINE_FAILED, during="warmup", error="boom")
        assert c.tick() == 4
        bus.emit(ENGINE_READY, tasks=[])
        assert [c.tick() for _ in range(2)] == [4, 3]

    def test_disable_resets_level(self):
        bus, c = make_controller()
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        c.tick()
        assert c.level() == 1
        c.configure({"enabled": False})
        assert c.level() == 0


class TestAdmit:
    def test_l0_is_shared_allow(self):
        _, c = make_controller()
        d1, d2 = c.admit("low"), c.admit("critical")
        assert d1 is d2  # the immutable fast path
        assert d1.action == "allow" and d1.use_learned

    def test_l2_brownout_is_priority_aware(self):
        bus, c = make_controller()
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        c.tick()
        c.tick()
        assert c.level() == 2
        assert not c.admit("normal").use_learned
        assert not c.admit("low").use_learned
        assert c.admit("high").use_learned
        assert c.admit("critical").use_learned
        # everything still serves at L2 — brownout degrades, never drops
        assert all(c.admit(p).action == "allow"
                   for p in ("critical", "high", "normal", "low"))

    def test_l3_rejects_lowest_class_with_retry_after(self):
        bus, c = make_controller()
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        for _ in range(3):
            c.tick()
        assert c.level() == 3
        d = c.admit("low")
        assert d.action == "shed" and d.retry_after_s >= 1.0
        assert c.admit("critical").action == "allow"
        assert c.shed_count >= 1

    def test_l3_bucket_empties_for_paying_classes(self):
        bus, c = make_controller()
        c.cost_model.default_request_cost_s = 10.0  # huge per-request
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        for _ in range(3):
            c.tick()
        # burst_s=2.0 at a fraction of utilization: a 10s-cost request
        # drains the bucket immediately
        outcomes = [c.admit("normal").action for _ in range(3)]
        assert "shed" in outcomes

    def test_l4_fail_static_for_everyone(self):
        bus, c = make_controller()
        bus.emit(ENGINE_FAILED, error="x")
        c.tick()
        for p in ("critical", "low"):
            d = c.admit(p)
            assert d.fail_static and d.action == "allow"
            assert not d.use_learned

    def test_l2_brownout_keeps_safety_families(self):
        # the jailbreak screen survives the brownout: a browned-out
        # class's disposition names the families route() must NOT skip
        bus, c = make_controller()
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        c.tick()
        c.tick()
        assert c.level() == 2
        d = c.admit("normal")
        assert not d.use_learned
        assert "jailbreak" in d.keep_families
        # full-service classes carry no keep set (nothing is skipped)
        assert c.admit("high").keep_families == ()
        # operator override via the knob block
        _, c2 = make_controller(
            brownout_keep_families=["jailbreak", "pii"])
        assert c2.brownout_keep == frozenset({"jailbreak", "pii"})
        assert c2.report()["brownout_keep_families"] == [
            "jailbreak", "pii"]

    def test_dispatcher_learned_types_honors_keep(self):
        from semantic_router_tpu.signals.dispatch import (
            SAFETY_FAMILIES,
            SignalDispatcher,
        )

        class Fake:
            def __init__(self, t, engine):
                self.signal_type = t
                self.engine = engine

        disp = SignalDispatcher([Fake("jailbreak", object()),
                                 Fake("domain", object()),
                                 Fake("keyword", None)])
        try:
            assert disp.learned_types() == ["domain", "jailbreak"]
            assert disp.learned_types(keep=SAFETY_FAMILIES) == ["domain"]
        finally:
            disp.pool.shutdown(wait=False)

    def test_l3_retry_after_from_live_drain_rate(self):
        bus, c = make_controller()
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        for _ in range(3):
            c.tick()
        assert c.level() == 3
        # live drain estimate: backlog × warm per-row device cost
        c.cost_model.cost_per_row_s = lambda: 0.05
        c._last_pressure = {"pending_items": 100.0}
        assert c.admit("low").retry_after_s == pytest.approx(5.0)
        # a deep queue is capped — never "come back in an hour"
        c._last_pressure = {"pending_items": 1e6}
        assert c.admit("low").retry_after_s == pytest.approx(
            c.retry_after_cap_s)
        # pre-telemetry keeps the static recovery-window fallback
        c.cost_model.cost_per_row_s = lambda: None
        c._last_pressure = {"pending_items": 100.0}
        assert c.admit("low").retry_after_s == pytest.approx(
            max(1.0, c.interval_s * c.hysteresis_ticks))


class TestKnobSideEffects:
    def test_trace_and_record_sampling_shed_and_restore(self):
        class Tracerish:
            sample_rate = 0.25

        class Explainish:
            sample_rate = 1.0

        tr, ex = Tracerish(), Explainish()
        bus, c = make_controller(hysteresis_ticks=1)
        c.bind(tracer=tr, explain=ex)
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        c.tick()
        assert tr.sample_rate == 0.0  # floored on entering the ladder
        assert ex.sample_rate == pytest.approx(0.1)
        bus.emit(SLO_ALERT_RESOLVED, objective="o")
        c.tick()
        assert c.level() == 0
        assert tr.sample_rate == 0.25  # operator values restored exactly
        assert ex.sample_rate == 1.0

    def test_hot_reload_resync_refloors_and_restores_new_values(self):
        """A config reload re-applies operator sampling knobs while
        degraded: resync must floor them again AND make recovery
        restore the post-reload values, not the stale saved ones."""
        class Tracerish:
            sample_rate = 0.25

        tr = Tracerish()
        bus, c = make_controller(hysteresis_ticks=1)
        c.bind(tracer=tr)
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        c.tick()
        assert tr.sample_rate == 0.0
        tr.sample_rate = 0.5  # the reload path re-applied new config
        c.resync_knob_effects()
        assert tr.sample_rate == 0.0  # shed wins again while degraded
        bus.emit(SLO_ALERT_RESOLVED, objective="o")
        c.tick()
        assert c.level() == 0
        assert tr.sample_rate == 0.5  # the NEW operator value restored

    def test_bucket_gauges_reset_on_leaving_admission(self):
        bus, c = make_controller(hysteresis_ticks=1)
        c.cost_model.default_request_cost_s = 10.0
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        for _ in range(3):
            c.tick()
        assert c.level() == 3
        assert c.admit("normal").action == "shed"  # bucket drained
        bus.emit(SLO_ALERT_RESOLVED, objective="o")
        c.tick()  # 3 → 2: buckets retire
        assert c.level() == 2
        assert c.report()["admission_buckets"] == {}
        # the gauge publishes full headroom, not the frozen drained fill
        assert c.bucket_fill._values[(("priority", "normal"),)] == 1.0

    def test_report_shape(self):
        bus, c = make_controller()
        bus.emit(SLO_ALERT_FIRING, objective="o", severity="fast")
        c.tick()
        rep = c.report()
        assert rep["level"] == 1 and rep["level_name"] == "shed_optional"
        assert rep["pressure"]["firing"] == {"o": "fast"}
        assert rep["transitions"][-1]["to"] == 1
        assert "cost_model" in rep


class TestDurableStoreFilters:
    def test_rule_and_family_filter_payloads(self, tmp_path):
        from semantic_router_tpu.observability.explain_store import (
            SQLiteDecisionStore,
        )

        store = SQLiteDecisionStore(str(tmp_path / "d.db"))
        for i, (rules, fams) in enumerate([
                (["keyword:urgent"], {"keyword": [{"rule": "urgent"}]}),
                (["domain:law"], {"domain": [{"rule": "law"}]}),
                (["keyword:urgent"], {"keyword": []})]):
            store.add({"record_id": f"r{i}", "trace_id": f"t{i}",
                       "request_id": f"q{i}", "ts_unix": float(i),
                       "kind": "route", "model": "m",
                       "decision": {"name": "d",
                                    "matched_rules": rules},
                       "signals": {f: {"hits": h}
                                   for f, h in fams.items()}})
        got = store.list(rule="keyword:urgent")
        assert {r["record_id"] for r in got} == {"r0", "r2"}
        got = store.list(family="keyword")  # needs HITS, not presence
        assert {r["record_id"] for r in got} == {"r0"}
        got = store.list(family="domain", model="m")
        assert {r["record_id"] for r in got} == {"r1"}
        store.close()


class TestRegistrySlot:
    def test_isolated_registries_have_independent_ladders(self):
        from semantic_router_tpu.runtime.registry import RuntimeRegistry

        a = RuntimeRegistry.isolated()
        b = RuntimeRegistry.isolated()
        ca, cb = a.get("resilience"), b.get("resilience")
        assert ca is not cb
        ca.configure({"enabled": True})
        ca.bind(events=a.get("events"))
        a.get("events").emit(SLO_ALERT_FIRING, objective="o",
                             severity="fast")
        ca.tick()
        cb.configure({"enabled": True})
        assert ca.level() == 1 and cb.tick() == 0
