"""Decision-aware early-exit signal cascade tests (ISSUE 16).

- tri-state fold: bit-for-bit agreement with ``eval_rule_node`` on fully
  resolved trees, bound-soundness under every fuzzred partial resolution;
- planner: relevance sets (direct + derived feeders), pinned families,
  the safety floor (jailbreak never skippable, guard raises);
- certain_winner: the interval proof behind every skip;
- parity: cascade on vs off selects the identical decision + model over
  a mixed/packed/LoRA'd corpus, with skips actually occurring;
- skip-aware prefetch: a skipped family's task never reaches the engine
  (so it can never occupy a packed segment);
- brownout: L2 truncates the cascade tail (reason "truncated", never
  claimed neutral) while pinned safety families keep evaluating;
- knobs: default-off, attach/detach via apply_cascade_knobs across
  reloads, registry slot persistence;
- explain/replay: the skip certificate lands in the decision record and
  ``rederive_cascade_skips`` re-proves it deterministically;
- bench: the cascade arm's child-output parser and the always-emits-a-
  row watchdog contract (PR 13 regression class).
"""

import json
import random
from types import SimpleNamespace

import pytest

import bench
from semantic_router_tpu.config.schema import (
    Decision,
    InferenceEngineConfig,
    KeywordRule,
    ModelRef,
    NamedRule,
    DomainRule,
    RouterConfig,
    RuleNode,
    SignalsConfig,
)
from semantic_router_tpu.decision.engine import (
    DecisionEngine,
    SignalMatches,
    eval_rule_node,
)
from semantic_router_tpu.engine.cascade import (
    CascadeEvaluator,
    CascadePlanError,
    FALSE,
    TRUE,
    UNKNOWN,
    build_plan,
    certain_winner,
    normalize_cascade,
    plan_order,
    tri_eval_node,
)
from semantic_router_tpu.engine.cascade.planner import (
    CascadePlan,
    _check_safety_floor,
    _composer_feeders,
    _projection_feeders,
)
from semantic_router_tpu.engine.testing import make_shared_trunk_engine
from semantic_router_tpu.observability.explain import DecisionExplainer
from semantic_router_tpu.observability.flightrec import FlightRecorder
from semantic_router_tpu.observability.metrics import (
    MetricSeries,
    MetricsRegistry,
)
from semantic_router_tpu.observability.tracing import Tracer
from semantic_router_tpu.replay import replay_decision
from semantic_router_tpu.replay.recorder import rederive_cascade_skips
from semantic_router_tpu.router.pipeline import Router
from semantic_router_tpu.runtime.bootstrap import apply_cascade_knobs
from semantic_router_tpu.runtime.registry import RuntimeRegistry
from semantic_router_tpu.signals.base import RequestContext
from semantic_router_tpu.signals.dispatch import SignalDispatcher


def leaf(styp: str, name: str) -> RuleNode:
    return RuleNode(signal_type=styp, name=name)


# ---------------------------------------------------------------------------
# tri-state fold
# ---------------------------------------------------------------------------

_FAMS = ["keyword", "domain", "fact_check", "user_feedback", "modality",
         "complexity"]
_RULES = ["r0", "r1", "r2"]


def _rand_tree(rng: random.Random, depth: int = 0) -> RuleNode:
    if depth >= 3 or rng.random() < 0.4:
        return leaf(rng.choice(_FAMS), rng.choice(_RULES))
    op = rng.choice(["AND", "OR", "NOT"])
    return RuleNode(operator=op, conditions=[
        _rand_tree(rng, depth + 1)
        for _ in range(rng.randint(1, 3))])


def _rand_signals(rng: random.Random) -> SignalMatches:
    sm = SignalMatches()
    for f in _FAMS:
        for r in _RULES:
            if rng.random() < 0.45:
                name = r if f != "complexity" else \
                    f"{r}:{rng.choice(['easy', 'hard'])}"
                sm.add(f, name, round(rng.random(), 3))
    return sm


def _strip(sm: SignalMatches, fams) -> SignalMatches:
    """Partial view: the final matches minus the unresolved families."""
    out = SignalMatches()
    for f, names in sm.matches.items():
        if f in fams:
            continue
        for n in names:
            out.add(f, n, sm.confidences.get(f"{f}:{n}", 1.0))
    return out


class TestTriState:
    def test_matches_two_valued_when_resolved(self):
        rng = random.Random(0xCA5)
        for _ in range(500):
            tree, sm = _rand_tree(rng), _rand_signals(rng)
            matched, conf, rules = eval_rule_node(tree, sm)
            t = tri_eval_node(tree, sm, frozenset())
            assert t.status in (TRUE, FALSE)
            assert (t.status == TRUE) == matched
            if matched:
                assert t.conf_lo == t.conf_hi == conf
                assert t.matched_rules == rules
                assert t.pinned

    def test_bounds_sound_under_partial_resolution(self):
        rng = random.Random(0x5CADE)
        for _ in range(300):
            tree, final = _rand_tree(rng), _rand_signals(rng)
            matched, conf, rules = eval_rule_node(tree, final)
            for _ in range(10):
                unresolved = frozenset(
                    f for f in _FAMS if rng.random() < 0.4)
                partial = _strip(final, unresolved)
                t = tri_eval_node(tree, partial, unresolved)
                if t.status == TRUE:
                    assert matched
                elif t.status == FALSE:
                    assert not matched
                if matched and t.status != FALSE:
                    assert t.conf_lo - 1e-9 <= conf <= t.conf_hi + 1e-9
                if t.status == TRUE and t.pinned:
                    # pinned = the (confidence, rules) pair is final
                    assert conf == pytest.approx(t.conf_lo)
                    assert rules == t.matched_rules


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

class _FakeLearned:
    """Evaluator stub: engine-backed family without a real engine."""

    def __init__(self, styp: str) -> None:
        self.signal_type = styp
        self.engine = object()
        self.prefetch_task = styp

    def evaluate(self, ctx):  # pragma: no cover - planner never calls it
        raise AssertionError("planner must not evaluate")


def _plan(decisions, evaluators, strategy="priority", **disp_kw):
    disp = SignalDispatcher(evaluators, **disp_kw)
    try:
        return build_plan(DecisionEngine(decisions, strategy), disp)
    finally:
        disp.shutdown()


class TestPlanner:
    def test_safety_family_always_pinned_never_skippable(self):
        plan = _plan(
            [Decision(name="d", rules=leaf("jailbreak", "jb"))],
            [_FakeLearned("jailbreak"), _FakeLearned("user_feedback")])
        assert "jailbreak" in plan.pinned
        assert "jailbreak" not in plan.skippable
        assert plan.skippable == frozenset({"user_feedback"})

    def test_pipeline_consumed_families_pinned(self):
        plan = _plan(
            [Decision(name="d", rules=leaf("domain", "law"))],
            [_FakeLearned(f) for f in
             ("domain", "pii", "fact_check", "modality")])
        for fam in ("domain", "pii", "fact_check"):
            assert fam in plan.pinned
            assert fam not in plan.skippable
        assert plan.skippable == frozenset({"modality"})

    def test_safety_floor_guard_raises(self):
        with pytest.raises(CascadePlanError):
            _check_safety_floor(frozenset(), frozenset({"jailbreak"}))
        with pytest.raises(CascadePlanError):
            # not skippable, but not pinned either: still a violation
            _check_safety_floor(frozenset({"pii"}), frozenset())
        _check_safety_floor(frozenset({"jailbreak"}), frozenset())

    def test_automix_pins_complexity(self):
        dec = Decision(name="d", rules=leaf("complexity", "c"),
                       algorithm={"type": "automix"})
        plan = _plan([dec], [_FakeLearned("complexity")])
        assert "complexity" in plan.pinned
        assert plan.skippable == frozenset()

    def test_relevance_expands_derived_feeders(self):
        comp_rule = SimpleNamespace(
            composer=leaf("user_feedback", "negative"))
        plan = _plan(
            [Decision(name="uses_complexity",
                      rules=leaf("complexity", "c")),
             Decision(name="plain", rules=leaf("keyword", "k"))],
            [_FakeLearned("user_feedback")],
            complexity_rules=[comp_rule])
        assert "user_feedback" in plan.families("uses_complexity")
        assert plan.families("plain") == frozenset({"keyword"})
        assert plan.complexity_feeders == frozenset({"user_feedback"})

    def test_composer_and_projection_feeders(self):
        assert _composer_feeders([
            SimpleNamespace(composer=leaf("user_feedback", "negative")),
            SimpleNamespace(composer=None)]) == {"user_feedback"}
        proj = SimpleNamespace(cfg=SimpleNamespace(
            scores=[SimpleNamespace(inputs=[
                SimpleNamespace(type="kb_metric"),
                SimpleNamespace(type="domain")])],
            partitions=[]))
        assert _projection_feeders(proj, None) == {"kb", "domain"}
        assert _projection_feeders(None, None) == set()

    def test_plan_order_cost_and_value_blend(self):
        plan = CascadePlan(version=1,
                           relevance={"d": frozenset({"a"})},
                           skippable=frozenset({"a", "b"}))
        assert plan_order(plan, {"a": 10.0, "b": 1.0}, {}, 5.0,
                          0.25) == ["b", "a"]
        # a feeds a high-value decision: the discount flips the order
        assert plan_order(plan, {"a": 10.0, "b": 1.0}, {"d": 40.0}, 5.0,
                          0.25) == ["a", "b"]
        # no costs yet: the default applies, ties break by name
        assert plan_order(plan, {}, {}, 5.0, 0.0) == ["a", "b"]


# ---------------------------------------------------------------------------
# certain_winner
# ---------------------------------------------------------------------------

class TestCertainWinner:
    DECISIONS = [
        Decision(name="high", priority=100, rules=leaf("keyword", "k")),
        Decision(name="low", priority=10,
                 rules=leaf("user_feedback", "negative")),
    ]

    def test_priority_winner_beats_unknown_rival(self):
        sm = SignalMatches()
        sm.add("keyword", "k", 0.9)
        decided, winner, _ = certain_winner(
            self.DECISIONS, "priority", sm, {"user_feedback"})
        assert decided and winner == "high"

    def test_unknown_higher_priority_rival_blocks(self):
        sm = SignalMatches()
        sm.add("user_feedback", "negative", 0.9)
        decided, winner, contending = certain_winner(
            self.DECISIONS, "priority", sm, {"keyword"})
        assert not decided and winner is None
        assert {d.name for d, _ in contending} == {"high", "low"}

    def test_all_false_is_decided_fallback(self):
        decided, winner, contending = certain_winner(
            self.DECISIONS, "priority", SignalMatches(), set())
        assert decided and winner is None and contending == []

    def test_confidence_strategy_needs_bound_separation(self):
        decisions = [
            Decision(name="a", rules=leaf("keyword", "k")),
            Decision(name="b", rules=leaf("user_feedback", "negative")),
        ]
        sm = SignalMatches()
        sm.add("keyword", "k", 0.8)
        # the unknown rival could report up to 1.0 > 0.8: undecided
        decided, _, _ = certain_winner(decisions, "confidence", sm,
                                       {"user_feedback"})
        assert not decided
        # fully resolved: decided on the only match
        decided, winner, _ = certain_winner(decisions, "confidence", sm,
                                            set())
        assert decided and winner == "a"


# ---------------------------------------------------------------------------
# end-to-end rig (shared-trunk engine, packed, one LoRA'd family)
# ---------------------------------------------------------------------------

DOMAINS = ["business", "law", "health", "computer science", "other"]


def _rig_config() -> RouterConfig:
    return RouterConfig(
        default_model="backend-model",
        strategy="priority",
        signals=SignalsConfig(
            keywords=[KeywordRule(name="escalate",
                                  keywords=["urgent", "outage"])],
            domains=[DomainRule(name=d) for d in DOMAINS],
            user_feedbacks=[NamedRule(name="positive"),
                            NamedRule(name="negative")],
            modality=[NamedRule(name="diffusion"),
                      NamedRule(name="both")]),
        decisions=[
            Decision(name="escalation", priority=100,
                     rules=leaf("keyword", "escalate"),
                     model_refs=[ModelRef(model="escalation-model")]),
            Decision(name="law_route", priority=60,
                     rules=leaf("domain", "law"),
                     model_refs=[ModelRef(model="law-model")]),
            Decision(name="retry_churn", priority=50,
                     rules=RuleNode(operator="OR", conditions=[
                         leaf("user_feedback", "negative"),
                         RuleNode(operator="AND", conditions=[
                             leaf("user_feedback", "positive"),
                             leaf("modality", "diffusion")])]),
                     model_refs=[ModelRef(model="retry-model")]),
            Decision(name="imagegen", priority=40,
                     rules=RuleNode(operator="OR", conditions=[
                         leaf("modality", "diffusion"),
                         leaf("modality", "both")]),
                     model_refs=[ModelRef(model="image-model")]),
        ])


CORPUS = [
    "urgent outage in the payment cluster right now",
    "please summarize this contract clause for me",
    "urgent outage in the payment cluster right now",  # dedup repeat
    "draw me a watercolor painting of a lighthouse",
    "what are the symptoms of the common flu",
    "my last answer was wrong, try that request again",
    "refactor this python function to be iterative " * 8,  # long → packed
    "book review of a mystery novel",
]


@pytest.fixture(scope="module")
def rig():
    engine = make_shared_trunk_engine(
        tasks=[("intent", DOMAINS),
               ("user_feedback", ["none", "positive", "negative"]),
               ("modality", ["ar", "diffusion", "both"])],
        lora_tasks=("modality",),
        engine_cfg=InferenceEngineConfig(
            max_batch_size=8, max_wait_ms=1.0,
            seq_len_buckets=[32, 128, 512],
            packing={"enabled": True}),
        metrics=MetricSeries(MetricsRegistry()))
    cfg = _rig_config()
    explainer = DecisionExplainer(ring_size=64)
    explainer.enabled = True
    explainer.sample_rate = 1.0
    router = Router(cfg, engine=engine,
                    metrics=MetricSeries(MetricsRegistry()),
                    tracer=Tracer(sample_rate=0.0),
                    flightrec=FlightRecorder(), explain=explainer)
    metrics = MetricSeries(MetricsRegistry())
    casc = CascadeEvaluator(metrics=metrics)
    casc.configure(normalize_cascade({"enabled": True}))
    r = SimpleNamespace(engine=engine, cfg=cfg, router=router,
                        cascade=casc, explainer=explainer,
                        metrics=metrics)
    try:
        yield r
    finally:
        router.cascade = None
        router.shutdown()
        engine.shutdown()


def _body(text: str) -> dict:
    return {"model": "auto",
            "messages": [{"role": "user", "content": text}]}


class TestCascadeParity:
    def test_same_decision_and_model_with_skips(self, rig):
        got_skips = False
        for text in CORPUS:
            rig.router.cascade = None
            off = rig.router.route(_body(text))
            rig.router.cascade = rig.cascade
            on = rig.router.route(_body(text))
            rig.router.cascade = None
            off_dec = off.decision.decision.name if off.decision else None
            on_dec = on.decision.decision.name if on.decision else None
            assert on_dec == off_dec, text
            assert on.model == off.model, text
            cert = getattr(on, "signals_report", None)
            rep = rig.cascade.report()
            got_skips = got_skips or bool(rep["skipped_forwards"])
        rep = rig.cascade.report()
        assert rep["skipped_forwards"], \
            "cascade never skipped a forward on the parity corpus"
        assert rep["decided_early_total"] > 0
        assert rep["requests_total"] >= len(CORPUS)
        # the new counters actually tick
        assert rig.metrics.cascade_skipped.total() > 0
        assert rig.metrics.cascade_waves.total() >= 0

    def test_report_shape_for_debug_runtime(self, rig):
        rep = rig.cascade.report()
        for key in ("enabled", "planner_version", "order", "cost_ms",
                    "skipped_forwards", "waves_total",
                    "decided_early_total", "requests_total", "wave_size",
                    "brownout_max_waves"):
            assert key in rep
        assert rep["enabled"] is True

    def test_off_route_has_no_certificate(self, rig):
        rig.router.cascade = None
        res = rig.router.route(_body(CORPUS[0]))
        report = getattr(res, "report", None)
        if report is not None:
            assert report.cascade is None


class TestSkipAwarePrefetch:
    def test_skipped_family_never_reaches_engine(self, rig):
        """A keyword-decided request must never classify the skippable
        learned tasks — not via the fused prefetch (no packed segment is
        occupied by a skipped family) and not via a direct forward."""
        calls = []
        orig_multi = rig.engine.classify_multi
        orig_single = rig.engine.classify

        def spy_multi(tasks, texts, **kw):
            calls.extend(tasks)
            return orig_multi(tasks, texts, **kw)

        def spy_single(task, text, **kw):
            calls.append(task)
            return orig_single(task, text, **kw)

        rig.engine.classify_multi = spy_multi
        rig.engine.classify = spy_single
        rig.router.cascade = rig.cascade
        try:
            res = rig.router.route(
                _body("urgent outage in the billing stack"))
        finally:
            rig.router.cascade = None
            del rig.engine.classify_multi
            del rig.engine.classify
        assert res.decision.decision.name == "escalation"
        assert "user_feedback" not in calls
        assert "modality" not in calls
        assert "intent" in calls  # pinned family still evaluated


class TestBrownoutTruncation:
    def test_l2_truncates_tail_never_safety(self, rig):
        casc = CascadeEvaluator()
        casc.configure(normalize_cascade(
            {"enabled": True, "wave_size": 1, "brownout_max_waves": 1}))
        cfg = RouterConfig(
            default_model="backend-model",
            strategy="priority",
            signals=SignalsConfig(
                user_feedbacks=[NamedRule(name="positive"),
                                NamedRule(name="negative")],
                modality=[NamedRule(name="diffusion"),
                          NamedRule(name="both")]),
            decisions=[
                Decision(name="d1", priority=50,
                         rules=RuleNode(operator="OR", conditions=[
                             leaf("user_feedback", "negative"),
                             leaf("modality", "both")]),
                         model_refs=[ModelRef(model="m1")]),
                Decision(name="d2", priority=40,
                         rules=leaf("modality", "diffusion"),
                         model_refs=[ModelRef(model="m2")]),
            ])
        router = Router(cfg, engine=rig.engine,
                        metrics=MetricSeries(MetricsRegistry()),
                        tracer=Tracer(sample_rate=0.0))
        try:
            ctx = RequestContext.from_openai_body(
                _body("please summarize the quarterly report"))
            signals, report = casc.evaluate(
                ctx, router.dispatcher, router.decision_engine,
                signals_cfg=cfg.signals, brownout=True)
            cert = report.cascade
            assert cert["mode"] == "cascade"
            # exactly one wave ran (the brownout budget), the other
            # skippable family was truncated — a quality trade the
            # certificate never claims neutral
            assert len(cert["waves"]) == 1
            assert "truncated" in cert["skipped"].values()
        finally:
            router.shutdown()

    def test_unbrowned_cascade_runs_all_needed_waves(self, rig):
        casc = CascadeEvaluator()
        casc.configure(normalize_cascade(
            {"enabled": True, "wave_size": 1}))  # max_waves 0 = unlimited
        cfg = RouterConfig(
            default_model="backend-model",
            strategy="priority",
            signals=SignalsConfig(
                user_feedbacks=[NamedRule(name="positive"),
                                NamedRule(name="negative")],
                modality=[NamedRule(name="diffusion"),
                          NamedRule(name="both")]),
            decisions=[
                Decision(name="d1", priority=50,
                         rules=RuleNode(operator="AND", conditions=[
                             leaf("user_feedback", "negative"),
                             leaf("modality", "both")]),
                         model_refs=[ModelRef(model="m1")]),
            ])
        router = Router(cfg, engine=rig.engine,
                        metrics=MetricSeries(MetricsRegistry()),
                        tracer=Tracer(sample_rate=0.0))
        try:
            ctx = RequestContext.from_openai_body(
                _body("please summarize the quarterly report"))
            signals, report = casc.evaluate(
                ctx, router.dispatcher, router.decision_engine,
                signals_cfg=cfg.signals, brownout=False)
            cert = report.cascade
            # no truncation off-brownout: every family either ran or was
            # proven irrelevant/decided
            assert "truncated" not in cert["skipped"].values()
        finally:
            router.shutdown()


# ---------------------------------------------------------------------------
# knobs / bootstrap wiring
# ---------------------------------------------------------------------------

class TestKnobWiring:
    def test_normalize_defaults_off(self):
        ck = normalize_cascade({})
        assert ck["enabled"] is False
        assert ck["wave_size"] == 2
        assert ck["max_waves"] == 0
        assert ck["brownout_max_waves"] == 1
        # clamps
        ck = normalize_cascade({"enabled": 1, "wave_size": 0,
                                "brownout_max_waves": -3,
                                "value_blend": -1.0})
        assert ck["enabled"] is True
        assert ck["wave_size"] == 1
        assert ck["brownout_max_waves"] == 1
        assert ck["value_blend"] == 0.0

    def test_schema_accessor_defaults_off(self):
        cfg = RouterConfig(default_model="m")
        assert cfg.engine.cascade_config()["enabled"] is False

    def test_apply_cascade_knobs_attach_reload_detach(self):
        reg = RuntimeRegistry.isolated()
        router = SimpleNamespace(flywheel=None)
        on_cfg = RouterConfig(
            default_model="m",
            engine=InferenceEngineConfig(
                cascade={"enabled": True, "wave_size": 3}))
        off_cfg = RouterConfig(default_model="m")

        apply_cascade_knobs(on_cfg, reg, router)
        casc = reg.get("cascade")
        assert casc is not None and router.cascade is casc
        assert casc.knobs["wave_size"] == 3

        # hot reload with new knob values: SAME evaluator (registry slot
        # keeps counters), reconfigured
        on_cfg2 = RouterConfig(
            default_model="m",
            engine=InferenceEngineConfig(
                cascade={"enabled": True, "wave_size": 1}))
        router2 = SimpleNamespace(flywheel=None)
        apply_cascade_knobs(on_cfg2, reg, router2)
        assert reg.get("cascade") is casc
        assert router2.cascade is casc
        assert casc.knobs["wave_size"] == 1

        # reload to disabled: detached everywhere
        apply_cascade_knobs(off_cfg, reg, router2)
        assert reg.get("cascade") is None
        assert router2.cascade is None

    def test_malformed_config_never_raises(self):
        reg = RuntimeRegistry.isolated()
        router = SimpleNamespace(flywheel=None)
        cfg = RouterConfig(default_model="m",
                           engine=InferenceEngineConfig(
                               cascade={"enabled": True,
                                        "wave_size": "not-a-number"}))
        apply_cascade_knobs(cfg, reg, router)  # must not raise


# ---------------------------------------------------------------------------
# explain / replay
# ---------------------------------------------------------------------------

class TestExplainAndReplay:
    def _cascade_record(self, rig):
        rig.router.cascade = rig.cascade
        try:
            rig.router.route(_body("urgent outage in the auth service"))
        finally:
            rig.router.cascade = None
        for rec in rig.explainer.list(limit=10):
            cert = rec.get("cascade")
            if isinstance(cert, dict) and cert.get("mode") == "cascade" \
                    and cert.get("skipped"):
                return rec
        raise AssertionError("no cascade record with skips in the ring")

    def test_record_carries_certificate(self, rig):
        rec = self._cascade_record(rig)
        assert rec["skipped_families"] == sorted(rec["cascade"]["skipped"])
        assert set(rec["cascade"]["skipped"]) == \
            {"user_feedback", "modality"}
        assert rec["cascade"]["planner_version"] >= 1
        # records are json-serializable end to end
        json.dumps(rec)

    def test_replay_rederives_skips_deterministically(self, rig):
        rec = self._cascade_record(rig)
        red = rederive_cascade_skips(rec, rig.cfg)
        assert red["applicable"] is True
        assert red["planner_version_match"] is True
        assert red["outcome_neutral"] is True
        assert red["matches_recorded_decision"] is True
        assert red["winner"] == rec["decision"]["name"]
        assert red["truncated_families"] == []
        # and it rides the standard replay surface
        out = replay_decision(rec, rig.cfg)
        assert out["cascade_rederive"]["outcome_neutral"] is True
        assert out["decision"] == rec["decision"]["name"]

    def test_non_cascade_record_not_applicable(self, rig):
        rig.router.cascade = None
        rig.router.route(_body("plain request with no cascade"))
        rec = rig.explainer.list(limit=1)[0]
        assert rec["cascade"] is None
        assert rec["skipped_families"] == []
        assert rederive_cascade_skips(rec, rig.cfg) == \
            {"applicable": False}
        out = replay_decision(rec, rig.cfg)
        assert "cascade_rederive" not in out

    def test_truncated_families_excluded_from_proof(self, rig):
        rec = self._cascade_record(rig)
        doctored = json.loads(json.dumps(rec))
        doctored["cascade"]["skipped"]["modality"] = "truncated"
        red = rederive_cascade_skips(doctored, rig.cfg)
        assert red["truncated_families"] == ["modality"]
        assert "modality" not in red["neutral_families"]
        # the remaining neutral skip still proves out
        assert red["outcome_neutral"] is True


# ---------------------------------------------------------------------------
# bench arm: child-output parser + watchdog contract (PR 13 class)
# ---------------------------------------------------------------------------

class TestBenchCascadeArm:
    def test_parser_takes_last_json_object_line(self):
        out = "\n".join([
            "I0000 jax platform notice",
            '{"stale": true}',
            '{"speedup": 1.4, "forwards_avoided_fraction": 0.5}',
        ])
        row = bench._parse_cascade_child(out)
        assert row["speedup"] == 1.4

    def test_parser_skips_watchdog_truncated_tail(self):
        out = '{"speedup": 1.4}\n{"half": '
        assert bench._parse_cascade_child(out)["speedup"] == 1.4

    def test_parser_raises_on_no_json(self):
        with pytest.raises(ValueError):
            bench._parse_cascade_child("no json here\nstill none")
        with pytest.raises(ValueError):
            bench._parse_cascade_child("")

    def test_watchdog_timeout_yields_complete_error_row(self, monkeypatch):
        calls = []

        def fake_run(*a, **kw):
            calls.append(kw.get("timeout"))
            raise bench.subprocess.TimeoutExpired(cmd="bench", timeout=1)

        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        monkeypatch.setattr(bench, "CASCADE_CHILD_ATTEMPTS", 2)
        row = bench._measure_cascade("cpu")
        assert "error" in row
        assert len(calls) == 2  # attempts hard-capped, never unbounded
        json.dumps(row)  # the row always lands in the BENCH json

    def test_child_failure_rc_yields_complete_error_row(self, monkeypatch):
        def fake_run(*a, **kw):
            return SimpleNamespace(returncode=4, stdout="",
                                   stderr="boom\n")

        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        monkeypatch.setattr(bench, "CASCADE_CHILD_ATTEMPTS", 1)
        row = bench._measure_cascade("cpu")
        assert "error" in row and "rc=4" in row["error"]
