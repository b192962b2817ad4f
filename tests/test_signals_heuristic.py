"""Heuristic signal evaluator tests (reference: keyword_classifier.go,
structure_classifier.go, context_classifier.go, language_classifier.go,
authz_classifier.go, reask_classifier.go, nlp-binding scorers)."""

import pytest

from semantic_router_tpu.config import load_config
from semantic_router_tpu.decision import DecisionEngine
from semantic_router_tpu.signals import (
    Message,
    RequestContext,
    build_heuristic_dispatcher,
    detect_language,
)


def ctx_from_text(text, **kw):
    return RequestContext(messages=[Message(role="user", content=text)], **kw)


def hits(result):
    return {h.rule for h in result.hits}


class TestKeyword:
    def test_bm25(self, router_config):
        from semantic_router_tpu.signals import KeywordSignal

        sig = KeywordSignal(router_config.signals.keywords)
        res = sig.evaluate(ctx_from_text(
            "please debug this function, the algorithm is broken code"))
        assert "code_keywords" in hits(res)
        res2 = sig.evaluate(ctx_from_text("what is the weather like today"))
        assert "code_keywords" not in hits(res2)

    def test_ngram_tolerates_typos(self, router_config):
        from semantic_router_tpu.signals import KeywordSignal

        sig = KeywordSignal(router_config.signals.keywords)
        res = sig.evaluate(ctx_from_text("this is urgent, reply now"))
        assert "urgent_keywords" in hits(res)
        # typo still caught by character trigrams
        res2 = sig.evaluate(ctx_from_text("this is urgentt, reply now"))
        assert "urgent_keywords" in hits(res2)

    def test_fuzzy(self, router_config):
        from semantic_router_tpu.signals import KeywordSignal

        sig = KeywordSignal(router_config.signals.keywords)
        res = sig.evaluate(ctx_from_text("my credit-card number is 4111"))
        assert "fuzzy_sensitive" in hits(res)

    @staticmethod
    def _every_window(needle, haystack):
        """fuzzy_partial_ratio as it was: every candidate window scored.
        Kept here as the reference the pruned scan is held to."""
        import re

        from semantic_router_tpu.signals.keyword import fuzzy_ratio

        if not needle or not haystack:
            return 0.0
        if needle in haystack:
            return 100.0
        n = len(needle)
        if len(haystack) <= n:
            return fuzzy_ratio(needle, haystack)
        starts = {0}
        for m in re.finditer(r"\S+", haystack):
            starts.add(m.start())
        starts.update(range(0, len(haystack) - n + 1, max(1, n // 2)))
        best = 0.0
        for i in sorted(starts):
            if i + 1 >= len(haystack):
                break
            best = max(best, fuzzy_ratio(needle, haystack[i:i + n]))
            if best >= 99.9:
                break
        return best

    @pytest.mark.parametrize("library", ["native", "pure_python"])
    @pytest.mark.parametrize("floor", [0.0, 50.0, 82.0])
    def test_fuzzy_window_bound_changes_no_answer(self, floor, library,
                                                  monkeypatch):
        """Windows whose character counts cannot reach the floor (or the
        best so far) are not scored: a score of at least the floor is
        exactly what scoring every window gives, a lower one is never
        reported higher; with no floor every score is exact."""
        import random

        from semantic_router_tpu import native
        from semantic_router_tpu.signals.keyword import fuzzy_partial_ratio

        if library == "pure_python":
            monkeypatch.setattr(native, "_LIB", None)
            monkeypatch.setattr(native, "_LOAD_FAILED", True)
        elif not native.available():
            pytest.skip("native lexical library not built")
        rng = random.Random(int(floor) + len(library))
        alphabet = "abcde\u00e9 s-"
        reached = 0
        for _ in range(1500):
            needle = "".join(rng.choice(alphabet)
                             for _ in range(rng.randint(1, 12)))
            hay = "".join(rng.choice(alphabet)
                          for _ in range(rng.randint(0, 80)))
            got = fuzzy_partial_ratio(needle, hay, floor=floor)
            want = self._every_window(needle, hay)
            if want >= floor:
                assert got == want, (needle, hay)
                reached += 1
            else:
                assert got <= want, (needle, hay)
        assert reached > 20

    def test_fuzzy_rule_on_a_long_prompt_scores_few_windows(
            self, router_config, monkeypatch):
        """A 5,000-word prompt with one near miss in it: the rule's hit
        and confidence are what every window gives, from a handful of
        ratio computations, not seven thousand a keyword."""
        from semantic_router_tpu.signals import KeywordSignal, keyword

        words = " ".join(f"w{(i * 7919) % 250000}" for i in range(5000))
        text = words + " here is my pasword and my credit-card " + words
        calls = []
        inner = keyword.fuzzy_ratio
        monkeypatch.setattr(keyword, "fuzzy_ratio",
                            lambda a, b: calls.append(1) or inner(a, b))
        sig = KeywordSignal(router_config.signals.keywords)
        (hit,) = [h for h in sig.evaluate(ctx_from_text(text)).hits
                  if h.rule == "fuzzy_sensitive"]
        assert sorted(hit.detail["keywords"]) == ["credit card", "password"]
        scored = len(calls)
        want = [self._every_window(kw, text.lower())
                for kw in ("credit card", "password")]
        assert hit.confidence == pytest.approx(sum(want) / 200.0)
        assert scored < 50 < len(calls) - scored

    def test_exact_and_operator(self, router_config):
        from semantic_router_tpu.signals import KeywordSignal

        sig = KeywordSignal(router_config.signals.keywords)
        assert "exact_hello" in hits(sig.evaluate(ctx_from_text("hello wonderful world")))
        assert "exact_hello" not in hits(sig.evaluate(ctx_from_text("hello there")))

    def test_regex(self, router_config):
        from semantic_router_tpu.signals import KeywordSignal

        sig = KeywordSignal(router_config.signals.keywords)
        assert "regex_numbered" in hits(sig.evaluate(ctx_from_text("1. first step")))


class TestStructure:
    def test_count_questions(self, router_config):
        from semantic_router_tpu.signals import StructureSignal

        sig = StructureSignal(router_config.signals.structure)
        res = sig.evaluate(ctx_from_text("a? b? c? d? plus 什么？"))
        assert "many_questions" in hits(res)
        assert "many_questions" not in hits(sig.evaluate(ctx_from_text("one? two?")))

    def test_exists_numbered_steps(self, router_config):
        from semantic_router_tpu.signals import StructureSignal

        sig = StructureSignal(router_config.signals.structure)
        assert "numbered_steps" in hits(sig.evaluate(ctx_from_text("1. do x\n2. do y")))

    def test_sequence_multilingual(self, router_config):
        from semantic_router_tpu.signals import StructureSignal

        sig = StructureSignal(router_config.signals.structure)
        assert "first_then_flow" in hits(sig.evaluate(
            ctx_from_text("First install deps, then run the tests")))
        assert "first_then_flow" in hits(sig.evaluate(
            ctx_from_text("首先安装依赖，然后运行测试")))
        assert "first_then_flow" not in hits(sig.evaluate(
            ctx_from_text("then something first")))

    def test_density(self, router_config):
        from semantic_router_tpu.signals import StructureSignal

        sig = StructureSignal(router_config.signals.structure)
        assert "constraint_dense" in hits(sig.evaluate(
            ctx_from_text("keep it under 100 words at most")))


class TestContext:
    def test_token_bands(self, router_config):
        from semantic_router_tpu.signals import ContextSignal

        sig = ContextSignal(router_config.signals.context)
        assert "short_context" in hits(sig.evaluate(ctx_from_text("short q")))
        long_text = "word " * 3000
        assert "long_context" in hits(sig.evaluate(ctx_from_text(long_text)))


class TestLanguage:
    def test_detect(self):
        assert "zh" in detect_language("请问如何配置系统的网络设置？")
        assert "en" in detect_language("How do I configure the network settings?")
        assert "es" in detect_language("¿Cómo puedo configurar los ajustes de la red?")
        assert "ja" in detect_language("ネットワーク設定はどのように構成しますか")
        assert "ru" in detect_language("Как настроить параметры сети?")

    def test_signal(self, router_config):
        from semantic_router_tpu.signals import LanguageSignal

        sig = LanguageSignal(router_config.signals.language)
        assert "zh" in hits(sig.evaluate(ctx_from_text("帮我写一个程序来处理数据")))
        assert "en" in hits(sig.evaluate(ctx_from_text("write the program for me and the data")))


class TestAuthz:
    def test_group_and_user_binding(self, router_config):
        from semantic_router_tpu.signals import AuthzSignal

        sig = AuthzSignal(router_config.signals.role_bindings)
        ctx = ctx_from_text("hi", user_groups=["platform-admins"])
        assert "admin" in hits(sig.evaluate(ctx))
        ctx2 = ctx_from_text("hi", user_id="vip-1")
        assert "premium_user" in hits(sig.evaluate(ctx2))
        assert not hits(sig.evaluate(ctx_from_text("hi")))


class TestConversation:
    def test_multi_turn_and_tools(self, router_config):
        from semantic_router_tpu.signals import ConversationSignal

        sig = ConversationSignal(router_config.signals.conversation)
        ctx = RequestContext(messages=[
            Message("user", "a"), Message("assistant", "b"), Message("user", "c")],
            tools=[{"type": "function"}])
        got = hits(sig.evaluate(ctx))
        assert "multi_turn_user" in got
        assert "has_tools" in got

    def test_active_tool_loop(self, router_config):
        from semantic_router_tpu.signals import ConversationSignal

        sig = ConversationSignal(router_config.signals.conversation)
        ctx = RequestContext(messages=[
            Message("user", "a"),
            Message("assistant", "", tool_calls=[{"id": "t1"}]),
            Message("tool", "result", tool_call_id="t1"),
        ])
        assert "active_tool_use" in hits(sig.evaluate(ctx))


class TestEventAndReask:
    def test_event_match(self, router_config):
        from semantic_router_tpu.signals import EventSignal

        sig = EventSignal(router_config.signals.events)
        ctx = ctx_from_text("payment issue", )
        ctx.event = {"type": "payment_failed", "severity": "critical",
                     "action_code": "TXN_DECLINE"}
        assert "critical_payment_event" in hits(sig.evaluate(ctx))
        ctx.event = {"type": "payment_failed", "severity": "low"}
        assert not hits(sig.evaluate(ctx))

    def test_reask(self, router_config):
        from semantic_router_tpu.signals import ReaskSignal

        sig = ReaskSignal(router_config.signals.reasks)
        ctx = RequestContext(messages=[
            Message("user", "how do I reset my password?"),
            Message("assistant", "click forgot password"),
            Message("user", "how do I reset my password??"),
        ])
        assert "likely_dissatisfied" in hits(sig.evaluate(ctx))
        ctx2 = RequestContext(messages=[
            Message("user", "how do I reset my password?"),
            Message("assistant", "click forgot password"),
            Message("user", "thanks, worked great!"),
        ])
        assert not hits(sig.evaluate(ctx2))


class TestDispatch:
    def test_fanout_and_decision(self, router_config):
        dispatcher = build_heuristic_dispatcher(router_config)
        engine = DecisionEngine(router_config.decisions, router_config.strategy)
        ctx = ctx_from_text("this is urgent: my deploy failed, respond asap")
        signals, report = dispatcher.evaluate(ctx)
        assert "urgent_keywords" in signals.matches.get("keyword", [])
        res = engine.evaluate(signals)
        assert res is not None
        assert res.decision.name == "urgent_route"
        dispatcher.shutdown()

    def test_admin_not_urgent_routed(self, router_config):
        dispatcher = build_heuristic_dispatcher(router_config)
        engine = DecisionEngine(router_config.decisions, router_config.strategy)
        ctx = ctx_from_text("this is urgent, fix asap",
                            user_groups=["platform-admins"])
        signals, _ = dispatcher.evaluate(ctx)
        res = engine.evaluate(signals)
        # NOT authz:admin blocks urgent_route; falls to a lower decision
        assert res is None or res.decision.name != "urgent_route"
        dispatcher.shutdown()

    def test_fail_open_on_evaluator_error(self, router_config):
        from semantic_router_tpu.signals import SignalDispatcher

        class Exploder:
            signal_type = "keyword"

            def evaluate(self, ctx):
                raise RuntimeError("boom")

        d = SignalDispatcher([Exploder()])
        signals, report = d.evaluate(ctx_from_text("x"))
        assert signals.matches == {}
        assert "boom" in report.results["keyword"].error
        d.shutdown()

    def test_skip_signals(self, router_config):
        dispatcher = build_heuristic_dispatcher(router_config)
        ctx = ctx_from_text("this is urgent asap")
        signals, report = dispatcher.evaluate(ctx, skip_signals=["keyword"])
        assert "keyword" not in report.results
        dispatcher.shutdown()
