"""Fused classifier-bank execution (engine TrunkGroup): trunk grouping,
fused-vs-traditional equivalence, mixed-task/LoRA batches, the
tokenize-once + trunk-once fan-out acceptance counters, the jit-cache
budget, head-bank sharding specs, and the batcher/bucket satellites."""

import math
import threading

import numpy as np
import pytest

from semantic_router_tpu.config.schema import (
    DomainRule,
    InferenceEngineConfig,
    NamedRule,
)
from semantic_router_tpu.engine import DynamicBatcher, pick_bucket, pow2_batch
from semantic_router_tpu.engine.testing import (
    SHARED_TRUNK_TASKS,
    make_shared_trunk_engine,
)
from semantic_router_tpu.observability.metrics import (
    MetricSeries,
    MetricsRegistry,
)
from semantic_router_tpu.utils.tokenization import EncodingCache, HashTokenizer

TASKS = [name for name, _ in SHARED_TRUNK_TASKS]


def fresh_series() -> MetricSeries:
    return MetricSeries(MetricsRegistry())


@pytest.fixture(scope="module")
def fused_engine():
    """Shared-trunk engine: 3 sequence tasks, one (fact_check) head-LoRA."""
    eng = make_shared_trunk_engine(lora_tasks=("fact_check",),
                                   metrics=fresh_series())
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def unfused_engine():
    """Same tasks/weights, fusion off — the equivalence reference."""
    eng = make_shared_trunk_engine(lora_tasks=("fact_check",), fuse=False,
                                   metrics=fresh_series())
    yield eng
    eng.shutdown()


class TestTrunkGrouping:
    def test_shared_trunk_forms_one_group(self, fused_engine):
        groups = fused_engine.trunk_group_info()
        assert len(groups) == 1
        (members,) = groups.values()
        assert sorted(members) == sorted(TASKS)

    def test_distinct_trunks_do_not_group(self):
        # independent inits → different trunk arrays → separate groups
        eng = make_shared_trunk_engine(metrics=fresh_series())
        eng2 = make_shared_trunk_engine(seed=1, metrics=fresh_series())
        try:
            a = list(eng.trunk_group_info().values())
            b = list(eng2.trunk_group_info().values())
            assert len(a) == 1 and len(b) == 1
        finally:
            eng.shutdown()
            eng2.shutdown()

    def test_opt_out_knob_disables_grouping(self, unfused_engine):
        assert unfused_engine.trunk_group_info() == {}
        res = unfused_engine.classify("intent", "plain path still serves")
        assert res.label in unfused_engine.task_labels("intent")

    def test_config_knob_disables_grouping(self):
        cfg = InferenceEngineConfig(max_batch_size=8, max_wait_ms=1.0,
                                    seq_len_buckets=[32, 128, 512],
                                    fuse_trunks=False)
        eng = make_shared_trunk_engine(engine_cfg=cfg,
                                       metrics=fresh_series())
        try:
            assert eng.trunk_group_info() == {}
        finally:
            eng.shutdown()

    def test_reregistration_replaces_member(self):
        """Hot-reloading a task must REPLACE its bank row, never append
        a stale duplicate; re-registering as non-fusable evicts it."""
        eng = make_shared_trunk_engine(metrics=fresh_series())
        try:
            t = eng._tasks["intent"]
            eng.register_task("intent", "sequence", t.module, t.params,
                              t.tokenizer, t.labels, max_seq_len=512)
            (members,) = eng.trunk_group_info().values()
            assert sorted(members) == sorted(TASKS)  # no duplicate row
            eng.register_task("intent", "sequence", t.module, t.params,
                              t.tokenizer, t.labels, max_seq_len=512,
                              fuse=False)
            (members,) = eng.trunk_group_info().values()
            assert sorted(members) == sorted(set(TASKS) - {"intent"})
            res = eng.classify("intent", "still serves traditionally")
            assert res.label in eng.task_labels("intent")
            # remaining members still serve correct fused results
            res2 = eng.classify("fact_check", "check this")
            assert res2.label in eng.task_labels("fact_check")
        finally:
            eng.shutdown()

    def test_config_knob_parses(self):
        assert InferenceEngineConfig.from_dict({}).fuse_trunks is True
        assert InferenceEngineConfig.from_dict(
            {"fuse_trunks": False}).fuse_trunks is False


class TestFusedEquivalence:
    TEXTS = ["what is the capital of france",
             "sue them for breach of contract now",
             "does this medicine interact with alcohol",
             "segfault in my rust program"]

    def test_classify_matches_traditional(self, fused_engine,
                                          unfused_engine):
        """Same inputs through fused vs per-task execution produce
        identical ClassResults — including the LoRA member."""
        for task in TASKS:
            fused = fused_engine.classify_batch(task, self.TEXTS)
            trad = unfused_engine.classify_batch(task, self.TEXTS)
            for f, t in zip(fused, trad):
                assert f.label == t.label
                assert f.index == t.index
                assert set(f.probs) == set(t.probs)
                for k in f.probs:
                    assert f.probs[k] == pytest.approx(t.probs[k],
                                                       abs=1e-4)

    def test_classify_multi_matches_traditional(self, fused_engine,
                                                unfused_engine):
        """Mixed-task fused batches (one item, K tasks) decode each task
        with its own label set, matching K separate traditional runs."""
        out = fused_engine.classify_multi(TASKS, self.TEXTS)
        for task in TASKS:
            trad = unfused_engine.classify_batch(task, self.TEXTS)
            for f, t in zip(out[task], trad):
                assert f.label == t.label
                assert f.confidence == pytest.approx(t.confidence,
                                                     abs=1e-4)

    def test_lora_adapter_actually_applies(self, fused_engine):
        """The LoRA member's stacked adapter is non-zero in the bank —
        the fused head math includes the delta, it does not silently run
        the base head (equivalence above proves it matches module.apply,
        which applies the delta)."""
        g = list(fused_engine._groups_by_gid.values())[0]
        assert "lora_A" in g.bank and "lora_B" in g.bank
        row = g.row_of["fact_check"]
        assert float(np.abs(np.asarray(g.bank["lora_B"][row])).max()) > 0
        # non-LoRA members ride the same batch with exact no-op rows
        assert float(np.abs(np.asarray(
            g.bank["lora_B"][g.row_of["intent"]])).max()) == 0.0

    def test_concurrent_mixed_tasks_coalesce(self):
        """Concurrent classify() calls on DIFFERENT member tasks land in
        one (trunk, bucket) group — the cross-task coalescing the
        (task, bucket) keying could never do."""
        series = fresh_series()
        cfg = InferenceEngineConfig(max_batch_size=8, max_wait_ms=50.0,
                                    seq_len_buckets=[32, 128, 512])
        eng = make_shared_trunk_engine(engine_cfg=cfg, metrics=series)
        try:
            results = {}

            def worker(i):
                task = TASKS[i % len(TASKS)]
                results[i] = eng.classify(task, f"payload number {i}")

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert len(results) == 12
            stats = eng.batcher.stats()
            # 12 items from 3 different tasks rode FEWER batches than
            # items — impossible under per-task keys with max_wait high
            assert stats["max_batch"] >= 2
            fused = sum(v for k, v in
                        series.trunk_forwards.values().items()
                        if ("path", "fused") in k)
            assert 0 < fused < 12
        finally:
            eng.shutdown()


class TestFusedDedup:
    def test_identical_sequences_share_one_trunk_row(self):
        """Identical token sequences within one fused batch ride a
        single trunk row and fan logits out on demux — counter-proven:
        6 copies of the same prompt collapse 5 rows, and every copy's
        result equals the singleton run bit-for-bit."""
        series = fresh_series()
        cfg = InferenceEngineConfig(max_batch_size=16, max_wait_ms=20.0,
                                    seq_len_buckets=[32, 128, 512])
        eng = make_shared_trunk_engine(engine_cfg=cfg, metrics=series)
        try:
            text = "the same hot prompt arriving six times"
            task = TASKS[0]
            solo = eng.classify(task, text)
            before = series.fused_dedup_rows.total()
            out = eng.classify_batch(task, [text] * 6)
            assert series.fused_dedup_rows.total() - before >= 5
            for r in out:
                assert r.label == solo.label
                assert r.index == solo.index
                for k in r.probs:
                    assert r.probs[k] == pytest.approx(solo.probs[k],
                                                       abs=1e-5)
        finally:
            eng.shutdown()

    def test_dedup_keeps_mixed_batches_correct(self, fused_engine,
                                               unfused_engine):
        """Duplicates mixed with distinct prompts: the deduped fused
        batch still matches the unfused reference for every item."""
        texts = ["alpha prompt", "alpha prompt", "beta prompt",
                 "alpha prompt", "gamma prompt", "beta prompt"]
        for task in TASKS:
            fused = fused_engine.classify_batch(task, texts)
            trad = unfused_engine.classify_batch(task, texts)
            for f, t in zip(fused, trad):
                assert f.label == t.label
                for k in f.probs:
                    assert f.probs[k] == pytest.approx(t.probs[k],
                                                       abs=1e-4)

    def test_dedup_counter_registered(self):
        series = fresh_series()
        assert series.fused_dedup_rows.total() == 0


class TestFanoutCounters:
    def _dispatcher(self, eng):
        from semantic_router_tpu.signals.dispatch import SignalDispatcher
        from semantic_router_tpu.signals.learned import (
            BinaryTaskSignal,
            DomainSignal,
        )

        return SignalDispatcher([
            DomainSignal(eng, [DomainRule(name=n)
                               for n in eng.task_labels("intent")]),
            BinaryTaskSignal(eng, [NamedRule(name=n) for n in
                                   eng.task_labels("fact_check")],
                             "fact_check", "fact_check"),
            BinaryTaskSignal(eng, [NamedRule(name=n) for n in
                                   eng.task_labels("user_feedback")],
                             "user_feedback", "user_feedback"),
        ])

    def test_k_signals_one_trunk_forward_one_tokenization(self):
        """Acceptance: a request activating K=3 learned signals on one
        shared trunk executes exactly 1 trunk forward and 1 tokenization
        (counter-backed), with outputs matching the unfused engine."""
        from semantic_router_tpu.signals.base import (
            Message,
            RequestContext,
        )

        series = fresh_series()
        eng = make_shared_trunk_engine(lora_tasks=("fact_check",),
                                       metrics=series)
        disp = self._dispatcher(eng)
        try:
            ctx = RequestContext(messages=[
                Message("user", "please fact check the capital of france")])
            _, report = disp.evaluate(ctx)
            assert not any(r.error for r in report.results.values())
            assert series.trunk_forwards.total() == 1
            assert series.tokenizations.total() == 1
            # all three families produced results from that one forward
            assert set(report.results) == {"domain", "fact_check",
                                           "user_feedback"}
            # memo carries the per-task results the evaluators consumed
            assert len(ctx.class_memo) == 3
        finally:
            disp.shutdown()
            eng.shutdown()

    def test_fanout_matches_unfused_results(self, unfused_engine):
        """The prefetched fan-out's decisions equal the per-task path's."""
        from semantic_router_tpu.signals.base import (
            Message,
            RequestContext,
        )

        series = fresh_series()
        eng = make_shared_trunk_engine(lora_tasks=("fact_check",),
                                       metrics=series)
        disp = self._dispatcher(eng)
        disp_ref = self._dispatcher(unfused_engine)
        try:
            msg = "my program crashes with a segmentation fault"
            a = disp.evaluate(RequestContext(
                messages=[Message("user", msg)]))[1]
            b = disp_ref.evaluate(RequestContext(
                messages=[Message("user", msg)]))[1]
            for fam in a.results:
                ha = [(h.rule, round(h.confidence, 4))
                      for h in a.results[fam].hits]
                hb = [(h.rule, round(h.confidence, 4))
                      for h in b.results[fam].hits]
                assert ha == hb
        finally:
            disp.shutdown()
            disp_ref.shutdown()
            eng.shutdown()

    def test_tokenize_once_cache(self, fused_engine):
        cache = EncodingCache()
        fused_engine.classify("intent", "same text twice",
                              enc_cache=cache)
        fused_engine.classify("fact_check", "same text twice",
                              enc_cache=cache)
        assert cache.misses == 1
        assert cache.hits == 1


SEQ_PAIR = [("intent", ["business", "law", "health", "computer science",
                        "other"]),
            ("jailbreak", ["benign", "jailbreak"])]
PII_TASK = ("pii", ["O", "B-EMAIL_ADDRESS", "I-EMAIL_ADDRESS",
                    "B-PHONE_NUMBER", "I-PHONE_NUMBER",
                    "B-PERSON", "I-PERSON"])
# under 1/7: with seven labels every token's best probability passes, so
# a random head reports spans to compare
LOW = 0.1
SHORT_TEXT = "mail alice at example dot com or call 555 0100 about the contract"
LONG_TEXT = "please forward the invoice to bob " * 120  # past max_seq_len


def _bank_engine(series=None, pii_on_trunk=True):
    """intent + jailbreak (sequence) and pii (token): one trunk group, or
    pii on a trunk of its own draw (a token task on no trunk group)."""
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.engine.testing import tiny_config
    from semantic_router_tpu.models.modernbert import (
        ModernBertForTokenClassification,
    )

    eng = make_shared_trunk_engine(
        tasks=SEQ_PAIR, token_tasks=[PII_TASK] if pii_on_trunk else None,
        metrics=series or fresh_series())
    if not pii_on_trunk:
        module = ModernBertForTokenClassification(
            tiny_config(len(PII_TASK[1])))
        params = module.init(jax.random.PRNGKey(77),
                             jnp.ones((1, 8), jnp.int32))
        eng.register_task("pii", "token", module, params,
                          HashTokenizer(vocab_size=1024), PII_TASK[1],
                          max_seq_len=512)
    return eng


def _bank_dispatcher(eng, pii_rules=None, jailbreak_rules=None,
                     extra=(), **kw):
    from semantic_router_tpu.config.schema import JailbreakRule, PIIRule
    from semantic_router_tpu.signals.dispatch import SignalDispatcher
    from semantic_router_tpu.signals.learned import (
        DomainSignal,
        JailbreakSignal,
        PIISignal,
    )

    return SignalDispatcher([
        DomainSignal(eng, [DomainRule(name=n)
                           for n in eng.task_labels("intent")]),
        JailbreakSignal(eng, jailbreak_rules or [
            JailbreakRule(name="jb", method="classifier", threshold=0.0)]),
        PIISignal(eng, pii_rules or [PIIRule(name="restricted",
                                             threshold=LOW)]),
        *extra], **kw)


def _ctx(text, history=()):
    from semantic_router_tpu.signals.base import Message, RequestContext

    return RequestContext(messages=[Message("user", h) for h in history]
                          + [Message("user", text)])


def _spans(result):
    return [(e.type, e.start, e.end, e.text) for e in result.entities]


def _assert_same_entities(got, want):
    assert _spans(got) == _spans(want)
    assert [e.score for e in got.entities] == pytest.approx(
        [e.score for e in want.entities], abs=1e-5)
    assert got.truncated == want.truncated


def _hit_spans(report, rule):
    (hit,) = [h for h in report.results["pii"].hits if h.rule == rule]
    return [(e["type"], e["start"], e["end"]) for e in
            hit.detail["entities"]]


class TestOneItemAText:
    """The PII token task rides the sequence tasks' forward: a request
    activating domain + jailbreak + pii on one trunk group is ONE fused
    item, in flight beside the rest of the fan-out."""

    @pytest.mark.parametrize("text", [SHORT_TEXT, LONG_TEXT],
                             ids=["short", "truncated"])
    def test_three_families_one_forward_one_tokenization(self, text):
        series = fresh_series()
        eng = _bank_engine(series)
        disp = _bank_dispatcher(eng)
        try:
            ctx = _ctx(text)
            _, report = disp.evaluate(ctx)
            assert not any(r.error for r in report.results.values())
            assert series.trunk_forwards.total() == 1
            assert series.tokenizations.total() == 1
            assert {f: r.source for f, r in report.results.items()} == {
                "domain": "fused_bank", "jailbreak": "fused_bank",
                "pii": "fused_bank"}
            # the token member's answer is token_classify's alone
            rode = ctx.class_memo[(id(eng), "pii", text, LOW)]
            alone = eng.token_classify("pii", text, threshold=LOW)
            assert alone.entities
            assert alone.truncated == (text is LONG_TEXT)
            _assert_same_entities(rode, alone)
            assert _hit_spans(report, "restricted") == [
                s[:3] for s in _spans(alone)]
            # the sequence members' answers are classify_multi's alone
            seq = eng.classify_multi(["intent", "jailbreak"], [text])
            for task in ("intent", "jailbreak"):
                got = ctx.class_memo[(id(eng), task, text)]
                want = seq[task][0]
                assert (got.label, got.index, got.truncated) == \
                    (want.label, want.index, want.truncated)
                for k in want.probs:
                    assert got.probs[k] == pytest.approx(want.probs[k],
                                                         abs=1e-5)
        finally:
            disp.shutdown()
            eng.shutdown()

    def test_classify_multi_takes_token_members(self):
        """The engine entry: {task: [result per text]}, a
        TokenClassResult for the token task at the call's threshold, one
        trunk forward for texts of one bucket."""
        series = fresh_series()
        eng = _bank_engine(series)
        try:
            texts = [SHORT_TEXT, "write to carol about the audit"]
            out = eng.classify_multi(["intent", "jailbreak", "pii"], texts,
                                     threshold=LOW)
            assert series.trunk_forwards.total() == 1
            assert series.tokenizations.total() == len(texts)
            assert set(out) == {"intent", "jailbreak", "pii"}
            for i, text in enumerate(texts):
                _assert_same_entities(
                    out["pii"][i],
                    eng.token_classify("pii", text, threshold=LOW))
                assert out["intent"][i].label == \
                    eng.classify("intent", text).label
            # the threshold decides the spans: none pass 0.99
            strict = eng.classify_multi(["jailbreak", "pii"], texts,
                                        threshold=0.99)
            assert all(not r.entities for r in strict["pii"])
        finally:
            eng.shutdown()

    def test_fused_covers_token_members_of_the_group_only(self):
        on, off = _bank_engine(), _bank_engine(pii_on_trunk=False)
        try:
            assert on.fused_covers(["intent", "jailbreak", "pii"])
            assert on.fused_covers(["jailbreak", "pii"])
            assert not off.fused_covers(["intent", "jailbreak", "pii"])
            assert off.fused_covers(["intent", "jailbreak"])
            # off the group the call still answers, task by task
            out = off.classify_multi(["intent", "pii"], [SHORT_TEXT],
                                     threshold=LOW)
            _assert_same_entities(
                out["pii"][0],
                off.token_classify("pii", SHORT_TEXT, threshold=LOW))
            assert out["intent"][0].label == \
                off.classify("intent", SHORT_TEXT).label
        finally:
            on.shutdown()
            off.shutdown()

    def test_classify_multi_refuses_other_kinds(self):
        from semantic_router_tpu.engine.testing import make_embedding_engine

        eng = make_embedding_engine()
        try:
            with pytest.raises(TypeError, match="embed"):
                eng.classify_multi(["intent", "embedding"], ["x"])
            assert not eng.fused_covers(["intent", "embedding"])
        finally:
            eng.shutdown()

    # what the item cannot carry keeps its own call; every family still
    # answers.  ``forwards``: the fewest and the most steps the case
    # allows — calls in flight together on one trunk group may share a
    # step, a call made after the item came back cannot
    FALLBACKS = {
        "include_history_rule": dict(
            thresholds=[(LOW, True)], forwards=(1, 2),
            sources={"domain": "fused_bank", "jailbreak": "fused_bank",
                     "pii": "engine"}),
        "two_pii_thresholds": dict(
            thresholds=[(LOW, False), (0.3, False)], forwards=(2, 2),
            sources={"domain": "fused_bank", "jailbreak": "fused_bank",
                     "pii": "fused_bank"}),
        "token_task_on_no_trunk_group": dict(
            thresholds=[(LOW, False)], pii_on_trunk=False,
            forwards=(2, 2),
            sources={"domain": "fused_bank", "jailbreak": "fused_bank",
                     "pii": "engine"}),
        "item_raises": dict(
            thresholds=[(LOW, False)], item_error=RuntimeError("boom"),
            forwards=(1, 3),
            sources={"domain": "engine", "jailbreak": "engine",
                     "pii": "engine"}),
        "item_times_out": dict(
            thresholds=[(LOW, False)], item_error=TimeoutError(),
            forwards=(1, 3),
            sources={"domain": "engine", "jailbreak": "engine",
                     "pii": "engine"}),
    }

    @pytest.mark.parametrize("case", sorted(FALLBACKS))
    def test_fallbacks_each_family_still_answers(self, case):
        from semantic_router_tpu.config.schema import PIIRule

        spec = self.FALLBACKS[case]
        series = fresh_series()
        eng = _bank_engine(series, spec.get("pii_on_trunk", True))
        rules = [PIIRule(name=f"r{i}", threshold=th, include_history=hist)
                 for i, (th, hist) in enumerate(spec["thresholds"])]
        disp = _bank_dispatcher(eng, pii_rules=rules)
        if "item_error" in spec:
            def failing(*a, **kw):
                raise spec["item_error"]

            eng.classify_multi = failing
        try:
            ctx = _ctx(SHORT_TEXT, history=["earlier turn from dave"])
            _, report = disp.evaluate(ctx)
            assert set(report.results) == {"domain", "jailbreak", "pii"}
            assert not any(r.error for r in report.results.values())
            assert {f: r.source for f, r in report.results.items()} \
                == spec["sources"]
            fewest, most = spec["forwards"]
            assert fewest <= series.trunk_forwards.total() <= most
            for rule in rules:
                text = ctx.text_for(rule.include_history)
                alone = eng.token_classify("pii", text,
                                           threshold=rule.threshold)
                assert _hit_spans(report, rule.name) == [
                    s[:3] for s in _spans(alone)]
        finally:
            disp.shutdown()
            eng.shutdown()

    def test_dead_pii_head_does_not_take_jailbreak_with_it(self):
        """One item failing falls back per family: it does not fail
        three families."""
        eng = _bank_engine()
        disp = _bank_dispatcher(eng)

        def dead(*a, **kw):
            raise RuntimeError("pii head is gone")

        eng.classify_multi = dead
        eng.token_classify = dead
        try:
            _, report = disp.evaluate(_ctx(SHORT_TEXT))
            assert "pii head is gone" in report.results["pii"].error
            for fam in ("domain", "jailbreak"):
                assert not report.results[fam].error
                assert report.results[fam].source == "engine"
                assert report.results[fam].hits
        finally:
            disp.shutdown()
            eng.shutdown()

    def test_embedding_item_is_submitted_while_the_fused_item_rides(self):
        """The families the item does not serve start first: the fused
        item's return is gated on the embedding call having begun."""
        import jax
        import jax.numpy as jnp

        from semantic_router_tpu.config.schema import EmbeddingRule
        from semantic_router_tpu.engine.testing import tiny_config
        from semantic_router_tpu.models.embeddings import (
            MmBertEmbeddingModel,
        )
        from semantic_router_tpu.signals.embedding_signal import (
            EmbeddingSignal,
        )

        eng = _bank_engine()
        module = MmBertEmbeddingModel(tiny_config(0))
        eng.register_task(
            "embedding", "embedding", module,
            module.init(jax.random.PRNGKey(5), jnp.ones((1, 8), jnp.int32)),
            HashTokenizer(vocab_size=1024), [], max_seq_len=512)
        disp = _bank_dispatcher(eng, extra=[EmbeddingSignal(eng, [
            EmbeddingRule(name="support", threshold=0.0,
                          candidates=["how to configure the system"])])])
        embed_begun = threading.Event()
        seen = {}
        inner_embed, inner_multi = eng.embed, eng.classify_multi

        def embed(task, texts, *a, **kw):
            if SHORT_TEXT in texts:
                embed_begun.set()
            return inner_embed(task, texts, *a, **kw)

        def classify_multi(tasks, texts, **kw):
            out = inner_multi(tasks, texts, **kw)
            # the gate: a dispatcher that waits for the item before it
            # fans out never calls embed, and fails here
            seen["embed_begun"] = embed_begun.wait(timeout=60)
            return out

        eng.embed, eng.classify_multi = embed, classify_multi
        try:
            _, report = disp.evaluate(_ctx(SHORT_TEXT))
            assert seen == {"embed_begun": True}
            assert not any(r.error for r in report.results.values())
            assert report.results["embedding"].hits
            assert report.results["pii"].source == "fused_bank"
        finally:
            disp.shutdown()
            eng.shutdown()

    def test_generative_jailbreak_takes_none_of_the_new_path(self):
        """A jailbreak task answered by generation sits on no trunk
        group: its family goes to guard_classify, and with one other
        task left there is no item."""
        from types import SimpleNamespace

        from semantic_router_tpu.utils.tokenization import Encoding

        eng = make_shared_trunk_engine(tasks=SEQ_PAIR[:1],
                                       token_tasks=[PII_TASK],
                                       metrics=fresh_series())

        class Guard:
            tokenizer = SimpleNamespace(encode=lambda text, **kw: Encoding(
                ids=[1, 2, 3], attention_mask=[1, 1, 1],
                offsets=[(0, 0)] * 3))

            def generate(self, prompts, **kw):
                return [SimpleNamespace(
                    text="Safety: Safe\nCategories: None\n",
                    token_ids=[], truncated=False) for _ in prompts]

        eng.register_generative("jailbreak", Guard())
        calls = []
        eng.classify_multi = lambda *a, **kw: calls.append(a)
        disp = _bank_dispatcher(eng)
        try:
            only_guard = [disp.evaluators["jailbreak"]]
            assert disp._gather_fused(_ctx(SHORT_TEXT), only_guard) == []
            ctx = _ctx(SHORT_TEXT)
            (item,) = disp._gather_fused(ctx, disp.active_evaluators())
            assert item.tasks() == ["intent", "pii"]
            # the guard cell's shape: one engine family, no item, inline
            guard_only = _bank_dispatcher(eng)
            guard_only.used_types = {"jailbreak"}
            _, report = guard_only.evaluate(_ctx(SHORT_TEXT))
            guard_only.shutdown()
            assert not report.results["jailbreak"].error
            assert report.results["jailbreak"].source == "engine"
            assert calls == []
        finally:
            disp.shutdown()
            eng.shutdown()

    def test_cascade_results_unchanged(self):
        """engine/cascade keeps the blocking prefetch: a wave's families
        answer what the plain fan-out answers, PII from the memo."""
        from semantic_router_tpu.config.schema import (
            Decision,
            ModelRef,
            RuleNode,
        )
        from semantic_router_tpu.decision.engine import DecisionEngine
        from semantic_router_tpu.engine.cascade import (
            CascadeEvaluator,
            normalize_cascade,
        )

        def leaf(styp, name):
            return RuleNode(signal_type=styp, name=name)

        eng = _bank_engine()
        disp = _bank_dispatcher(eng)
        decisions = DecisionEngine([
            Decision(name="block", priority=9, rules=leaf("jailbreak", "jb"),
                     model_refs=[ModelRef(model="m")]),
            Decision(name="pii", priority=5,
                     rules=leaf("pii", "restricted"),
                     model_refs=[ModelRef(model="m")]),
            Decision(name="law", priority=1, rules=leaf("domain", "law"),
                     model_refs=[ModelRef(model="m")])], "priority")
        casc = CascadeEvaluator(metrics=fresh_series())
        casc.configure(normalize_cascade({"enabled": True}))
        try:
            plain_signals, plain = disp.evaluate(_ctx(SHORT_TEXT))
            signals, report = casc.evaluate(_ctx(SHORT_TEXT), disp,
                                            decisions)
            assert signals.matches == plain_signals.matches
            for fam, want in plain.results.items():
                got = report.results[fam]
                assert (got.error, got.source) == (want.error, want.source)
                assert [(h.rule, round(h.confidence, 5))
                        for h in got.hits] == \
                    [(h.rule, round(h.confidence, 5)) for h in want.hits]
            assert report.results["pii"].source == "fused_bank"
        finally:
            disp.shutdown()
            eng.shutdown()

    def test_results_counted_by_family_and_source(self):
        registry = MetricsRegistry()
        series = MetricSeries(registry)
        eng = _bank_engine()
        disp = _bank_dispatcher(eng, metrics=series)
        try:
            disp.evaluate(_ctx(SHORT_TEXT))
            for fam in ("domain", "jailbreak", "pii"):
                assert series.signal_results.get(
                    family=fam, source="fused_bank") == 1
            assert series.signal_results.total() == 3
            assert ('llm_signal_results_total{family="pii",'
                    'source="fused_bank"} 1') in registry.expose()
        finally:
            disp.shutdown()
            eng.shutdown()


class TestJitCacheBudget:
    def test_shapes_per_trunk_within_budget(self):
        """The fused bank's compiled-shape count stays ≤
        |buckets|·log2(max_batch) per TRUNK — one closed shape set for
        the whole bank, not one per task (the tentpole's cache story)."""
        cfg = InferenceEngineConfig(max_batch_size=8, max_wait_ms=1.0,
                                    seq_len_buckets=[32, 128, 512])
        series = fresh_series()
        eng = make_shared_trunk_engine(engine_cfg=cfg, metrics=series)
        try:
            short = "short one"
            medium = "word " * 60
            long = "word " * 300
            for task in TASKS:
                for text in (short, medium, long):
                    eng.classify(task, text)
            eng.classify_multi(TASKS, [short, medium, long, short, long])
            census = eng.shape_census()
            trunk_keys = [k for k in census if k.startswith("trunk:")]
            assert len(trunk_keys) == 1
            budget = len(cfg.seq_len_buckets) * int(
                math.log2(cfg.max_batch_size))
            assert len(census[trunk_keys[0]]) <= budget
            # and NO per-task shapes leaked out of the fused group
            assert not any(k.startswith("task:") for k in census)
        finally:
            eng.shutdown()


class TestBucketOverflow:
    def test_overflow_tagged_and_counted(self):
        """max_seq_len past the largest bucket: the clamp clips at the
        bucket edge, tags the result truncated, and counts — never
        silent."""
        series = fresh_series()
        cfg = InferenceEngineConfig(max_batch_size=8, max_wait_ms=1.0,
                                    seq_len_buckets=[32])
        eng = make_shared_trunk_engine(engine_cfg=cfg, metrics=series)
        try:
            res = eng.classify("intent", "word " * 100)
            assert res.truncated
            assert series.bucket_overflows.total() >= 1
        finally:
            eng.shutdown()

    def test_pow2_batch_non_pow2_max(self):
        # batch dims draw from {1,2,4,…} ∪ {max_batch}: one extra shape,
        # still a closed set
        assert pow2_batch(1, 12) == 1
        assert pow2_batch(5, 12) == 8
        assert pow2_batch(9, 12) == 12
        assert pow2_batch(13, 12) == 12

    def test_pick_bucket_clamps_documented(self):
        assert pick_bucket(999, [32, 128]) == 128


class TestBatcherHistograms:
    def test_stats_report_wait_and_fill(self):
        series = fresh_series()

        def runner(key, items):
            return [0] * len(items)

        b = DynamicBatcher(runner, max_batch_size=8, max_wait_ms=5.0,
                           name="histo-test", metrics=series)
        try:
            futs = b.submit_many("g", list(range(6)))
            for f in futs:
                f.result(timeout=5)
            stats = b.stats()
            assert stats["queue_wait_p99_s"] >= 0.0
            assert 0.0 < stats["fill_ratio_mean"] <= 1.0
            assert series.batcher_queue_wait.count(
                batcher="histo-test") == 6
            # exposition carries the series for /metrics scrapes
            text = series.registry.expose()
            assert "llm_batcher_queue_wait_seconds" in text
            assert "llm_batcher_batch_fill_ratio" in text
        finally:
            b.shutdown()


class TestBankSharding:
    def test_head_bank_specs_task_axis_over_tp(self):
        from jax.sharding import PartitionSpec as P

        from semantic_router_tpu.parallel import (
            create_mesh,
            head_bank_specs,
        )

        mesh = create_mesh({"dp": 4, "tp": 2})
        bank = {"cls_kernel": np.zeros((4, 16, 5), np.float32),
                "scale": np.zeros((4,), np.float32)}
        specs = head_bank_specs(bank, mesh)
        assert specs["cls_kernel"] == P("tp", None, None)
        assert specs["scale"] == P("tp")
        # indivisible task count replicates rather than erroring
        bank3 = {"cls_kernel": np.zeros((3, 16, 5), np.float32)}
        assert head_bank_specs(bank3, mesh)["cls_kernel"] == P()
        # dp-only mesh: bank replicates (dp shards batches, not heads)
        assert head_bank_specs(bank, create_mesh({"dp": 8}))[
            "cls_kernel"] == P()

    def test_fused_serving_on_cpu_mesh_matches_unsharded(self):
        """The classifier-bank sharding story on a CPU mesh: 4 tasks'
        head bank laid out over tp=2, trunk Megatron-sharded, batches
        dp-sharded — results equal the unsharded fused engine's."""
        four = SHARED_TRUNK_TASKS + [("jailbreak", ["benign", "jailbreak"])]
        mesh_cfg = InferenceEngineConfig(
            max_batch_size=8, max_wait_ms=1.0,
            seq_len_buckets=[32, 128, 512],
            mesh_shape={"dp": 4, "tp": 2})
        eng_mesh = make_shared_trunk_engine(
            tasks=four, lora_tasks=("fact_check",), engine_cfg=mesh_cfg,
            metrics=fresh_series())
        eng_plain = make_shared_trunk_engine(
            tasks=four, lora_tasks=("fact_check",),
            metrics=fresh_series())
        try:
            g = list(eng_mesh._groups_by_gid.values())[0]
            # the spec landed: task axis of the bank is tp-sharded
            from semantic_router_tpu.parallel import AXIS_TENSOR

            spec = g.bank["cls_kernel"].sharding.spec
            assert spec[0] == AXIS_TENSOR
            texts = ["hello mesh world", "fact check this claim today"]
            out_m = eng_mesh.classify_multi([n for n, _ in four], texts)
            out_p = eng_plain.classify_multi([n for n, _ in four], texts)
            for task in out_m:
                for a, b in zip(out_m[task], out_p[task]):
                    assert a.label == b.label
                    assert a.confidence == pytest.approx(b.confidence,
                                                         abs=1e-3)
        finally:
            eng_mesh.shutdown()
            eng_plain.shutdown()


class TestWindowedStillTraditional:
    def test_classify_windowed_on_fused_task(self, fused_engine):
        """Stride-window classification bypasses the fused group (per-
        task windows) and still serves."""
        res = fused_engine.classify_windowed("intent", "word " * 700,
                                             stride=16)
        assert res.label in fused_engine.task_labels("intent")
        assert res.truncated is False


class TestContentAddressedFingerprint:
    """Content-addressed trunk fingerprint (ISSUE 9 satellite, carried
    from PR 1): different checkpoint loads with IDENTICAL frozen trunks
    fuse into one TrunkGroup — object identity is no longer required —
    while trunks differing in a single weight stay separate."""

    def _two_task_engine(self, copy_trunk: bool, perturb: bool = False):
        import flax
        import jax
        import jax.numpy as jnp

        from semantic_router_tpu.engine.classify import InferenceEngine
        from semantic_router_tpu.engine.testing import TINY, tiny_config
        from semantic_router_tpu.models.modernbert import (
            ModernBertForSequenceClassification,
        )

        cfg = InferenceEngineConfig(max_batch_size=8, max_wait_ms=1.0,
                                    seq_len_buckets=[32, 128])
        eng = InferenceEngine(cfg, metrics=fresh_series())
        tok = HashTokenizer(vocab_size=TINY["vocab_size"])
        key = jax.random.PRNGKey(7)
        dummy = jnp.ones((1, 8), jnp.int32)
        trunk = None
        for i, (name, labels) in enumerate(
                [("task_a", ["x", "y"]), ("task_b", ["p", "q", "r"])]):
            module = ModernBertForSequenceClassification(
                tiny_config(len(labels)))
            params = flax.core.unfreeze(
                module.init(jax.random.fold_in(key, i), dummy))
            if trunk is None:
                trunk = params["params"]["model"]
            elif copy_trunk:
                # DISTINCT arrays with identical bytes — the two-
                # checkpoint-files-same-frozen-trunk shape
                copied = jax.tree_util.tree_map(
                    lambda a: jnp.array(np.array(a)), trunk)
                if perturb:
                    leaves, treedef = jax.tree_util.tree_flatten(copied)
                    leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(
                        1e-3)
                    copied = jax.tree_util.tree_unflatten(treedef,
                                                          leaves)
                params["params"]["model"] = copied
            engine_trunk = params["params"]["model"]
            assert copy_trunk is False or i == 0 \
                or engine_trunk is not trunk  # really distinct objects
            eng.register_task(name, "sequence", module, params, tok,
                              labels, max_seq_len=128)
        return eng

    def test_identical_content_distinct_arrays_fuse(self):
        eng = self._two_task_engine(copy_trunk=True)
        try:
            groups = eng.trunk_group_info()
            assert len(groups) == 1
            (members,) = groups.values()
            assert sorted(members) == ["task_a", "task_b"]
            # and the fused path still serves correct labels
            res = eng.classify("task_b", "hello fused world")
            assert res.label in ("p", "q", "r")
        finally:
            eng.shutdown()

    def test_single_weight_difference_splits_groups(self):
        eng = self._two_task_engine(copy_trunk=True, perturb=True)
        try:
            assert len(eng.trunk_group_info()) == 2
        finally:
            eng.shutdown()

    def test_equivalent_tokenizer_instances_do_not_split(self):
        import flax
        import jax
        import jax.numpy as jnp

        from semantic_router_tpu.engine.classify import InferenceEngine
        from semantic_router_tpu.engine.testing import TINY, tiny_config
        from semantic_router_tpu.models.modernbert import (
            ModernBertForSequenceClassification,
        )

        cfg = InferenceEngineConfig(max_batch_size=8, max_wait_ms=1.0,
                                    seq_len_buckets=[32, 128])
        eng = InferenceEngine(cfg, metrics=fresh_series())
        key = jax.random.PRNGKey(9)
        dummy = jnp.ones((1, 8), jnp.int32)
        trunk = None
        for i, name in enumerate(["t1", "t2"]):
            module = ModernBertForSequenceClassification(tiny_config(2))
            params = flax.core.unfreeze(
                module.init(jax.random.fold_in(key, 0), dummy))
            if trunk is None:
                trunk = params["params"]["model"]
            else:
                params["params"]["model"] = trunk
            # a FRESH HashTokenizer per task: same vocab = same content
            eng.register_task(name, "sequence", module, params,
                              HashTokenizer(vocab_size=TINY["vocab_size"]),
                              ["a", "b"], max_seq_len=128)
        try:
            assert len(eng.trunk_group_info()) == 1
        finally:
            eng.shutdown()

    def test_digest_memo_serves_identity_case(self):
        from semantic_router_tpu.engine.classify import _leaf_digest

        arr = np.arange(16.0, dtype=np.float32)
        d1 = _leaf_digest(arr)
        assert _leaf_digest(arr) == d1            # memo hit
        assert _leaf_digest(arr.copy()) == d1     # content equal
        arr2 = arr.copy()
        arr2[3] += 1.0
        assert _leaf_digest(arr2) != d1
