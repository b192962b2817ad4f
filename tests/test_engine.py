"""Inference engine + dynamic batcher tests (reference parity targets:
continuous_batch_scheduler.rs behaviours, unified classifier batch API,
token span decoding)."""

import threading
import time

import numpy as np
import pytest

from semantic_router_tpu.engine import DynamicBatcher, pick_bucket, pow2_batch
from semantic_router_tpu.engine.testing import make_test_engine
from semantic_router_tpu.utils import HashTokenizer, decode_entity_spans


@pytest.fixture(scope="module")
def engine():
    eng = make_test_engine()
    yield eng
    eng.shutdown()


class TestBatcherPrimitives:
    def test_pow2_batch(self):
        assert pow2_batch(1, 32) == 1
        assert pow2_batch(3, 32) == 4
        assert pow2_batch(9, 32) == 16
        assert pow2_batch(33, 32) == 32

    def test_pick_bucket(self):
        buckets = [128, 512, 2048]
        assert pick_bucket(5, buckets) == 128
        assert pick_bucket(128, buckets) == 128
        assert pick_bucket(129, buckets) == 512
        assert pick_bucket(99999, buckets) == 2048

    def test_batcher_coalesces(self):
        batches = []

        def runner(key, items):
            batches.append(len(items))
            return [item.payload * 2 for item in items]

        b = DynamicBatcher(runner, max_batch_size=8, max_wait_ms=20.0)
        futs = b.submit_many("g", list(range(6)))
        assert [f.result(timeout=5) for f in futs] == [0, 2, 4, 6, 8, 10]
        # all six should ride few batches (coalesced), not six singles
        assert sum(batches) == 6
        assert len(batches) <= 3
        b.shutdown()

    def test_batcher_full_batch_fires_immediately(self):
        def runner(key, items):
            return [0] * len(items)

        b = DynamicBatcher(runner, max_batch_size=4, max_wait_ms=10_000.0)
        futs = b.submit_many("g", [1, 2, 3, 4])
        t0 = time.perf_counter()
        for f in futs:
            f.result(timeout=5)
        assert time.perf_counter() - t0 < 5.0  # did not wait max_wait

    def test_batcher_low_qps_no_added_latency(self):
        def runner(key, items):
            return [0] * len(items)

        b = DynamicBatcher(runner, max_batch_size=32, max_wait_ms=5_000.0)
        t0 = time.perf_counter()
        b.submit("g", 1).result(timeout=10)
        # single idle request must not wait out max_wait_ms (hard-part 2)
        assert time.perf_counter() - t0 < 1.0
        b.shutdown()

    def test_batcher_error_fails_open(self):
        def runner(key, items):
            raise ValueError("model exploded")

        b = DynamicBatcher(runner, max_batch_size=4, max_wait_ms=1.0)
        fut = b.submit("g", 1)
        with pytest.raises(ValueError, match="model exploded"):
            fut.result(timeout=5)
        b.shutdown()

    def test_separate_groups_not_mixed(self):
        seen = []

        def runner(key, items):
            seen.append((key, len(items)))
            return [key] * len(items)

        b = DynamicBatcher(runner, max_batch_size=8, max_wait_ms=5.0)
        f1 = b.submit_many("a", [1, 2])
        f2 = b.submit_many("b", [3])
        assert [f.result(timeout=5) for f in f1] == ["a", "a"]
        assert [f.result(timeout=5) for f in f2] == ["b"]
        assert all(k in ("a", "b") for k, _ in seen)
        b.shutdown()


class TestEngine:
    def test_sequence_classify(self, engine):
        res = engine.classify("intent", "what is the capital of france")
        assert res.label in engine.task_labels("intent")
        assert 0.0 < res.confidence <= 1.0
        assert abs(sum(res.probs.values()) - 1.0) < 1e-4

    def test_deterministic(self, engine):
        a = engine.classify("intent", "hello world")
        b = engine.classify("intent", "hello world")
        assert a.label == b.label
        assert a.confidence == pytest.approx(b.confidence, abs=1e-5)

    def test_batch_matches_single(self, engine):
        texts = [f"question number {i} about topic {i%3}" for i in range(10)]
        batch = engine.classify_batch("intent", texts)
        singles = [engine.classify("intent", t) for t in texts]
        for b, s in zip(batch, singles):
            assert b.label == s.label
            # batch padding changes XLA reduction order slightly
            assert b.confidence == pytest.approx(s.confidence, abs=5e-3)

    def test_concurrent_load_coalesces(self, engine):
        results = {}

        def worker(i):
            results[i] = engine.classify("jailbreak", f"payload {i}")

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 16
        stats = engine.batcher.stats()
        assert stats["max_batch"] >= 2  # some coalescing happened

    def test_token_classify_returns_spans(self, engine):
        res = engine.token_classify("pii", "contact john at j@x.com now",
                                    threshold=0.0)
        for e in res.entities:
            # spans must be exact substrings (offset mapping contract)
            assert e.text == "contact john at j@x.com now"[e.start:e.end]

    def test_multi_single_task_matches_batch(self, engine):
        texts = ["alpha beta", "gamma delta"]
        out = engine.classify_multi(["intent"], texts)
        assert set(out) == {"intent"}
        for got, want in zip(out["intent"],
                             engine.classify_batch("intent", texts)):
            assert got.label == want.label
            assert got.confidence == pytest.approx(want.confidence,
                                                   abs=1e-5)

    def test_multi_different_trunks_served_per_task(self, engine):
        """Tasks of different trunks share no fused item: classify_multi
        serves each through its own call, and answers what those calls
        answer."""
        tasks = ["intent", "jailbreak", "pii"]
        assert not engine.fused_covers(tasks)
        text = "contact john at j@x.com now"
        out = engine.classify_multi(tasks, [text], threshold=0.0)
        assert set(out) == set(tasks)
        for task in ("intent", "jailbreak"):
            want = engine.classify_batch(task, [text])[0]
            assert out[task][0].label == want.label
            assert out[task][0].probs == pytest.approx(want.probs,
                                                       abs=1e-5)
        want = engine.token_classify("pii", text, threshold=0.0)
        assert [(e.type, e.start, e.end) for e in out["pii"][0].entities] \
            == [(e.type, e.start, e.end) for e in want.entities]

    def test_unknown_task_raises(self, engine):
        with pytest.raises(KeyError, match="not registered"):
            engine.classify("nope", "x")

    def test_long_text_truncated_not_crashing(self, engine):
        res = engine.classify("intent", "word " * 5000)
        assert res.label


class TestSpanDecoding:
    def test_bio_merge(self):
        text = "email a@b.c please"
        offsets = [(0, 0), (0, 5), (6, 11), (12, 18), (0, 0)]
        labels = ["O", "O", "B-EMAIL", "O", "O"]
        scores = [1.0, 0.9, 0.95, 0.9, 1.0]
        spans = decode_entity_spans(text, offsets, labels, scores)
        assert len(spans) == 1
        assert spans[0]["text"] == "a@b.c"
        assert spans[0]["type"] == "EMAIL"

    def test_bi_continuation(self):
        text = "call john smith now"
        offsets = [(0, 4), (5, 9), (10, 15), (16, 19)]
        labels = ["O", "B-PERSON", "I-PERSON", "O"]
        scores = [1.0, 0.9, 0.8, 1.0]
        spans = decode_entity_spans(text, offsets, labels, scores)
        assert len(spans) == 1
        assert spans[0]["text"] == "john smith"
        assert spans[0]["score"] == pytest.approx(0.8)  # min over span

    def test_b_b_splits(self):
        text = "alice bob"
        offsets = [(0, 5), (6, 9)]
        labels = ["B-PERSON", "B-PERSON"]
        scores = [0.9, 0.9]
        spans = decode_entity_spans(text, offsets, labels, scores)
        assert [s["text"] for s in spans] == ["alice", "bob"]

    def test_threshold_breaks_span(self):
        text = "x aaa bbb y"
        offsets = [(0, 1), (2, 5), (6, 9), (10, 11)]
        labels = ["O", "PHONE", "PHONE", "O"]
        scores = [1.0, 0.9, 0.3, 1.0]
        spans = decode_entity_spans(text, offsets, labels, scores,
                                    threshold=0.5)
        assert len(spans) == 1
        assert spans[0]["text"] == "aaa"
