"""Learned (TPU-backed) signal evaluators.

Each evaluator fans one request into the InferenceEngine's batching shim and
maps classifier outputs onto configured signal rules. Reference parity:

- domain   → category classifier (category_classifier.go;
             ClassifyMmBert32KIntent, candle-binding/semantic-router.go:2329)
- jailbreak→ classifier / pattern / hybrid methods
             (classifier_jailbreak_init.go, contrastive_jailbreak_classifier.go:265,
             ClassifyMmBert32KJailbreak :2417)
- pii      → token classifier + allowed-types policy
             (classifier_pii_init.go, token path :2538)
- fact_check → binary seq classifier (fact_check_classifier.go)
- user_feedback → feedback detector (feedback_detector.go:236)
- modality → modality classifier (AR / DIFFUSION / BOTH)
- embedding / preference / complexity-prototypes live in
  signals/embedding_signal.py (they need the embedding engine).

All evaluators fail open: engine errors are recorded on the SignalResult,
never raised across the dispatch boundary (processor_core.go:74-81 parity).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from ..config.schema import (
    DomainRule,
    JailbreakRule,
    NamedRule,
    PIIRule,
)
from ..engine.classify import InferenceEngine
from .base import RequestContext, SignalHit, SignalResult


class _EngineSignal:
    """Shared plumbing: run fn against the engine, fail open on errors."""

    signal_type = ""

    def __init__(self, engine: InferenceEngine, task: str) -> None:
        self.engine = engine
        self.task = task
        # classification of ctx.user_text the dispatcher may carry on
        # the request's ONE fused item (a trunk forward shared by every
        # learned family on that trunk group) and seed into the memo;
        # a token task rides it at ``prefetch_threshold`` (None: a
        # sequence task).  Blank the task to opt out.
        self.prefetch_task = task
        self.prefetch_threshold: Optional[float] = None

    def _classify(self, ctx: RequestContext, text: str):
        """Engine classify through the request's shared state: the
        dispatcher-seeded memo first (fused prefetch already paid the
        forward), else a classify call threading the tokenize-once
        cache.  Memo keys carry the engine's identity — two engines
        exposing the same task name must never read each other's
        results."""
        memo = getattr(ctx, "class_memo", None)
        key = (id(self.engine), self.task, text)
        if memo is not None:
            hit = memo.get(key)
            if hit is not None:
                # decision-record source attribution: this value rode
                # the fused-bank prefetch (or an earlier evaluator's
                # call) instead of paying its own forward
                ctx.ext[("signal_source", id(self))] = "fused_bank"
                return hit
        out = self.engine.classify(
            self.task, text, enc_cache=getattr(ctx, "enc_cache", None))
        if memo is not None:
            memo[key] = out
        ctx.ext[("signal_source", id(self))] = "engine"
        return out

    def _source(self, ctx: RequestContext) -> str:
        """Where this evaluation's classify result came from (set by
        _classify; "engine" when no classify ran — the family is still
        engine-backed)."""
        return ctx.ext.pop(("signal_source", id(self)), "engine")

    def evaluate(self, ctx: RequestContext) -> SignalResult:
        start = time.perf_counter()
        res = SignalResult(self.signal_type)
        try:
            if self.engine.has_task(self.task):
                self._evaluate(ctx, res)
            else:
                res.error = f"task {self.task!r} not loaded"
        except Exception as exc:
            res.error = f"{type(exc).__name__}: {exc}"
        res.latency_s = time.perf_counter() - start
        res.source = self._source(ctx)
        return res

    def _evaluate(self, ctx: RequestContext, res: SignalResult) -> None:
        raise NotImplementedError


class DomainSignal(_EngineSignal):
    """Maps the category classifier's label onto configured domain rules.
    The classifier's label set is the configured domain list (the reference
    trains the intent head on exactly these MMLU-style categories)."""

    signal_type = "domain"

    def __init__(self, engine: InferenceEngine, rules: List[DomainRule],
                 task: str = "intent", threshold: float = 0.0) -> None:
        super().__init__(engine, task)
        self.rules = rules
        self.threshold = threshold
        self._by_name = {r.name.lower(): r for r in rules}
        for r in rules:
            for cat in r.mmlu_categories:
                self._by_name.setdefault(cat.lower(), r)

    def _evaluate(self, ctx: RequestContext, res: SignalResult) -> None:
        out = self._classify(ctx, ctx.user_text)
        rule = self._by_name.get(out.label.lower())
        if rule is not None and out.confidence >= self.threshold:
            detail = {"label": out.label}
            if out.truncated:
                # the classifier never saw the input's tail — flag the
                # hit so downstream consumers can weigh it accordingly
                detail["truncated"] = True
            res.hits.append(SignalHit(rule.name, out.confidence, detail))


class JailbreakSignal(_EngineSignal):
    """method: classifier | pattern | hybrid. Pattern mode scores the text
    against jailbreak vs benign pattern sets lexically (the contrastive
    pattern path); hybrid ORs both."""

    signal_type = "jailbreak"

    def __init__(self, engine: InferenceEngine, rules: List[JailbreakRule],
                 task: str = "jailbreak",
                 positive_labels: Optional[List[str]] = None) -> None:
        super().__init__(engine, task)
        self.rules = rules
        self.positive = set(l.lower() for l in
                            (positive_labels or ["jailbreak", "injection",
                                                 "unsafe", "malicious"]))

    def evaluate(self, ctx: RequestContext) -> SignalResult:
        # pattern-only rules must work with no model loaded
        start = time.perf_counter()
        res = SignalResult(self.signal_type)
        try:
            self._evaluate(ctx, res)
        except Exception as exc:
            res.error = f"{type(exc).__name__}: {exc}"
        res.latency_s = time.perf_counter() - start
        res.source = self._source(ctx)
        return res

    # guard safety levels → jailbreak scores (Unsafe blocks outright;
    # Controversial lands at typical rule thresholds, qwen3_guard.rs role)
    GUARD_SCORES = {"Unsafe": 0.95, "Controversial": 0.6, "Safe": 0.0}

    def _classifier_score(self, ctx: RequestContext, text: str) -> float:
        if not self.engine.has_task(self.task):
            return 0.0
        if self.engine.task_kind(self.task) == "generative":
            # Qwen3Guard-style generative safety classifier: structured
            # generation + parse instead of a softmax head
            verdict = self.engine.guard_classify(self.task, text)
            return self.GUARD_SCORES.get(verdict.safety, 0.6)
        out = self._classify(ctx, text)
        if out.label.lower() in self.positive:
            return out.confidence
        # positive-class probability even when benign wins
        return max((p for l, p in out.probs.items()
                    if l.lower() in self.positive), default=0.0)

    @staticmethod
    def _pattern_score(text: str, rule: JailbreakRule) -> float:
        """Contrastive lexical score: fraction of jailbreak patterns present
        minus fraction of benign patterns present, clamped to [0, 1]."""
        t = text.lower()
        if not rule.jailbreak_patterns:
            return 0.0
        jb = sum(1 for p in rule.jailbreak_patterns if p.lower() in t)
        if jb == 0:
            return 0.0
        benign = sum(1 for p in rule.benign_patterns if p.lower() in t)
        score = 0.5 + 0.5 * jb / len(rule.jailbreak_patterns)
        if rule.benign_patterns:
            score -= 0.4 * benign / len(rule.benign_patterns)
        return max(0.0, min(1.0, score))

    def _evaluate(self, ctx: RequestContext, res: SignalResult) -> None:
        cls_cache: Dict[str, float] = {}
        for rule in self.rules:
            text = ctx.text_for(rule.include_history)
            score = 0.0
            if rule.method in ("classifier", "hybrid"):
                if not self.engine.has_task(self.task):
                    # surface the disabled guard (pattern leg may still run)
                    res.error = f"task {self.task!r} not loaded"
                elif text not in cls_cache:
                    cls_cache[text] = self._classifier_score(ctx, text)
                score = cls_cache.get(text, 0.0)
            if rule.method in ("pattern", "hybrid"):
                score = max(score, self._pattern_score(text, rule))
            if score >= rule.threshold:
                res.hits.append(SignalHit(rule.name, score))


class PIISignal(_EngineSignal):
    """Token-classifies the text and matches rules whose *disallowed* PII
    types are present (pii_types_allowed is the allowlist)."""

    signal_type = "pii"

    def __init__(self, engine: InferenceEngine, rules: List[PIIRule],
                 task: str = "pii") -> None:
        super().__init__(engine, task)
        self.rules = rules
        # an item carries ONE threshold: the first rule's that reads
        # ctx.user_text.  A second threshold and include_history rules
        # (another text) keep their own token_classify call.
        own = [r.threshold for r in rules if not r.include_history]
        if own:
            self.prefetch_threshold = own[0]
        else:
            self.prefetch_task = ""

    def _token_classify(self, ctx: RequestContext, text: str,
                        threshold: float):
        """The dispatcher-seeded memo first (the request's fused item
        already paid the forward; keyed by the threshold too, which
        decides the spans), else a token_classify call of its own."""
        memo = getattr(ctx, "class_memo", None)
        source = ("signal_source", id(self))
        if memo is not None:
            hit = memo.get((id(self.engine), self.task, text, threshold))
            if hit is not None:
                ctx.ext[source] = "fused_bank"
                return hit
        ctx.ext.setdefault(source, "engine")
        return self.engine.token_classify(
            self.task, text, threshold=threshold,
            enc_cache=getattr(ctx, "enc_cache", None))

    def _evaluate(self, ctx: RequestContext, res: SignalResult) -> None:
        cache: Dict[tuple, list] = {}
        for rule in self.rules:
            key = (rule.include_history, rule.threshold)
            if key not in cache:
                cache[key] = self._token_classify(
                    ctx, ctx.text_for(rule.include_history),
                    rule.threshold).entities
            entities = cache[key]
            allowed = {t.upper() for t in rule.pii_types_allowed}
            denied = [e for e in entities if e.type.upper() not in allowed]
            if denied:
                res.hits.append(SignalHit(
                    rule.name,
                    min(e.score for e in denied),
                    {"types": sorted({e.type for e in denied}),
                     "entities": [
                         {"type": e.type, "start": e.start, "end": e.end,
                          "score": e.score} for e in denied]},
                ))


class BinaryTaskSignal(_EngineSignal):
    """Generic classifier-label → rule-name mapper for fact_check,
    user_feedback, and modality: a rule matches when the classifier emits
    its name (label set == rule names by construction/training)."""

    def __init__(self, engine: InferenceEngine, rules: List[NamedRule],
                 task: str, signal_type: str) -> None:
        super().__init__(engine, task)
        self.signal_type = signal_type
        self.rules = rules
        self._names = {r.name.lower(): r for r in rules}

    def _evaluate(self, ctx: RequestContext, res: SignalResult) -> None:
        out = self._classify(ctx, ctx.user_text)
        rule = self._names.get(out.label.lower())
        if rule is not None:
            threshold = rule.threshold or 0.0
            if out.confidence >= threshold:
                res.hits.append(SignalHit(rule.name, out.confidence))


def build_learned_evaluators(engine: InferenceEngine, cfg) -> list:
    """Wire every learned family whose rules are configured. Task names
    follow the engine's default registry: intent/jailbreak/pii/fact_check/
    user_feedback/modality/embedding."""
    from .embedding_signal import (
        ComplexitySignal,
        EmbeddingSignal,
        PreferenceSignal,
    )

    evs: list = []
    s = cfg.signals
    if s.domains:
        evs.append(DomainSignal(engine, s.domains))
    if s.jailbreak:
        evs.append(JailbreakSignal(engine, s.jailbreak))
    if s.pii:
        evs.append(PIISignal(engine, s.pii))
    if s.fact_check:
        evs.append(BinaryTaskSignal(engine, s.fact_check, "fact_check",
                                    "fact_check"))
    if s.user_feedbacks:
        evs.append(BinaryTaskSignal(engine, s.user_feedbacks, "user_feedback",
                                    "user_feedback"))
    if s.modality:
        evs.append(BinaryTaskSignal(engine, s.modality, "modality",
                                    "modality"))
    if s.kb and getattr(cfg, "knowledge_bases", None):
        from .kb import KBSignal

        evs.append(KBSignal(engine, s.kb, cfg.knowledge_bases))
    if s.embeddings:
        # image-modality rules route through the engine's multimodal
        # (SigLIP shared-space) task when one is registered
        mm = next((t for t in engine.tasks()
                   if engine.task_kind(t) == "multimodal"), "multimodal")
        evs.append(EmbeddingSignal(engine, s.embeddings,
                                   multimodal_task=mm))
    if s.preferences:
        evs.append(PreferenceSignal(engine, s.preferences))
    if s.complexity:
        evs.append(ComplexitySignal(engine, s.complexity))
    return evs
