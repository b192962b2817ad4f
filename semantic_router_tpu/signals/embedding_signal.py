"""Embedding-similarity signal family + preference + complexity prototypes.

Reference parity:
- embedding rules → embedding_classifier*.go: rule candidates embedded once,
  query embedded per request, cosine aggregation max|any|mean vs threshold
  (GetEmbeddingBatched semantic-router.go:808 feeding the batch scheduler).
- preference rules → contrastive_preference_classifier.go: example-set
  similarity.
- complexity → complexity_classifier.go + prototype_bank.go: hard/easy
  prototype banks; the margin decides hard/medium/easy; an optional
  ``composer`` boolean expression over other signals can force-escalate
  (evaluated post-dispatch by the dispatcher since it references sibling
  families).

Candidate embeddings are computed lazily on first use and cached per rule —
the prototype bank. Cosine scores are plain numpy dots on L2-normalized
vectors (a [n_cand, dim] @ [dim] matmul — the N16 SIMD kernels' role).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..config.schema import ComplexityRule, EmbeddingRule, PreferenceRule
from ..engine.classify import InferenceEngine
from .base import RequestContext, SignalHit, SignalResult


class _PrototypeBank:
    """Lazy per-rule candidate-embedding cache (prototype_bank.go)."""

    def __init__(self, engine: InferenceEngine, task: str) -> None:
        self.engine = engine
        self.task = task
        self._cache: Dict[str, np.ndarray] = {}
        self._lock = threading.Lock()

    def get(self, key: str, texts: List[str],
            embed_fn=None) -> np.ndarray:
        """Get-or-create candidate embeddings.  ``embed_fn`` overrides
        the embedder (the image-modality rules embed their candidate
        texts through the multimodal SHARED space, not the text-only
        model) — the lock/check/embed/store sequence stays in ONE
        place either way."""
        with self._lock:
            hit = self._cache.get(key)
        if hit is not None:
            return hit
        emb = embed_fn(texts) if embed_fn is not None \
            else self.engine.embed(self.task, texts)
        with self._lock:
            self._cache[key] = emb
        return emb

    def embed_query(self, text: str,
                    ctx: Optional[RequestContext] = None) -> np.ndarray:
        """Embed the query, memoized per request so the embedding /
        preference / complexity families share one forward pass."""
        if ctx is None:
            return self.engine.embed(self.task, [text])[0]
        key = ("query_emb", self.task, text)
        # the families evaluate on concurrent threads: without the lock
        # all of them miss the memo at once and each pays a forward (at
        # 32K tokens, three of them, batched into a shape no warmup
        # compiled).  dict.setdefault is atomic, so one lock per request.
        with ctx.ext.setdefault(("query_emb_lock", self.task),
                                threading.Lock()):
            if key not in ctx.ext:
                ctx.ext[key] = self.engine.embed(self.task, [text])[0]
            return ctx.ext[key]


def _aggregate(sims: np.ndarray, method: str, threshold: float
               ) -> tuple[bool, float]:
    if sims.size == 0:
        return False, 0.0
    if method == "mean":
        score = float(sims.mean())
        return score >= threshold, score
    if method == "any":
        matched = bool((sims >= threshold).any())
        return matched, float(sims.max())
    # max (default)
    score = float(sims.max())
    return score >= threshold, score


class EmbeddingSignal:
    """Similarity routing over candidate prototypes.

    Text rules embed the query text with the ``task`` embedding model.
    Rules with ``query_modality: image`` (reference multimodal-routing
    e2e profile; EmbeddingRule schema.py query_modality) embed the
    request's FIRST image through the ``multimodal_task`` shared text/
    image space (SigLIP, N5) and score it against the rule's candidate
    TEXTS embedded in that same space — a picture of an invoice matches
    the "billing documents" prototypes with no caption needed."""

    signal_type = "embedding"

    def __init__(self, engine: InferenceEngine, rules: List[EmbeddingRule],
                 task: str = "embedding",
                 multimodal_task: str = "multimodal") -> None:
        self.rules = rules
        self.bank = _PrototypeBank(engine, task)
        self.engine = engine
        self.task = task
        self.multimodal_task = multimodal_task

    def _image_query(self, ctx: RequestContext) -> np.ndarray:
        """First request image → shared-space embedding, memoized per
        request (several image rules share one forward pass)."""
        key = ("query_img_emb", self.multimodal_task)
        if key in ctx.ext:
            return ctx.ext[key]
        ref = next(ref for m in ctx.messages for ref in m.images)
        emb = self.engine.embed_multimodal(
            self.multimodal_task, image_refs=[ref])["image"][0]
        ctx.ext[key] = emb
        return emb

    def _mm_candidates(self, rule: EmbeddingRule) -> np.ndarray:
        """Candidate texts embedded in the SHARED space (mm text tower,
        not the text-only embedding model), cached in the bank."""
        return self.bank.get(
            f"mm_cands:{rule.name}", rule.candidates,
            embed_fn=lambda texts: self.engine.embed_multimodal(
                self.multimodal_task, texts=texts)["text"])

    def evaluate(self, ctx: RequestContext) -> SignalResult:
        start = time.perf_counter()
        res = SignalResult(self.signal_type)
        text_rules = [r for r in self.rules
                      if r.query_modality != "image"]
        image_rules = [r for r in self.rules
                       if r.query_modality == "image"]
        # the two modality branches fail INDEPENDENTLY: a malformed
        # image must not void the text rules' hits (and vice versa) —
        # fail-open stays per-branch, not per-family
        try:
            if text_rules:
                if not self.engine.has_task(self.task):
                    res.error = f"task {self.task!r} not loaded"
                else:
                    query = self.bank.embed_query(ctx.user_text, ctx)
                    for rule in text_rules:
                        if not rule.candidates:
                            continue
                        cands = self.bank.get(f"emb:{rule.name}",
                                              rule.candidates)
                        sims = cands @ query
                        matched, score = _aggregate(
                            sims, rule.aggregation_method, rule.threshold)
                        if matched:
                            res.hits.append(SignalHit(rule.name, score))
        except Exception as exc:
            res.error = f"{type(exc).__name__}: {exc}"
        try:
            if image_rules and ctx.has_images():
                if not self.engine.has_task(self.multimodal_task):
                    res.error = (f"task {self.multimodal_task!r} "
                                 f"not loaded")
                else:
                    img_q = self._image_query(ctx)
                    for rule in image_rules:
                        if not rule.candidates:
                            continue
                        sims = self._mm_candidates(rule) @ img_q
                        matched, score = _aggregate(
                            sims, rule.aggregation_method, rule.threshold)
                        if matched:
                            res.hits.append(SignalHit(
                                rule.name, score,
                                {"modality": "image"}))
        except Exception as exc:
            res.error = f"image: {type(exc).__name__}: {exc}"
        res.latency_s = time.perf_counter() - start
        return res


class PreferenceSignal:
    signal_type = "preference"

    def __init__(self, engine: InferenceEngine, rules: List[PreferenceRule],
                 task: str = "embedding") -> None:
        self.rules = rules
        self.bank = _PrototypeBank(engine, task)
        self.engine = engine
        self.task = task

    def evaluate(self, ctx: RequestContext) -> SignalResult:
        start = time.perf_counter()
        res = SignalResult(self.signal_type)
        try:
            if not self.engine.has_task(self.task):
                res.error = f"task {self.task!r} not loaded"
                return res
            query = self.bank.embed_query(ctx.user_text, ctx)
            for rule in self.rules:
                if not rule.examples:
                    continue
                ex = self.bank.get(f"pref:{rule.name}", rule.examples)
                score = float((ex @ query).max())
                if score >= rule.threshold:
                    res.hits.append(SignalHit(rule.name, score))
        except Exception as exc:
            res.error = f"{type(exc).__name__}: {exc}"
        finally:
            res.latency_s = time.perf_counter() - start
        return res


class ComplexitySignal:
    """Prototype-margin difficulty scoring. Reports "rule:level" hits
    (hard/medium/easy) the decision engine matches by exact or bare name."""

    signal_type = "complexity"
    MARGIN = 0.05

    def __init__(self, engine: InferenceEngine, rules: List[ComplexityRule],
                 task: str = "embedding") -> None:
        self.rules = rules
        self.bank = _PrototypeBank(engine, task)
        self.engine = engine
        self.task = task

    def evaluate(self, ctx: RequestContext) -> SignalResult:
        start = time.perf_counter()
        res = SignalResult(self.signal_type)
        try:
            if not self.engine.has_task(self.task):
                res.error = f"task {self.task!r} not loaded"
                return res
            query = self.bank.embed_query(ctx.user_text, ctx)
            for rule in self.rules:
                level, conf = self._level(rule, query, ctx)
                if level is not None:
                    res.hits.append(SignalHit(f"{rule.name}:{level}", conf))
        except Exception as exc:
            res.error = f"{type(exc).__name__}: {exc}"
        finally:
            res.latency_s = time.perf_counter() - start
        return res

    def _level(self, rule: ComplexityRule, query: np.ndarray,
               ctx: RequestContext) -> tuple[Optional[str], float]:
        hard_c = list(rule.hard_candidates)
        easy_c = list(rule.easy_candidates)
        variant = "txt"
        if ctx.has_images():
            hard_c += rule.hard_image_candidates
            easy_c += rule.easy_image_candidates
            variant = "img"  # distinct cache slot per candidate-set variant
        sim_hard = sim_easy = 0.0
        if hard_c:
            bank = self.bank.get(f"cx:{rule.name}:hard:{variant}", hard_c)
            sim_hard = float((bank @ query).max())
        if easy_c:
            bank = self.bank.get(f"cx:{rule.name}:easy:{variant}", easy_c)
            sim_easy = float((bank @ query).max())
        margin = sim_hard - sim_easy
        if sim_hard >= rule.threshold and margin > self.MARGIN:
            return "hard", sim_hard
        if sim_easy >= rule.threshold and margin < -self.MARGIN:
            return "easy", sim_easy
        if max(sim_hard, sim_easy) >= rule.threshold * 0.5:
            return "medium", max(sim_hard, sim_easy)
        return None, 0.0
