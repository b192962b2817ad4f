"""Keyword signal: exact / regex / fuzzy / BM25 / n-gram scorers.

Capability parity with the reference's keyword family
(pkg/classification/keyword_classifier.go for exact/regex/fuzzy and
nlp-binding/src/{bm25_classifier,ngram_classifier}.rs for the learned-free
lexical scorers, selected by ``method`` in config — config/config.yaml:135-160).

The scorers are pure Python with pre-compiled per-rule state; when the native
C++ lexical library is present (semantic_router_tpu.native), BM25/ngram
scoring transparently dispatches to it.
"""

from __future__ import annotations

import math
import re
import time
import unicodedata
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ..config.schema import KeywordRule
from .base import RequestContext, SignalHit, SignalResult

_TOKEN_RE = re.compile(r"\w+", re.UNICODE)


def _native():
    """The C++ lexical library when built (semantic_router_tpu.native) —
    the N15/N16 native path; None → pure-Python fallback (the CGo-free
    seam, SURVEY.md §4)."""
    try:
        from .. import native as native_mod

        return native_mod if native_mod.available() else None
    except Exception:
        return None


def tokenize(text: str, lower: bool = True) -> List[str]:
    if lower:
        text = text.lower()
    return _TOKEN_RE.findall(text)


def _norm(text: str, case_sensitive: bool) -> str:
    text = unicodedata.normalize("NFKC", text)
    return text if case_sensitive else text.lower()


def _lcs_ratio_py(a: str, b: str) -> float:
    """2·LCS/(|a|+|b|) percent — the indel ratio (rapidfuzz `ratio` family,
    which is what the reference's fuzzy matching uses). Pure-Python
    fallback; the native kernel computes the identical metric."""
    la, lb = len(a), len(b)
    if la == 0 and lb == 0:
        return 100.0
    if la == 0 or lb == 0:
        return 0.0
    prev = [0] * (lb + 1)
    for i in range(1, la + 1):
        cur = [0] * (lb + 1)
        ca = a[i - 1]
        for j in range(1, lb + 1):
            if ca == b[j - 1]:
                cur[j] = prev[j - 1] + 1
            else:
                cur[j] = prev[j] if prev[j] >= cur[j - 1] else cur[j - 1]
        prev = cur
    return 200.0 * prev[lb] / (la + lb)


def fuzzy_ratio(a: str, b: str) -> float:
    """Similarity percent in [0,100]: LCS-indel ratio. The native kernel
    and the Python fallback compute the SAME metric, so fuzzy thresholds
    route identically whether or not _lexical.so is built."""
    if a.isascii() and b.isascii():
        native = _native()
        if native is not None:
            return native.fuzzy_ratio(a, b)
    return _lcs_ratio_py(a, b)


def fuzzy_partial_ratio(needle: str, haystack: str,
                        floor: float = 0.0) -> float:
    """Best fuzzy match of *needle* against any equal-length window of
    *haystack* (cheap partial-ratio: slide by whole tokens).  A caller
    that only acts on scores of at least ``floor`` says so: such a score
    is returned as it is, a lower one may come back lower still (windows
    that cannot reach ``floor`` are not scored)."""
    if not needle or not haystack:
        return 0.0
    if needle in haystack:
        return 100.0
    n = len(needle)
    if len(haystack) <= n:
        return fuzzy_ratio(needle, haystack)
    # Candidate windows anchored at word boundaries (plus a coarse stride as
    # fallback) — catches "credit-card" for needle "credit card" without an
    # O(n*m) full slide.
    starts = {0}
    for m in re.finditer(r"\S+", haystack):
        starts.add(m.start())
    starts.update(range(0, len(haystack) - n + 1, max(1, n // 2)))
    order = np.asarray(sorted(i for i in starts if i + 1 < len(haystack)),
                       dtype=np.int64)
    bounds = _ratio_bounds(needle, haystack, order)
    best = 0.0
    for i, bound in zip(order.tolist(), bounds.tolist()):
        # a window whose bound passes neither the floor nor the best so
        # far cannot change the answer: only windows that share most of
        # the needle's letters pay the quadratic ratio (in a 5,000-word
        # prompt, a handful of seven thousand)
        if bound < floor or bound <= best:
            continue
        best = max(best, fuzzy_ratio(needle, haystack[i:i + n]))
        if best >= 99.9:
            break
    return best


def _ratio_bounds(needle: str, haystack: str, starts: np.ndarray
                  ) -> np.ndarray:
    """For each window ``haystack[i:i + len(needle)]``, a value its
    fuzzy_ratio cannot pass: a common subsequence holds each character at
    most as often as either string does, so LCS <= sum over the needle's
    characters of min(count in needle, count in window).  Counts of all
    windows at once from running sums over the haystack's code points."""
    n = len(needle)
    text = np.frombuffer(haystack.encode("utf-32-le", "surrogatepass"),
                         dtype=np.uint32)
    ends = np.minimum(starts + n, len(text))
    common = np.zeros(len(starts), np.int64)
    for ch in set(needle):
        running = np.concatenate(([0], np.cumsum(text == ord(ch))))
        common += np.minimum(running[ends] - running[starts],
                             needle.count(ch))
    # fuzzy_ratio's own expression, so that a window whose subsequence
    # reaches the bound reads the same float
    return 200.0 * common / (n + ends - starts)


class BM25Scorer:
    """BM25 keyword-set scorer (nlp-binding/src/bm25_classifier.rs).

    The rule's keywords act as the "query"; the request text is the single
    document scored against a background corpus statistic. With no corpus at
    config time we use the standard BM25 saturation form with neutral IDF
    weights — the effective behavior (score grows with keyword term frequency,
    saturates with k1, normalizes by document length) matches the reference's
    lexical scorer; thresholds are config-tuned the same way.
    """

    def __init__(self, keywords: Sequence[str], k1: float = 1.5, b: float = 0.75,
                 case_sensitive: bool = False) -> None:
        self.k1 = k1
        self.b = b
        self.case_sensitive = case_sensitive
        self.keywords = list(keywords)
        self.keyword_tokens: List[List[str]] = [
            tokenize(k, lower=not case_sensitive) for k in keywords
        ]
        self.avgdl = 64.0  # neutral prior average doc length (tokens)

    def score(self, text: str) -> Tuple[float, List[str]]:
        # Native dispatch only where its byte-level tokenizer agrees with
        # the Unicode-aware Python oracle: ASCII text + non-empty keywords.
        if not self.case_sensitive and text.isascii() \
                and all(k and k.isascii() for k in self.keywords):
            native = _native()
            if native is not None:
                s, idx = native.bm25_score(text, self.keywords,
                                           self.k1, self.b, self.avgdl)
                return s, [self.keywords[i] for i in idx]
        return self._score_py(text)

    def _score_py(self, text: str) -> Tuple[float, List[str]]:
        doc = tokenize(text, lower=not self.case_sensitive)
        if not doc:
            return 0.0, []
        tf: Dict[str, int] = {}
        for t in doc:
            tf[t] = tf.get(t, 0) + 1
        dl = len(doc)
        norm = self.k1 * (1.0 - self.b + self.b * dl / self.avgdl)
        total, matched = 0.0, []
        for kw_tokens in self.keyword_tokens:
            if not kw_tokens:
                continue
            # phrase keywords score as the min over their tokens (all must appear)
            per_tok = []
            for t in kw_tokens:
                f = tf.get(t, 0)
                per_tok.append((f * (self.k1 + 1.0)) / (f + norm) if f else 0.0)
            kw_score = min(per_tok)
            if kw_score > 0.0:
                matched.append(" ".join(kw_tokens))
            total += kw_score
        # normalize to [0,1]-ish per keyword count so thresholds are stable
        return total / max(len(self.keyword_tokens), 1), matched


class NGramScorer:
    """Character n-gram containment scorer (nlp-binding/src/ngram_classifier.rs):
    fraction of each keyword's n-grams present in the text; robust to small
    typos and inflections."""

    def __init__(self, keywords: Sequence[str], arity: int = 3,
                 case_sensitive: bool = False) -> None:
        self.arity = max(1, arity)
        self.case_sensitive = case_sensitive
        self.keyword_grams: List[Tuple[str, frozenset]] = []
        for k in keywords:
            kn = _norm(k, case_sensitive)
            self.keyword_grams.append((k, frozenset(self._grams(kn))))

    def _grams(self, s: str) -> List[str]:
        s = f" {s} "
        n = self.arity
        if len(s) < n:
            return [s]
        return [s[i:i + n] for i in range(len(s) - n + 1)]

    def score(self, text: str) -> Tuple[float, List[str]]:
        tn = _norm(text, self.case_sensitive)
        text_grams = set(self._grams(tn))
        best, matched = 0.0, []
        for kw, grams in self.keyword_grams:
            if not grams:
                continue
            containment = len(grams & text_grams) / len(grams)
            if containment > best:
                best = containment
            matched.append((kw, containment))
        return best, [kw for kw, c in matched if c >= best and best > 0.0]


@dataclass
class _CompiledRule:
    rule: KeywordRule
    regexes: List[re.Pattern]
    bm25: BM25Scorer | None
    ngram: NGramScorer | None


class KeywordSignal:
    signal_type = "keyword"

    def __init__(self, rules: List[KeywordRule]) -> None:
        self.compiled: List[_CompiledRule] = []
        for r in rules:
            regexes: List[re.Pattern] = []
            if r.method == "regex":
                flags = 0 if r.case_sensitive else re.IGNORECASE
                regexes = [re.compile(k, flags) for k in r.keywords]
            bm25 = BM25Scorer(r.keywords, case_sensitive=r.case_sensitive) \
                if r.method == "bm25" else None
            ngram = NGramScorer(r.keywords, arity=r.ngram_arity,
                                case_sensitive=r.case_sensitive) \
                if r.method == "ngram" else None
            self.compiled.append(_CompiledRule(r, regexes, bm25, ngram))

    def evaluate(self, ctx: RequestContext) -> SignalResult:
        start = time.perf_counter()
        res = SignalResult(signal_type=self.signal_type)
        text = ctx.user_text
        for c in self.compiled:
            hit = self._eval_rule(c, text)
            if hit is not None:
                res.hits.append(hit)
        res.latency_s = time.perf_counter() - start
        return res

    def _eval_rule(self, c: _CompiledRule, text: str) -> SignalHit | None:
        r = c.rule
        if r.method == "bm25":
            score, matched = c.bm25.score(text)  # type: ignore[union-attr]
            if score >= r.bm25_threshold:
                conf = min(1.0, score / max(r.bm25_threshold * 4.0, 1e-9))
                return SignalHit(r.name, conf, {"keywords": matched,
                                                "score": score})
            return None
        if r.method == "ngram":
            score, matched = c.ngram.score(text)  # type: ignore[union-attr]
            if score >= r.ngram_threshold:
                return SignalHit(r.name, min(1.0, score),
                                 {"keywords": matched, "score": score})
            return None
        if r.method == "regex":
            matched = []
            for pat in c.regexes:
                m = pat.search(text)
                if m:
                    matched.append(m.group(0))
            ok = (len(matched) == len(c.regexes)) if r.operator == "AND" \
                else bool(matched)
            return SignalHit(r.name, 1.0, {"keywords": matched}) if ok else None
        if r.method == "fuzzy" or r.fuzzy_match:
            tn = _norm(text, r.case_sensitive)
            matched, scores = [], []
            for kw in r.keywords:
                kn = _norm(kw, r.case_sensitive)
                s = fuzzy_partial_ratio(kn, tn, floor=r.fuzzy_threshold)
                if s >= r.fuzzy_threshold:
                    matched.append(kw)
                    scores.append(s)
            ok = (len(matched) == len(r.keywords)) if r.operator == "AND" \
                else bool(matched)
            if not ok:
                return None
            conf = min(1.0, (sum(scores) / len(scores)) / 100.0)
            return SignalHit(r.name, conf, {"keywords": matched})
        # exact substring
        tn = _norm(text, r.case_sensitive)
        matched = [kw for kw in r.keywords if _norm(kw, r.case_sensitive) in tn]
        ok = (len(matched) == len(r.keywords)) if r.operator == "AND" \
            else bool(matched)
        return SignalHit(r.name, 1.0, {"keywords": matched}) if ok else None
