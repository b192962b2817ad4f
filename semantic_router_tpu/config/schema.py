"""Typed configuration model for the router.

Capability parity with the reference's ``pkg/config`` (RouterConfig,
reference: src/semantic-router/pkg/config/config.go:60-100 and the signal
taxonomy at config.go:25-43) re-designed as Python dataclasses. The YAML
surface mirrors the reference's ``config/config.yaml`` layout (``routing:``
with ``modelCards``/``signals``/``projections``/``decisions``) so existing
configs translate mechanically.

Only the hot, structurally-validated parts get dedicated dataclasses
(signals, projections, decisions, model refs); long-tail plugin payloads
stay as open dicts validated by their consumers.
"""

from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# --------------------------------------------------------------------------
# Signal taxonomy (reference: pkg/config/config.go:25-43)
# --------------------------------------------------------------------------

SIGNAL_KEYWORD = "keyword"
SIGNAL_EMBEDDING = "embedding"
SIGNAL_DOMAIN = "domain"
SIGNAL_FACT_CHECK = "fact_check"
SIGNAL_USER_FEEDBACK = "user_feedback"
SIGNAL_REASK = "reask"
SIGNAL_PREFERENCE = "preference"
SIGNAL_LANGUAGE = "language"
SIGNAL_CONTEXT = "context"
SIGNAL_STRUCTURE = "structure"
SIGNAL_COMPLEXITY = "complexity"
SIGNAL_MODALITY = "modality"
SIGNAL_AUTHZ = "authz"
SIGNAL_JAILBREAK = "jailbreak"
SIGNAL_PII = "pii"
SIGNAL_KB = "kb"
SIGNAL_CONVERSATION = "conversation"
SIGNAL_EVENT = "event"
SIGNAL_PROJECTION = "projection"

ALL_SIGNAL_TYPES = (
    SIGNAL_KEYWORD,
    SIGNAL_EMBEDDING,
    SIGNAL_DOMAIN,
    SIGNAL_FACT_CHECK,
    SIGNAL_USER_FEEDBACK,
    SIGNAL_REASK,
    SIGNAL_PREFERENCE,
    SIGNAL_LANGUAGE,
    SIGNAL_CONTEXT,
    SIGNAL_STRUCTURE,
    SIGNAL_COMPLEXITY,
    SIGNAL_MODALITY,
    SIGNAL_AUTHZ,
    SIGNAL_JAILBREAK,
    SIGNAL_PII,
    SIGNAL_KB,
    SIGNAL_CONVERSATION,
    SIGNAL_EVENT,
    SIGNAL_PROJECTION,
)


def _take(d: Dict[str, Any], *names: str, default: Any = None) -> Any:
    for n in names:
        if n in d:
            return d[n]
    return default


# --------------------------------------------------------------------------
# Signal rule configs
# --------------------------------------------------------------------------


@dataclass
class KeywordRule:
    """Keyword signal rule (methods: exact substring, regex, fuzzy, bm25, ngram).

    Reference: routing.signals.keywords entries (config/config.yaml:135-160);
    scorer implementations in nlp-binding/src/{bm25,ngram}_classifier.rs and
    pkg/classification/keyword_classifier.go.
    """

    name: str
    keywords: List[str] = field(default_factory=list)
    operator: str = "OR"  # OR | AND
    method: str = "exact"  # exact | regex | fuzzy | bm25 | ngram
    case_sensitive: bool = False
    fuzzy_match: bool = False
    fuzzy_threshold: float = 80.0  # 0-100 similarity percent
    bm25_threshold: float = 0.1
    ngram_threshold: float = 0.4
    ngram_arity: int = 3
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KeywordRule":
        return cls(
            name=d["name"],
            keywords=list(d.get("keywords", [])),
            operator=str(d.get("operator", "OR")).upper(),
            method=d.get("method", "fuzzy" if d.get("fuzzy_match") else "exact"),
            case_sensitive=bool(d.get("case_sensitive", False)),
            fuzzy_match=bool(d.get("fuzzy_match", False)),
            fuzzy_threshold=float(d.get("fuzzy_threshold", 80.0)),
            bm25_threshold=float(d.get("bm25_threshold", 0.1)),
            ngram_threshold=float(d.get("ngram_threshold", 0.4)),
            ngram_arity=int(d.get("ngram_arity", 3)),
            description=d.get("description", ""),
        )


@dataclass
class EmbeddingRule:
    """Embedding-similarity signal rule (config/config.yaml:162-190)."""

    name: str
    candidates: List[str] = field(default_factory=list)
    threshold: float = 0.75
    aggregation_method: str = "max"  # max | any | mean
    query_modality: str = "text"  # text | image | audio
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "EmbeddingRule":
        return cls(
            name=d["name"],
            candidates=list(d.get("candidates", [])),
            threshold=float(d.get("threshold", 0.75)),
            aggregation_method=d.get("aggregation_method", "max"),
            query_modality=d.get("query_modality", "text"),
            description=d.get("description", ""),
        )


@dataclass
class ModelScore:
    model: str
    score: float = 0.0
    use_reasoning: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModelScore":
        return cls(
            model=d["model"],
            score=float(d.get("score", 0.0)),
            use_reasoning=bool(d.get("use_reasoning", False)),
        )


@dataclass
class DomainRule:
    """Domain/intent category (config/config.yaml:192-215; the learned
    category classifier maps prompts onto these)."""

    name: str
    description: str = ""
    mmlu_categories: List[str] = field(default_factory=list)
    model_scores: List[ModelScore] = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DomainRule":
        return cls(
            name=d["name"],
            description=d.get("description", ""),
            mmlu_categories=list(d.get("mmlu_categories", [])),
            model_scores=[ModelScore.from_dict(m) for m in d.get("model_scores", [])],
        )


@dataclass
class NamedRule:
    """Generic named signal class (fact_check, user_feedback, modality, ...)."""

    name: str
    description: str = ""
    threshold: float = 0.0
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "NamedRule":
        known = {"name", "description", "threshold"}
        return cls(
            name=d["name"],
            description=d.get("description", ""),
            threshold=float(d.get("threshold", 0.0)),
            extra={k: v for k, v in d.items() if k not in known},
        )


@dataclass
class ReaskRule:
    """History-aware dissatisfaction detection (repeated user turns)."""

    name: str
    threshold: float = 0.8
    lookback_turns: int = 1
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ReaskRule":
        return cls(
            name=d["name"],
            threshold=float(d.get("threshold", 0.8)),
            lookback_turns=int(d.get("lookback_turns", 1)),
            description=d.get("description", ""),
        )


@dataclass
class PreferenceRule:
    name: str
    examples: List[str] = field(default_factory=list)
    threshold: float = 0.7
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "PreferenceRule":
        return cls(
            name=d["name"],
            examples=list(d.get("examples", [])),
            threshold=float(d.get("threshold", 0.7)),
            description=d.get("description", ""),
        )


_TOKEN_SUFFIX = {"k": 1024, "m": 1024 * 1024}


def parse_token_count(v: Any) -> int:
    """Parse '32K' / '256K' / plain ints into token counts."""
    if v is None:
        return 0
    if isinstance(v, (int, float)):
        return int(v)
    s = str(v).strip().lower()
    if not s:
        return 0
    if s[-1] in _TOKEN_SUFFIX:
        return int(float(s[:-1]) * _TOKEN_SUFFIX[s[-1]])
    return int(float(s))


@dataclass
class ContextRule:
    """Token-length band rule (config/config.yaml:260-264)."""

    name: str
    min_tokens: int = 0
    max_tokens: int = 0  # 0 = unbounded
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ContextRule":
        return cls(
            name=d["name"],
            min_tokens=parse_token_count(d.get("min_tokens")),
            max_tokens=parse_token_count(d.get("max_tokens")),
            description=d.get("description", ""),
        )


@dataclass
class FeatureSource:
    """Where a structure/conversation feature is computed from."""

    type: str = "regex"  # regex | keyword_set | sequence | message | tool_definition | active_tool_loop
    pattern: str = ""
    keywords: List[str] = field(default_factory=list)
    sequences: List[List[str]] = field(default_factory=list)
    case_sensitive: bool = False
    role: str = ""  # for message source: user | assistant | developer | non_user

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FeatureSource":
        return cls(
            type=d.get("type", "regex"),
            pattern=d.get("pattern", ""),
            keywords=list(d.get("keywords", [])),
            sequences=[list(s) for s in d.get("sequences", [])],
            case_sensitive=bool(d.get("case_sensitive", False)),
            role=d.get("role", ""),
        )


@dataclass
class Predicate:
    """Numeric comparison bundle: any subset of gt/gte/lt/lte/eq."""

    gt: Optional[float] = None
    gte: Optional[float] = None
    lt: Optional[float] = None
    lte: Optional[float] = None
    eq: Optional[float] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "Predicate":
        d = d or {}
        fv = lambda k: (float(d[k]) if k in d and d[k] is not None else None)
        return cls(gt=fv("gt"), gte=fv("gte"), lt=fv("lt"), lte=fv("lte"), eq=fv("eq"))

    def check(self, value: float) -> bool:
        if self.gt is not None and not value > self.gt:
            return False
        if self.gte is not None and not value >= self.gte:
            return False
        if self.lt is not None and not value < self.lt:
            return False
        if self.lte is not None and not value <= self.lte:
            return False
        if self.eq is not None and value != self.eq:
            return False
        return True

    def is_empty(self) -> bool:
        return all(
            v is None for v in (self.gt, self.gte, self.lt, self.lte, self.eq)
        )


@dataclass
class StructureRule:
    """Prompt-structure feature rule (count/exists/sequence/density over a
    regex/keyword-set/sequence source). Reference:
    pkg/classification/structure_classifier.go and config.yaml:266-335."""

    name: str
    feature_type: str = "count"  # count | exists | sequence | density
    source: FeatureSource = field(default_factory=FeatureSource)
    predicate: Predicate = field(default_factory=Predicate)
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "StructureRule":
        feat = d.get("feature", {}) or {}
        return cls(
            name=d["name"],
            feature_type=feat.get("type", "count"),
            source=FeatureSource.from_dict(feat.get("source", {}) or {}),
            predicate=Predicate.from_dict(d.get("predicate")),
            description=d.get("description", ""),
        )


@dataclass
class ComplexityRule:
    """Learned complexity/difficulty rule with hard/easy prototype candidate
    sets and an optional composer sub-expression (config.yaml:337-365)."""

    name: str
    threshold: float = 0.75
    hard_candidates: List[str] = field(default_factory=list)
    easy_candidates: List[str] = field(default_factory=list)
    hard_image_candidates: List[str] = field(default_factory=list)
    easy_image_candidates: List[str] = field(default_factory=list)
    composer: Optional["RuleNode"] = None
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ComplexityRule":
        hard = d.get("hard", {}) or {}
        easy = d.get("easy", {}) or {}
        composer = d.get("composer")
        return cls(
            name=d["name"],
            threshold=float(d.get("threshold", 0.75)),
            hard_candidates=list(hard.get("candidates", [])),
            easy_candidates=list(easy.get("candidates", [])),
            hard_image_candidates=list(hard.get("image_candidates", [])),
            easy_image_candidates=list(easy.get("image_candidates", [])),
            composer=RuleNode.from_dict(composer) if composer else None,
            description=d.get("description", ""),
        )


@dataclass
class AuthzRule:
    """Role-binding rule: maps identity groups/users to a named role signal
    (routing.signals.role_bindings, config.yaml:380-397)."""

    name: str
    role: str = ""
    subjects: List[Dict[str, str]] = field(default_factory=list)  # {kind, name}
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "AuthzRule":
        return cls(
            name=d["name"],
            role=d.get("role", d["name"]),
            subjects=[dict(s) for s in d.get("subjects", [])],
            description=d.get("description", ""),
        )


@dataclass
class JailbreakRule:
    """Jailbreak detection rule (config.yaml:399-410): method is
    'classifier' (learned), 'pattern' (contrastive pattern match), or
    'hybrid' (both)."""

    name: str
    method: str = "classifier"
    threshold: float = 0.8
    include_history: bool = False
    jailbreak_patterns: List[str] = field(default_factory=list)
    benign_patterns: List[str] = field(default_factory=list)
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "JailbreakRule":
        return cls(
            name=d["name"],
            method=d.get("method", "classifier"),
            threshold=float(d.get("threshold", 0.8)),
            include_history=bool(d.get("include_history", False)),
            jailbreak_patterns=list(d.get("jailbreak_patterns", [])),
            benign_patterns=list(d.get("benign_patterns", [])),
            description=d.get("description", ""),
        )


@dataclass
class PIIRule:
    """PII policy rule: token-classifier detects entity types; rule matches
    when a *disallowed* type is present (config.yaml:412-419)."""

    name: str
    threshold: float = 0.85
    include_history: bool = False
    pii_types_allowed: List[str] = field(default_factory=list)
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "PIIRule":
        return cls(
            name=d["name"],
            threshold=float(d.get("threshold", 0.85)),
            include_history=bool(d.get("include_history", False)),
            pii_types_allowed=list(d.get("pii_types_allowed", [])),
            description=d.get("description", ""),
        )


@dataclass
class KBRule:
    name: str
    kb: str = ""
    target: Dict[str, str] = field(default_factory=dict)  # {kind, value}
    match: str = "best"
    # None = evaluator default; an explicit 0.0 means "unconditional"
    threshold: Optional[float] = None
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KBRule":
        return cls(
            name=d["name"],
            kb=d.get("kb", ""),
            target=dict(d.get("target", {}) or {}),
            match=d.get("match", "best"),
            threshold=None if d.get("threshold") is None
            else float(d["threshold"]),
            description=d.get("description", ""),
        )


@dataclass
class KnowledgeBaseDef:
    """Exemplar-based knowledge base (reference KnowledgeBaseConfig,
    category_kb_classifier.go): labels with exemplar texts, label groups,
    and derived metrics (best_score/best_matched_score built-in;
    group_margin configured) that feed kb_metric projection inputs."""

    name: str
    labels: Dict[str, List[str]] = field(default_factory=dict)  # label→exemplars
    groups: Dict[str, List[str]] = field(default_factory=dict)  # group→labels
    metrics: List[Dict[str, str]] = field(default_factory=list)
    # metric: {name, type: group_margin, positive_group, negative_group}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KnowledgeBaseDef":
        labels = {}
        for label, spec in (d.get("labels", {}) or {}).items():
            if isinstance(spec, dict):
                labels[label] = list(spec.get("exemplars", []) or [])
            else:
                labels[label] = list(spec or [])
        return cls(
            name=d["name"],
            labels=labels,
            groups={g: list(v or []) for g, v in
                    (d.get("groups", {}) or {}).items()},
            metrics=[dict(m) for m in (d.get("metrics", []) or [])],
        )


@dataclass
class ConversationRule:
    """Conversation-shape rule (message counts, tool defs, active tool loop)."""

    name: str
    feature_type: str = "count"
    source: FeatureSource = field(default_factory=FeatureSource)
    predicate: Predicate = field(default_factory=Predicate)
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ConversationRule":
        feat = d.get("feature", {}) or {}
        return cls(
            name=d["name"],
            feature_type=feat.get("type", "count"),
            source=FeatureSource.from_dict(feat.get("source", {}) or {}),
            predicate=Predicate.from_dict(d.get("predicate")),
            description=d.get("description", ""),
        )


@dataclass
class EventRule:
    name: str
    event_types: List[str] = field(default_factory=list)
    severities: List[str] = field(default_factory=list)
    action_codes: List[str] = field(default_factory=list)
    temporal: bool = False
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "EventRule":
        return cls(
            name=d["name"],
            event_types=list(d.get("event_types", [])),
            severities=list(d.get("severities", [])),
            action_codes=list(d.get("action_codes", [])),
            temporal=bool(d.get("temporal", False)),
            description=d.get("description", ""),
        )


@dataclass
class SignalsConfig:
    """All configured signal rules, by family."""

    keywords: List[KeywordRule] = field(default_factory=list)
    embeddings: List[EmbeddingRule] = field(default_factory=list)
    domains: List[DomainRule] = field(default_factory=list)
    fact_check: List[NamedRule] = field(default_factory=list)
    user_feedbacks: List[NamedRule] = field(default_factory=list)
    reasks: List[ReaskRule] = field(default_factory=list)
    preferences: List[PreferenceRule] = field(default_factory=list)
    language: List[NamedRule] = field(default_factory=list)
    context: List[ContextRule] = field(default_factory=list)
    structure: List[StructureRule] = field(default_factory=list)
    complexity: List[ComplexityRule] = field(default_factory=list)
    modality: List[NamedRule] = field(default_factory=list)
    role_bindings: List[AuthzRule] = field(default_factory=list)
    jailbreak: List[JailbreakRule] = field(default_factory=list)
    pii: List[PIIRule] = field(default_factory=list)
    kb: List[KBRule] = field(default_factory=list)
    conversation: List[ConversationRule] = field(default_factory=list)
    events: List[EventRule] = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SignalsConfig":
        d = d or {}
        return cls(
            keywords=[KeywordRule.from_dict(x) for x in d.get("keywords", [])],
            embeddings=[EmbeddingRule.from_dict(x) for x in d.get("embeddings", [])],
            domains=[DomainRule.from_dict(x) for x in d.get("domains", [])],
            fact_check=[NamedRule.from_dict(x) for x in d.get("fact_check", [])],
            user_feedbacks=[NamedRule.from_dict(x) for x in d.get("user_feedbacks", [])],
            reasks=[ReaskRule.from_dict(x) for x in d.get("reasks", [])],
            preferences=[PreferenceRule.from_dict(x) for x in d.get("preferences", [])],
            language=[NamedRule.from_dict(x) for x in d.get("language", [])],
            context=[ContextRule.from_dict(x) for x in d.get("context", [])],
            structure=[StructureRule.from_dict(x) for x in d.get("structure", [])],
            complexity=[ComplexityRule.from_dict(x) for x in d.get("complexity", [])],
            modality=[NamedRule.from_dict(x) for x in d.get("modality", [])],
            role_bindings=[AuthzRule.from_dict(x) for x in d.get("role_bindings", [])],
            jailbreak=[JailbreakRule.from_dict(x) for x in d.get("jailbreak", [])],
            pii=[PIIRule.from_dict(x) for x in d.get("pii", [])],
            kb=[KBRule.from_dict(x) for x in d.get("kb", [])],
            conversation=[ConversationRule.from_dict(x) for x in d.get("conversation", [])],
            events=[EventRule.from_dict(x) for x in d.get("events", [])],
        )

    def rule_names(self, signal_type: str) -> List[str]:
        """All configured rule names for a signal type (decision-engine leaf
        validation)."""
        family = {
            SIGNAL_KEYWORD: self.keywords,
            SIGNAL_EMBEDDING: self.embeddings,
            SIGNAL_DOMAIN: self.domains,
            SIGNAL_FACT_CHECK: self.fact_check,
            SIGNAL_USER_FEEDBACK: self.user_feedbacks,
            SIGNAL_REASK: self.reasks,
            SIGNAL_PREFERENCE: self.preferences,
            SIGNAL_LANGUAGE: self.language,
            SIGNAL_CONTEXT: self.context,
            SIGNAL_STRUCTURE: self.structure,
            SIGNAL_COMPLEXITY: self.complexity,
            SIGNAL_MODALITY: self.modality,
            SIGNAL_AUTHZ: self.role_bindings,
            SIGNAL_JAILBREAK: self.jailbreak,
            SIGNAL_PII: self.pii,
            SIGNAL_KB: self.kb,
            SIGNAL_CONVERSATION: self.conversation,
            SIGNAL_EVENT: self.events,
        }.get(signal_type, [])
        return [r.name for r in family]


# --------------------------------------------------------------------------
# Projections (reference: config.yaml:493-538, pkg/classification/classifier_projections.go)
# --------------------------------------------------------------------------


@dataclass
class ProjectionPartition:
    """Mutually-interacting signal group normalized into a distribution
    (softmax over member confidences with a temperature)."""

    name: str
    members: List[str] = field(default_factory=list)
    semantics: str = "exclusive"
    temperature: float = 1.0
    default: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ProjectionPartition":
        return cls(
            name=d["name"],
            members=list(d.get("members", [])),
            semantics=d.get("semantics", "exclusive"),
            temperature=float(d.get("temperature", 1.0)),
            default=d.get("default", ""),
        )


@dataclass
class ScoreInput:
    type: str = ""
    name: str = ""
    weight: float = 0.0
    value_source: str = "match"  # match | confidence | score
    match: float = 1.0
    miss: float = 0.0
    kb: str = ""
    metric: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ScoreInput":
        return cls(
            type=d.get("type", ""),
            name=d.get("name", ""),
            weight=float(d.get("weight", 0.0)),
            value_source=d.get("value_source", "match"),
            match=float(d.get("match", 1.0)),
            miss=float(d.get("miss", 0.0)),
            kb=d.get("kb", ""),
            metric=d.get("metric", ""),
        )


@dataclass
class ProjectionScore:
    name: str
    method: str = "weighted_sum"
    inputs: List[ScoreInput] = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ProjectionScore":
        return cls(
            name=d["name"],
            method=d.get("method", "weighted_sum"),
            inputs=[ScoreInput.from_dict(x) for x in d.get("inputs", [])],
        )


@dataclass
class MappingOutput:
    name: str
    predicate: Predicate = field(default_factory=Predicate)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "MappingOutput":
        return cls(name=d["name"], predicate=Predicate.from_dict(d))


@dataclass
class ProjectionMapping:
    """Score → derived routing-output band mapping."""

    name: str
    source: str = ""
    method: str = "threshold_bands"
    calibration: Dict[str, Any] = field(default_factory=dict)
    outputs: List[MappingOutput] = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ProjectionMapping":
        return cls(
            name=d["name"],
            source=d.get("source", ""),
            method=d.get("method", "threshold_bands"),
            calibration=dict(d.get("calibration", {}) or {}),
            outputs=[MappingOutput.from_dict(x) for x in d.get("outputs", [])],
        )


@dataclass
class ProjectionsConfig:
    partitions: List[ProjectionPartition] = field(default_factory=list)
    scores: List[ProjectionScore] = field(default_factory=list)
    mappings: List[ProjectionMapping] = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ProjectionsConfig":
        d = d or {}
        return cls(
            partitions=[ProjectionPartition.from_dict(x) for x in d.get("partitions", [])],
            scores=[ProjectionScore.from_dict(x) for x in d.get("scores", [])],
            mappings=[ProjectionMapping.from_dict(x) for x in d.get("mappings", [])],
        )


# --------------------------------------------------------------------------
# Decisions (reference: decision/engine.go, config.yaml:540+)
# --------------------------------------------------------------------------


@dataclass
class RuleNode:
    """Boolean expression tree node. Leaf: {type, name}. Composite:
    {operator: AND|OR|NOT, conditions: [...]}. Reference:
    pkg/decision/engine.go:160-200 (evalNode)."""

    operator: str = ""  # "" for leaf
    conditions: List["RuleNode"] = field(default_factory=list)
    signal_type: str = ""
    name: str = ""

    def is_leaf(self) -> bool:
        return self.operator == "" and self.signal_type != ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RuleNode":
        if not d:
            return cls()
        if "operator" in d and d.get("operator"):
            return cls(
                operator=str(d["operator"]).upper(),
                conditions=[cls.from_dict(c) for c in d.get("conditions", [])],
            )
        return cls(signal_type=d.get("type", ""), name=d.get("name", ""))

    def leaves(self) -> List["RuleNode"]:
        if self.is_leaf():
            return [self]
        out: List[RuleNode] = []
        for c in self.conditions:
            out.extend(c.leaves())
        return out


@dataclass
class ModelRef:
    """Candidate model for a decision, with reasoning controls and weight."""

    model: str
    weight: float = 1.0
    use_reasoning: bool = False
    reasoning_effort: str = ""
    reasoning_description: str = ""
    lora_name: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModelRef":
        return cls(
            model=d["model"],
            weight=float(d.get("weight", 1.0)),
            use_reasoning=bool(d.get("use_reasoning", False)),
            reasoning_effort=d.get("reasoning_effort", ""),
            reasoning_description=d.get("reasoning_description", ""),
            lora_name=d.get("lora_name", ""),
        )


@dataclass
class PluginConfig:
    type: str
    configuration: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "PluginConfig":
        return cls(type=d["type"], configuration=dict(d.get("configuration", {}) or {}))

    @property
    def enabled(self) -> bool:
        return bool(self.configuration.get("enabled", True))


@dataclass
class Decision:
    name: str
    rules: RuleNode = field(default_factory=RuleNode)
    priority: int = 0
    tier: int = 0
    description: str = ""
    model_refs: List[ModelRef] = field(default_factory=list)
    algorithm: Dict[str, Any] = field(default_factory=dict)  # {type: static|confidence|...}
    plugins: List[PluginConfig] = field(default_factory=list)
    output_contract: str = ""
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Decision":
        known = {
            "name", "rules", "priority", "tier", "description", "modelRefs",
            "model_refs", "algorithm", "plugins", "output_contract",
        }
        return cls(
            name=d["name"],
            rules=RuleNode.from_dict(d.get("rules", {}) or {}),
            priority=int(d.get("priority", 0)),
            tier=int(d.get("tier", 0)),
            description=d.get("description", ""),
            model_refs=[
                ModelRef.from_dict(m)
                for m in _take(d, "modelRefs", "model_refs", default=[])
            ],
            algorithm=dict(d.get("algorithm", {}) or {}),
            plugins=[PluginConfig.from_dict(p) for p in d.get("plugins", [])],
            output_contract=d.get("output_contract", ""),
            extra={k: v for k, v in d.items() if k not in known},
        )

    def plugin(self, ptype: str) -> Optional[PluginConfig]:
        for p in self.plugins:
            if p.type == ptype:
                return p
        return None


# --------------------------------------------------------------------------
# Model catalog / backends
# --------------------------------------------------------------------------


@dataclass
class LoRACard:
    name: str
    description: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "LoRACard":
        return cls(name=d["name"], description=d.get("description", ""))


@dataclass
class ModelCard:
    """Backend model card (routing.modelCards, config.yaml:99-133)."""

    name: str
    param_size: str = ""
    context_window_size: int = 0
    description: str = ""
    capabilities: List[str] = field(default_factory=list)
    quality_score: float = 0.0
    modality: str = "ar"  # ar | diffusion | omni
    tags: List[str] = field(default_factory=list)
    loras: List[LoRACard] = field(default_factory=list)
    pricing: Dict[str, float] = field(default_factory=dict)  # prompt/completion per 1M
    backend_refs: List[Dict[str, Any]] = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModelCard":
        return cls(
            name=d["name"],
            param_size=str(d.get("param_size", "")),
            context_window_size=parse_token_count(d.get("context_window_size", 0)),
            description=d.get("description", ""),
            capabilities=list(d.get("capabilities", [])),
            quality_score=float(d.get("quality_score", 0.0)),
            modality=d.get("modality", "ar"),
            tags=list(d.get("tags", [])),
            loras=[LoRACard.from_dict(x) for x in d.get("loras", [])],
            pricing=dict(d.get("pricing", {}) or {}),
            backend_refs=[dict(b) for b in d.get("backend_refs", [])],
        )

    def param_size_billions(self) -> float:
        s = self.param_size.strip().upper().rstrip("B")
        try:
            return float(s)
        except ValueError:
            return 0.0


# --------------------------------------------------------------------------
# Top-level config
# --------------------------------------------------------------------------


@dataclass
class SemanticCacheConfig:
    enabled: bool = False
    backend_type: str = "memory"  # memory | hnsw | hybrid
    similarity_threshold: float = 0.8
    max_entries: int = 1000
    ttl_seconds: int = 3600
    eviction_policy: str = "fifo"  # fifo | lru | lfu
    embedding_model: str = ""
    use_hnsw: bool = True
    backend_config: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SemanticCacheConfig":
        d = d or {}
        return cls(
            enabled=bool(d.get("enabled", False)),
            backend_type=d.get("backend_type", "memory"),
            similarity_threshold=float(d.get("similarity_threshold", 0.8)),
            max_entries=int(d.get("max_entries", 1000)),
            ttl_seconds=int(d.get("ttl_seconds", 3600)),
            eviction_policy=d.get("eviction_policy", "fifo"),
            embedding_model=d.get("embedding_model", ""),
            use_hnsw=bool(d.get("use_hnsw", True)),
            backend_config=dict(d.get("backend_config", {}) or {}),
        )


@dataclass
class InferenceEngineConfig:
    """TPU inference engine knobs — this framework's analog of the reference's
    candle/onnx device configuration plus the batching shim (N6) parameters
    (continuous_batch_scheduler.rs:124-250)."""

    max_batch_size: int = 32
    max_wait_ms: float = 2.0
    seq_len_buckets: List[int] = field(default_factory=lambda: [128, 512, 2048, 8192, 32768])
    dtype: str = "bfloat16"
    mesh_shape: Dict[str, int] = field(default_factory=dict)  # {"data": 4} etc.
    use_flash_attention: bool = True
    matryoshka_layers: List[int] = field(default_factory=list)
    matryoshka_dims: List[int] = field(default_factory=list)
    # concurrent batch-dispatch workers: a cold XLA compile of one
    # (task, bucket) shape must not park live traffic on warm shapes
    dispatch_workers: int = 4
    # fused classifier bank: sequence tasks registered with the same trunk
    # weights + tokenizer batch as ONE (trunk, bucket) group — a request
    # fanning out K learned signals pays 1 trunk forward instead of K.
    # Per-task opt-out via register_task(..., fuse=False) for tasks whose
    # max_seq_len / tokenizer must diverge from their trunk siblings.
    fuse_trunks: bool = True
    # sequence-packed continuous batching (docs/PACKING.md): raw knob
    # block, normalized by engine.packing.normalize_packing — the ONE
    # interpretation point.  {"enabled": false} restores byte-identical
    # fixed-batch behavior; hot-reloadable via bootstrap
    # apply_packing_knobs.
    packing: Dict[str, Any] = field(default_factory=dict)
    # quantized trunk serving mode (docs/KERNELS.md): raw knob block
    # normalized by engine.kernels.normalize_quant — mode off|bf16|int8
    # (default off = byte-identical), per-trunk-group selector, parity
    # calibration.  Hot-reloadable via bootstrap apply_kernel_knobs.
    quant: Dict[str, Any] = field(default_factory=dict)
    # tuned-kernel toggles (docs/KERNELS.md): raw knob block normalized
    # by engine.kernels.normalize_kernels — head-bank epilogue fusion +
    # the BGMV per-item gather.  All default OFF; hot-reloadable via
    # bootstrap apply_kernel_knobs.
    kernels: Dict[str, Any] = field(default_factory=dict)
    # serving mesh (docs/PARALLEL.md): raw knob block normalized by
    # engine.mesh.normalize_mesh — dp×tp placement of the fused/packed
    # classifier bank ({"enabled": false} default = byte-identical
    # single-device serving).  Hot-reloadable via bootstrap
    # apply_mesh_knobs with the atomic program-set swap.
    mesh: Dict[str, Any] = field(default_factory=dict)
    # decision-aware signal cascade (docs/CASCADE.md): raw knob block
    # normalized by engine.cascade.normalize_cascade — cost-ordered wave
    # dispatch that skips classifier forwards the routing decision
    # provably cannot use ({"enabled": false} default = full fan-out,
    # byte-identical routing).  Hot-reloadable via bootstrap
    # apply_cascade_knobs.
    cascade: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "InferenceEngineConfig":
        d = d or {}
        out = cls(
            max_batch_size=int(d.get("max_batch_size", 32)),
            max_wait_ms=float(d.get("max_wait_ms", 2.0)),
            dtype=d.get("dtype", "bfloat16"),
            mesh_shape=dict(d.get("mesh_shape", {}) or {}),
            use_flash_attention=bool(d.get("use_flash_attention", True)),
            matryoshka_layers=list(d.get("matryoshka_layers", [])),
            matryoshka_dims=list(d.get("matryoshka_dims", [])),
            dispatch_workers=int(d.get("dispatch_workers", 4)),
            fuse_trunks=bool(d.get("fuse_trunks", True)),
            packing=dict(d.get("packing", {}) or {}),
            quant=dict(d.get("quant", {}) or {}),
            kernels=dict(d.get("kernels", {}) or {}),
            mesh=dict(d.get("mesh", {}) or {}),
            cascade=dict(d.get("cascade", {}) or {}),
        )
        if d.get("seq_len_buckets"):
            out.seq_len_buckets = [int(x) for x in d["seq_len_buckets"]]
        return out

    def packing_config(self) -> Dict[str, Any]:
        """Normalized engine.packing block (defaults merged) — delegates
        to the subsystem's own normalizer so a directly constructed
        engine and a bootstrap-configured one can never drift."""
        from ..engine.packing import normalize_packing

        return normalize_packing(self.packing)

    def quant_config(self) -> Dict[str, Any]:
        """Normalized engine.quant block (docs/KERNELS.md) — same
        delegation pattern as packing_config: engine.kernels owns the
        ONE interpretation point."""
        from ..engine.kernels import normalize_quant

        return normalize_quant(self.quant)

    def kernels_config(self) -> Dict[str, Any]:
        """Normalized engine.kernels block (docs/KERNELS.md)."""
        from ..engine.kernels import normalize_kernels

        return normalize_kernels(self.kernels)

    def mesh_config(self) -> Dict[str, Any]:
        """Normalized engine.mesh block (docs/PARALLEL.md) — same
        delegation pattern: engine.mesh owns the ONE interpretation
        point for the serving-mesh knobs."""
        from ..engine.mesh import normalize_mesh

        return normalize_mesh(self.mesh)

    def cascade_config(self) -> Dict[str, Any]:
        """Normalized engine.cascade block (docs/CASCADE.md) — same
        delegation pattern: engine.cascade owns the ONE interpretation
        point for the early-exit cascade knobs."""
        from ..engine.cascade import normalize_cascade

        return normalize_cascade(self.cascade)


DEFAULT_RECIPE_NAME = "default"


@dataclass
class RoutingRecipe:
    """One named routing profile (reference RoutingRecipe,
    pkg/config/recipes.go:17-22 + canonical_recipes.go:19-23): the same
    profile shape as the top-level routing block, minus modelCards — the
    model catalog stays shared across recipes."""

    name: str
    description: str = ""
    signals: "SignalsConfig" = field(default_factory=lambda: SignalsConfig())
    projections: "ProjectionsConfig" = field(
        default_factory=lambda: ProjectionsConfig())
    decisions: List["Decision"] = field(default_factory=list)
    strategy: str = "priority"

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RoutingRecipe":
        d = d or {}
        routing = d.get("routing", d) or {}
        return cls(
            name=str(d.get("name", "")),
            description=str(d.get("description", "")),
            signals=SignalsConfig.from_dict(routing.get("signals", {})),
            projections=ProjectionsConfig.from_dict(
                routing.get("projections", {})),
            decisions=[Decision.from_dict(x)
                       for x in routing.get("decisions", []) or []],
            strategy=str(routing.get("strategy", "priority")),
        )


@dataclass
class Entrypoint:
    """Virtual request model names → recipe binding (reference
    EntrypointMapping, recipes.go:24-29): the virtual names never reach a
    backend; they only select which routing profile evaluates."""

    model_names: List[str] = field(default_factory=list)
    recipe: str = ""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Entrypoint":
        d = d or {}
        return cls(
            model_names=[str(m) for m in d.get("model_names", []) or []],
            recipe=str(d.get("recipe", "")))


@dataclass
class RouterConfig:
    """The root configuration object (reference RouterConfig,
    pkg/config/config.go:60-100)."""

    model_cards: List[ModelCard] = field(default_factory=list)
    signals: SignalsConfig = field(default_factory=SignalsConfig)
    projections: ProjectionsConfig = field(default_factory=ProjectionsConfig)
    decisions: List[Decision] = field(default_factory=list)
    strategy: str = "priority"  # priority | confidence
    default_model: str = ""
    semantic_cache: SemanticCacheConfig = field(default_factory=SemanticCacheConfig)
    engine: InferenceEngineConfig = field(default_factory=InferenceEngineConfig)
    classifier_models: Dict[str, Any] = field(default_factory=dict)  # per-task model specs
    authz: Dict[str, Any] = field(default_factory=dict)
    ratelimit: Dict[str, Any] = field(default_factory=dict)
    memory: Dict[str, Any] = field(default_factory=dict)
    looper: Dict[str, Any] = field(default_factory=dict)
    router_replay: Dict[str, Any] = field(default_factory=dict)
    observability: Dict[str, Any] = field(default_factory=dict)
    api_server: Dict[str, Any] = field(default_factory=dict)
    tool_selection: Dict[str, Any] = field(default_factory=dict)
    prompt_compression: Dict[str, Any] = field(default_factory=dict)
    # Client-controlled bypass headers are OFF unless the operator opts in
    # (reference SkipProcessingConfig, pkg/config/config.go:186:
    # x-vsr-skip-processing is honored only when enabled; skip_signals is
    # operator config, never a bare request header).
    skip_processing: Dict[str, Any] = field(default_factory=dict)
    # external durable-state backends (state taxonomy: response store,
    # vectorstore; cache/replay/memory carry backend fields in their own
    # blocks)
    response_store: Dict[str, Any] = field(default_factory=dict)
    vectorstore: Dict[str, Any] = field(default_factory=dict)
    knowledge_bases: List["KnowledgeBaseDef"] = field(default_factory=list)
    # remote MCP servers: {"classifiers": [{name, transport, command/url,
    # tool, threshold}]} — served-classifier clients (pkg/mcp)
    mcp: Dict[str, Any] = field(default_factory=dict)
    # external model endpoints: [{role: guardrail|embedding, base_url,
    # model, api_key_env, ...}] — vLLM-served guard classifier
    # (pkg/classification/vllm_classifier.go) and remote OpenAI-compatible
    # embedding provider (pkg/embedding)
    external_models: List[Dict[str, Any]] = field(default_factory=list)
    # router learning (pkg/extproc/router_learning*.go): {enabled,
    # store: {backend, ...}, adaptation: {mode, candidate_set},
    # protection: {scope, identity.headers, tuning}}
    learning: Dict[str, Any] = field(default_factory=dict)
    # overload control & graceful degradation (resilience/controller.py):
    # {enabled, interval_s, max_level, hysteresis_ticks, escalate_ticks,
    # queue_high_watermark, saturation_high_watermark, brownout_class,
    # admission: {target_utilization, burst_s, reject_class,
    # default_cost_ms}, fail_static: {model}, priority: {header,
    # trust_header, default, model_classes, group_classes}}
    resilience: Dict[str, Any] = field(default_factory=dict)
    # shared state plane (stateplane/): pluggable fleet backend behind
    # which the semantic cache, vector store, explain mirror, and
    # fleet-wide degradation share state across N replicas — {enabled,
    # backend: memory|resp|sqlite, replica_id, namespace, heartbeat_s,
    # ttl_s, ring_vnodes, cooldown_s, share: {cache, vectorstore,
    # explain, fleet}, backend_config: {host, port, path, ...}}
    stateplane: Dict[str, Any] = field(default_factory=dict)
    # learned routing flywheel (flywheel/): decision records → trained
    # policies → counterfactual promotion — {enabled, corpus: {max_rows,
    # path}, features: {dim}, trainer: {algorithms, out_dir, alpha,
    # cost_weight}, evaluator: {min_rows, bootstrap, seed}, promotion:
    # {mode: off|shadow|auto, canary_fraction, canary_min_requests,
    # rollback_on: any|fast}, admission: {enabled, floor, ceiling}}
    flywheel: Dict[str, Any] = field(default_factory=dict)
    # on-device ANN plane (ann/, docs/ANN.md): semantic-cache similarity
    # + RAG retrieval as a sharded device matmul — {enabled, dim,
    # min_capacity, max_capacity, quant: f32|bf16|int8, recall_floor,
    # calibration_queries, top_k, promote_ewma, promote_min_hits,
    # compact_interval_s, tombstone_ratio, evict_watermark,
    # sync_interval_s, batch: {enabled, max_batch, max_wait_ms},
    # mesh: {enabled, dp, tp}, share: {cache, vectorstore}} — raw block
    # normalized by ann.normalize_ann, applied by apply_ann_knobs
    # ({"enabled": false} default = byte-identical cache/vectorstore)
    ann: Dict[str, Any] = field(default_factory=dict)
    # canonical v0.3 contract surface (canonical_config.go): named routing
    # profiles + virtual-model entrypoints + deployment listeners/providers
    recipes: List[RoutingRecipe] = field(default_factory=list)
    entrypoints: List[Entrypoint] = field(default_factory=list)
    listeners: List[Dict[str, Any]] = field(default_factory=list)
    providers: Dict[str, Any] = field(default_factory=dict)
    version: str = ""
    raw: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RouterConfig":
        d = d or {}
        # canonical `global:` block (canonical_global.go): runtime config
        # grouped away from the routing surface — normalize by lifting its
        # keys to the top level (explicit top-level keys win)
        if isinstance(d.get("global"), dict):
            d = {**d["global"], **{k: v for k, v in d.items()
                                   if k != "global"}}
        routing = d.get("routing", {}) or {}
        return cls(
            model_cards=[ModelCard.from_dict(m) for m in routing.get("modelCards", d.get("model_cards", []))],
            signals=SignalsConfig.from_dict(routing.get("signals", d.get("signals", {}))),
            projections=ProjectionsConfig.from_dict(routing.get("projections", d.get("projections", {}))),
            decisions=[Decision.from_dict(x) for x in routing.get("decisions", d.get("decisions", []))],
            strategy=routing.get("strategy", d.get("strategy", "priority")),
            default_model=d.get("default_model", routing.get(
                "default_model",
                ((d.get("providers") or {}).get("defaults") or {})
                .get("default_model", ""))),
            semantic_cache=SemanticCacheConfig.from_dict(d.get("semantic_cache", {})),
            engine=InferenceEngineConfig.from_dict(d.get("engine", d.get("inference_engine", {}))),
            classifier_models=dict(d.get("classifier_models", {}) or {}),
            authz=dict(d.get("authz", {}) or {}),
            ratelimit=dict(d.get("ratelimit", {}) or {}),
            memory=dict(d.get("memory", {}) or {}),
            looper=dict(d.get("looper", {}) or {}),
            router_replay=dict(d.get("router_replay", {}) or {}),
            observability=dict(d.get("observability", {}) or {}),
            api_server=dict(d.get("api_server", {}) or {}),
            tool_selection=dict(d.get("tool_selection", {}) or {}),
            prompt_compression=dict(d.get("prompt_compression", {}) or {}),
            skip_processing=dict(d.get("skip_processing", {}) or {}),
            response_store=dict(d.get("response_store", {}) or {}),
            vectorstore=dict(d.get("vectorstore", {}) or {}),
            knowledge_bases=[KnowledgeBaseDef.from_dict(k) for k in
                             d.get("knowledge_bases",
                                   routing.get("knowledge_bases", []))
                             or []],
            mcp=dict(d.get("mcp", {}) or {}),
            external_models=list(d.get("external_models", []) or []),
            learning=dict(routing.get("learning",
                                      d.get("learning", {})) or {}),
            resilience=dict(d.get("resilience", {}) or {}),
            stateplane=dict(d.get("stateplane", {}) or {}),
            flywheel=dict(d.get("flywheel", {}) or {}),
            ann=dict(d.get("ann", {}) or {}),
            recipes=[RoutingRecipe.from_dict(r)
                     for r in d.get("recipes", []) or []],
            entrypoints=[Entrypoint.from_dict(e)
                         for e in d.get("entrypoints", []) or []],
            listeners=list(d.get("listeners", []) or []),
            providers=dict(d.get("providers", {}) or {}),
            version=str(d.get("version", "")),
            raw=d,
        )

    def model_card(self, name: str) -> Optional[ModelCard]:
        for m in self.model_cards:
            if m.name == name:
                return m
        return None

    # -- observability knobs ----------------------------------------------
    # The observability block is free-form; these accessors are the ONE
    # place its tracing/metrics/flight-recorder sub-keys are interpreted,
    # so bootstrap and tests can never drift on defaults:
    #
    #   observability:
    #     tracing:
    #       otlp_endpoint: http://collector:4318   # OTLP/HTTP JSON export
    #       sample_rate: 0.1       # fraction of traces that keep the
    #                              # per-stage children of batch.ride
    #                              # (stack, h2d, dispatch, readback,
    #                              # demux: host timers around the one
    #                              # program every step runs); continuity
    #                              # spans (batch.wait/ride + step links)
    #                              # are never sampled away.  1.0 = every
    #                              # trace keeps them, 0 = none
    #     metrics:
    #       exemplars: true        # OpenMetrics trace-id exemplars on
    #                              # histogram buckets (opt-in)
    #     flight_recorder:
    #       slowest_n: 16          # slowest requests retained with full
    #                              # span trees (/debug/flightrec)
    #       threshold_ms: 500      # also retain any request slower than
    #                              # this (0/absent = slowest-N only)
    #       breach_capacity: 64    # bounded ring for threshold breaches
    #     runtime_stats:
    #       enabled: true          # always-on device-step sampler +
    #                              # process gauges (llm_runtime_*)
    #       interval_s: 10         # sampler flush/gauge period
    #     programstats:
    #       enabled: true          # XLA program-cost catalog: compile
    #                              # sites register deferred cost
    #                              # captures (llm_program_* rooflines,
    #                              # GET /debug/programs)
    #       slo_capture:
    #         enabled: true        # a firing SLO alert arms ONE bounded
    #                              # profiler trace + catalog snapshot
    #         trace_s: 2.0         # bounded trace duration
    #         cooldown_s: 300      # min seconds between captures
    #     slo:
    #       enabled: true          # in-process burn-rate monitors
    #       evaluation_interval_s: 10
    #       objectives:            # compact DSL or explicit dicts
    #         - routing_latency p99 < 25ms over 5m
    #         - signal error-rate < 0.1% over 5m
    #       fast_burn: 14.4        # page pair (w, 12w) threshold
    #       slow_burn: 6.0         # ticket pair (6w, 72w) threshold
    #     fleet:
    #       enabled: false         # fleet observability plane
    #                              # (observability/fleetobs.py) —
    #                              # requires stateplane.enabled; off
    #                              # builds nothing
    #       publish_interval_s: 0  # snapshot publication cadence on the
    #                              # heartbeat thread (0 = every beat)
    #       cache_s: 1.0           # read-time merge cache (scrapes +
    #                              # SLO ticks share one merge)
    #       debug_top_n: 8         # slowest-N / newest-N summary rows
    #                              # shipped per replica

    def tracing_config(self) -> Dict[str, Any]:
        return dict((self.observability or {}).get("tracing", {}) or {})

    def tracing_sample_rate(self) -> float:
        try:
            return float(self.tracing_config().get("sample_rate", 0.1))
        except (TypeError, ValueError):
            return 0.1

    def metrics_exemplars_enabled(self) -> bool:
        m = (self.observability or {}).get("metrics", {}) or {}
        return bool(m.get("exemplars", False))

    def flight_recorder_config(self) -> Dict[str, Any]:
        """Normalized FlightRecorder.configure kwargs from the
        observability.flight_recorder block (ms → s for the threshold)."""
        fr = (self.observability or {}).get("flight_recorder", {}) or {}
        out: Dict[str, Any] = {}
        if "slowest_n" in fr:
            out["slowest_n"] = int(fr["slowest_n"])
        if "threshold_ms" in fr:
            out["threshold_s"] = float(fr["threshold_ms"]) / 1e3
        if "breach_capacity" in fr:
            out["breach_capacity"] = int(fr["breach_capacity"])
        return out

    def runtime_stats_config(self) -> Dict[str, Any]:
        """Normalized observability.runtime_stats block: the always-on
        device-step sampler + process gauges (on by default — the whole
        point is continuous coverage; disable only for overhead A/Bs)."""
        rs = (self.observability or {}).get("runtime_stats", {}) or {}
        try:
            interval = float(rs.get("interval_s", 10.0))
        except (TypeError, ValueError):
            interval = 10.0
        return {"enabled": bool(rs.get("enabled", True)),
                "interval_s": interval}

    def programstats_config(self) -> Dict[str, Any]:
        """Normalized observability.programstats block: the XLA
        program-cost catalog (on by default — capture is deferred, so
        the hot path only pays an abstract-shape insert) and the
        SLO-burn-triggered capture arm (bounded trace + snapshot)."""
        ps = (self.observability or {}).get("programstats", {}) or {}
        cap = ps.get("slo_capture", {}) or {}
        try:
            trace_s = float(cap.get("trace_s", 2.0))
        except (TypeError, ValueError):
            trace_s = 2.0
        try:
            cooldown_s = float(cap.get("cooldown_s", 300.0))
        except (TypeError, ValueError):
            cooldown_s = 300.0
        return {"enabled": bool(ps.get("enabled", True)),
                "slo_capture": {
                    "enabled": bool(cap.get("enabled", True)),
                    "trace_s": max(0.0, trace_s),
                    "cooldown_s": max(0.0, cooldown_s)}}

    def slo_config(self) -> Dict[str, Any]:
        """The observability.slo block, passed verbatim to
        SLOMonitor.configure (which owns parsing + error containment) —
        absent block = no objectives = monitor disabled."""
        return dict((self.observability or {}).get("slo", {}) or {})

    def fleet_obs_config(self) -> Dict[str, Any]:
        """Normalized observability.fleet block — the fleet
        observability plane (observability/fleetobs.py).  Default OFF:
        the disabled posture builds nothing (no publisher on the
        heartbeat, no llm_fleet_* series, /metrics byte-identical).
        Only effective when stateplane.enabled is also true — there is
        no plane to federate over otherwise."""
        f = (self.observability or {}).get("fleet", {}) or {}
        out: Dict[str, Any] = {"enabled": bool(f.get("enabled", False))}
        try:
            out["publish_interval_s"] = max(
                0.0, float(f.get("publish_interval_s", 0.0)))
        except (TypeError, ValueError):
            out["publish_interval_s"] = 0.0
        try:
            out["cache_s"] = max(0.0, float(f.get("cache_s", 1.0)))
        except (TypeError, ValueError):
            out["cache_s"] = 1.0
        try:
            out["debug_top_n"] = max(1, int(f.get("debug_top_n", 8)))
        except (TypeError, ValueError):
            out["debug_top_n"] = 8
        return out

    def decision_explain_config(self) -> Dict[str, Any]:
        """Normalized observability.decisions block — the per-request
        decision-record knobs (observability/explain.py):

          observability:
            decisions:
              enabled: true      # assemble + ring decision records
              ring_size: 512     # bounded in-process record ring
              sample_rate: 1.0   # deterministic per trace id
              redact_pii: true   # drop query text + pii details
              durable:           # optional SQLite mirror of the ring
                backend: sqlite  # (observability/explain_store.py) —
                path: /var/lib/vsr/decisions.db  # post-restart audits
                max_records: 100000

        Malformed values fall back to the defaults (telemetry config is
        never fatal)."""
        d = (self.observability or {}).get("decisions", {}) or {}
        out: Dict[str, Any] = {"enabled": bool(d.get("enabled", True)),
                               "redact_pii": bool(d.get("redact_pii",
                                                        True)),
                               "durable": dict(d.get("durable", {})
                                               or {})}
        try:
            out["ring_size"] = int(d.get("ring_size", 512))
        except (TypeError, ValueError):
            out["ring_size"] = 512
        try:
            out["sample_rate"] = float(d.get("sample_rate", 1.0))
        except (TypeError, ValueError):
            out["sample_rate"] = 1.0
        return out

    def resilience_config(self) -> Dict[str, Any]:
        """The ``resilience`` block, passed verbatim to
        DegradationController.configure / PriorityResolver.from_config
        (which own parsing + error containment — a malformed resilience
        knob must never stop the server)::

          resilience:
            enabled: true
            interval_s: 2            # control-loop tick period
            max_level: 4             # ladder ceiling (0..4)
            escalate_ticks: 1        # overloaded ticks per rung up
            hysteresis_ticks: 3      # healthy ticks per rung down
            queue_high_watermark: 64 # batcher pending_items trip point
            saturation_high_watermark: 0.9   # dispatch-pool busy ratio
            brownout_class: normal   # this class and below go
                                     # heuristic-only at L2
            admission:               # L3 token buckets
              target_utilization: 0.8
              burst_s: 2.0
              reject_class: low      # 429'd outright at L3
              default_cost_ms: 5     # pre-telemetry request cost
            fail_static:
              model: ""              # L4 model ("" = default_model)
            priority:
              header: x-vsr-priority
              trust_header: true
              default: normal
              model_classes: {}      # model/entrypoint -> class
              group_classes: {}      # authz group -> class
        """
        return dict(self.resilience or {})

    def upstream_config(self) -> Dict[str, Any]:
        """Normalized ``resilience.upstream`` block — the upstream
        resilience plane (resilience/upstream.py), the ONE
        interpretation point::

          resilience:
            upstream:
              enabled: false       # default OFF: byte-identical routing
              fleet_share: true    # publish open circuits via the
                                   # state plane (when one is attached)
              breaker:
                failures: 5        # consecutive failures to open
                open_s: 10         # cooldown before the half-open probe
                ewma_alpha: 0.2    # error-rate / latency EWMA weight
                error_rate: 0.5    # ALSO open on sustained EWMA error
                                   # rate >= this once >= 10 samples
                                   # exist (0 or 1 disables this leg)
              retry:
                budget_per_s: 1.0  # token-bucket retry budget
                burst: 10          # bucket burst (retries)
                max_attempts: 3    # total attempts incl. the first
                backoff_ms: 50     # jittered exponential backoff base
                disable_at_level: 2   # no retries at degradation >= L2
                on: [connect, 5xx, timeout, reset]  # retryable kinds
              deadline:
                header: x-vsr-deadline
                default_s: 0       # request budget (0 = flat forward
                                   # timeout)
                floor_s: 0.5       # per-attempt timeout floor

        Malformed values fall back to defaults — resilience config must
        never stop the server."""
        up = dict((self.resilience or {}).get("upstream", {}) or {})
        out: Dict[str, Any] = {
            "enabled": bool(up.get("enabled", False)),
            "fleet_share": bool(up.get("fleet_share", True)),
        }

        def _block(name: str, defaults: Dict[str, Any]) -> Dict[str, Any]:
            raw = dict(up.get(name, {}) or {})
            merged = dict(defaults)
            for k, v in raw.items():
                if k not in defaults:
                    continue
                want = type(defaults[k])
                try:
                    if want is bool:
                        merged[k] = bool(v)
                    elif want is int:
                        merged[k] = int(v)
                    elif want is float:
                        merged[k] = float(v)
                    elif want is list:
                        if isinstance(v, (list, tuple)):
                            merged[k] = [str(x) for x in v]
                        elif v:
                            merged[k] = [str(v)]
                    else:
                        merged[k] = str(v)
                except (TypeError, ValueError):
                    pass
            return merged

        out["breaker"] = _block("breaker", {
            "failures": 5, "open_s": 10.0, "ewma_alpha": 0.2,
            "error_rate": 0.5})
        out["retry"] = _block("retry", {
            "budget_per_s": 1.0, "burst": 10.0, "max_attempts": 3,
            "backoff_ms": 50.0, "disable_at_level": 2,
            "on": ["connect", "5xx", "timeout", "reset"],
            # share the retry budget FLEET-WIDE through the StatePlane
            # StateBackend seam (docs/RESILIENCE.md): N replicas then
            # spend ONE budget_per_s pool instead of N — active only
            # when a plane is attached and fleet_share is on; plane
            # loss degrades to the local per-replica bucket
            "fleet_budget": True})
        out["deadline"] = _block("deadline", {
            "header": "x-vsr-deadline", "default_s": 0.0,
            "floor_s": 0.5})
        return out

    def stateplane_config(self) -> Dict[str, Any]:
        """Normalized ``stateplane`` block — the ONE interpretation
        point (bootstrap, the fleet harness, and tests must never drift
        on defaults)::

          stateplane:
            enabled: false         # default OFF: byte-identical
                                   # single-process behavior
            backend: resp          # memory | resp/redis/valkey | sqlite
            backend_config:
              host: redis.svc      # resp
              port: 6379
              path: /var/lib/vsr/plane.db   # sqlite
            replica_id: ""         # default host-pid-nonce
            namespace: srt         # key prefix on the shared store
            heartbeat_s: 2         # membership beat; TTL = 3x
            ring_vnodes: 64        # consistent-hash ring resolution
            cooldown_s: 2          # breaker reopen probe interval
            share:                 # which layers ride the plane
              cache: true
              vectorstore: true
              explain: true
              fleet: true          # fleet-aggregated shed ladder

        Malformed values fall back to defaults — shared-state config
        must never stop a replica."""
        sp = dict(self.stateplane or {})
        out: Dict[str, Any] = {
            "enabled": bool(sp.get("enabled", False)),
            "backend": str(sp.get("backend", "memory")),
            "replica_id": str(sp.get("replica_id", "")),
            "namespace": str(sp.get("namespace", "srt")) or "srt",
            "backend_config": dict(sp.get("backend_config", {}) or {}),
        }

        def _f(key: str, default: float, lo: float) -> float:
            try:
                return max(lo, float(sp.get(key, default)))
            except (TypeError, ValueError):
                return default

        out["heartbeat_s"] = _f("heartbeat_s", 2.0, 0.05)
        out["ttl_s"] = _f("ttl_s", 0.0, 0.0)  # 0 = 3x heartbeat
        out["cooldown_s"] = _f("cooldown_s", 2.0, 0.05)
        try:
            out["ring_vnodes"] = max(1, int(sp.get("ring_vnodes", 64)))
        except (TypeError, ValueError):
            out["ring_vnodes"] = 64
        share = dict(sp.get("share", {}) or {})
        out["share"] = {k: bool(share.get(k, True))
                        for k in ("cache", "vectorstore", "explain",
                                  "fleet")}
        return out

    def ann_config(self) -> Dict[str, Any]:
        """Normalized ``ann`` block (docs/ANN.md knob table) — same
        delegation pattern as mesh/cascade: ann.normalize_ann owns the
        ONE interpretation point for the on-device ANN plane knobs."""
        from ..ann.knobs import normalize_ann

        return normalize_ann(self.ann)

    def flywheel_config(self) -> Dict[str, Any]:
        """Normalized ``flywheel`` block — the ONE interpretation point
        (bootstrap, the controller, and tests must never drift on
        defaults)::

          flywheel:
            enabled: false         # default OFF: byte-identical routing
            cycle_interval_s: 0    # scheduled run_cycle period
                                   # (0 = operator-triggered POST only)
            corpus:
              max_rows: 10000      # export window over the explain ring
                                   # + durable mirror
              path: ""             # optional JSONL export target
            features:
              dim: 64              # signal-hash bucket width
            trainer:
              algorithms: [cost_bandit]   # first trainable = candidate
              out_dir: ""          # artifact directory ("" = in-memory)
              alpha: 0.0           # LinUCB exploration bonus
              cost_weight: 0.1     # device-cost penalty weight
            evaluator:
              min_rows: 20         # corpus floor before any cycle acts
              bootstrap: 200       # CI resamples
              seed: 0
            promotion:
              mode: shadow         # off | shadow | auto
              canary_fraction: 0.1
              canary_min_requests: 200
              rollback_on: any     # any | fast (SLO burn severities)
            admission:
              enabled: true        # feed value weights to L3 admission
              floor: 0.25          # weight clamp (cheapest admission)
              ceiling: 4.0

        Malformed values fall back to defaults — flywheel config must
        never stop the server."""
        fw = dict(self.flywheel or {})
        out: Dict[str, Any] = {"enabled": bool(fw.get("enabled", False))}
        # scheduled cycle runner: run_cycle() fires every interval
        # instead of operator-triggered POST only (0 = operator-only)
        try:
            out["cycle_interval_s"] = max(
                0.0, float(fw.get("cycle_interval_s", 0.0)))
        except (TypeError, ValueError):
            out["cycle_interval_s"] = 0.0

        def _block(name: str, defaults: Dict[str, Any]) -> Dict[str, Any]:
            raw = dict(fw.get(name, {}) or {})
            merged = dict(defaults)
            for k, v in raw.items():
                if k not in defaults:
                    continue
                want = type(defaults[k])
                try:
                    if want is bool:
                        merged[k] = bool(v)
                    elif want is int:
                        merged[k] = int(v)
                    elif want is float:
                        merged[k] = float(v)
                    elif want is list:
                        # a bare scalar ("algorithms: cost_bandit") is
                        # one entry, never exploded character-wise
                        if isinstance(v, (list, tuple)):
                            merged[k] = [str(x) for x in v]
                        elif v:
                            merged[k] = [str(v)]
                    else:
                        merged[k] = str(v)
                except (TypeError, ValueError):
                    pass
            return merged

        out["corpus"] = _block("corpus", {"max_rows": 10_000,
                                          "path": ""})
        out["features"] = _block("features", {"dim": 64})
        out["trainer"] = _block("trainer", {
            "algorithms": ["cost_bandit"], "out_dir": "",
            "alpha": 0.0, "cost_weight": 0.1})
        out["evaluator"] = _block("evaluator", {
            "min_rows": 20, "bootstrap": 200, "seed": 0})
        out["promotion"] = _block("promotion", {
            "mode": "shadow", "canary_fraction": 0.1,
            "canary_min_requests": 200, "rollback_on": "any"})
        out["admission"] = _block("admission", {
            "enabled": True, "floor": 0.25, "ceiling": 4.0})
        return out

    # -- recipes (pkg/config/recipes.go) -----------------------------------

    def recipe_by_name(self, name: str) -> Optional[RoutingRecipe]:
        """Named recipe lookup; DEFAULT_RECIPE_NAME always resolves to a
        recipe mirroring the flat routing fields (recipes.go:31-52), so
        single-profile and recipe-aware read sites observe the same
        default behavior."""
        for r in self.recipes:
            if r.name == name:
                return r
        if name == DEFAULT_RECIPE_NAME:
            return RoutingRecipe(
                name=DEFAULT_RECIPE_NAME, signals=self.signals,
                projections=self.projections, decisions=self.decisions,
                strategy=self.strategy)
        return None

    def recipe_for_request_model(self, model: str
                                 ) -> Optional[RoutingRecipe]:
        """Resolve a request model name through the entrypoint table
        (recipes.go:55-73); None when no entrypoint matches — callers
        fall back to auto/specified-model handling."""
        model = (model or "").strip()
        if not model:
            return None
        for ep in self.entrypoints:
            if model in ep.model_names:
                return self.recipe_by_name(ep.recipe)
        return None

    def used_signal_types(self) -> List[str]:
        """Signal families actually referenced by decision rules, complexity
        composers, or projections — the dispatch layer only evaluates these
        (reference: classifier_signal_dispatch.go buildSignalDispatchers)."""
        used: set = set()
        for dec in self.decisions:
            for leaf in dec.rules.leaves():
                used.add(leaf.signal_type.lower())
        for comp in self.signals.complexity:
            if comp.composer is not None:
                for leaf in comp.composer.leaves():
                    used.add(leaf.signal_type.lower())
        for score in self.projections.scores:
            for inp in score.inputs:
                if inp.type == "kb_metric":
                    # kb_metric values come from the kb family evaluator
                    used.add("kb")
                elif inp.type:
                    used.add(inp.type.lower())
        # Partition members are rule names from arbitrary families; the
        # families providing them must be evaluated too.
        member_names = {m for p in self.projections.partitions for m in p.members}
        if member_names:
            for styp in ALL_SIGNAL_TYPES:
                if member_names & set(self.signals.rule_names(styp)):
                    used.add(styp)
        return sorted(t for t in used if t)


def asdict(cfg: Any) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)


_SECRET_KEY_MARKERS = ("api_key", "apikey", "secret", "password",
                       "private_key", "access_key")


def _is_secret_key(key: str) -> bool:
    lk = key.lower()
    if any(m in lk for m in _SECRET_KEY_MARKERS):
        return True
    # "token" only as the trailing word: auth_token/bearer_token/token are
    # secrets; min_tokens/max_tokens are routing limits and must survive
    return lk == "token" or lk.endswith("_token") or lk == "credential"


def redact_config(d: Any) -> Any:
    """Deep-copy ``d`` with secret-bearing values masked.

    Any mapping value whose key names a secret (api_key, *_token, secret,
    password, ...) becomes ``"***"`` regardless of value type — a list or
    dict under a secret key is masked whole, never recursed into.  Used
    before serving raw config on unauthenticated listeners (reference
    redacts unless the principal has secret_view,
    pkg/config/management_api.go:67).
    """
    if isinstance(d, dict):
        out = {}
        for k, v in d.items():
            out[k] = "***" if _is_secret_key(str(k)) else redact_config(v)
        return out
    if isinstance(d, list):
        return [redact_config(x) for x in d]
    if isinstance(d, tuple):
        return tuple(redact_config(x) for x in d)
    return d
