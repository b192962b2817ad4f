"""Concurrent signal dispatch.

Mirrors the reference's per-request fan-out (classifier_signal_dispatch.go:
16-133): only signal families referenced by decisions/projections are
evaluated; each active family runs on its own worker; the join is the
wall-clock of the slowest family. Evaluator exceptions are contained and
recorded (fail-open — a dead signal family never kills routing, matching
processor_core.go:74-81's guarantee).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional

from ..config.schema import RouterConfig, SIGNAL_PROJECTION
from ..decision.engine import SignalMatches
from ..decision.projections import ProjectionEvaluator, ProjectionTrace
from .base import RequestContext, SignalEvaluator, SignalResult


# the fused item's budget: a cold fused compile past this falls back to
# the parallel per-evaluator path instead of stalling the whole request
PREFETCH_TIMEOUT_S = 10.0

# signal families that are a SAFETY control, not a quality optimization:
# the L2 brownout (resilience/controller.py) keeps these evaluating even
# for priority classes routed heuristic-only — browning out the
# jailbreak screen to save fused-bank capacity would trade an abuse
# vector for throughput, which is never the right trade
SAFETY_FAMILIES = ("jailbreak",)


@dataclass
class DispatchReport:
    results: Dict[str, SignalResult] = field(default_factory=dict)
    wall_s: float = 0.0
    projection_trace: Optional[ProjectionTrace] = None
    # set by Router.evaluate_signals: whether the evaluated view was
    # prompt-compressed.  route() reuses the PREFETCH's decision when
    # consuming precomputed signals, so a degradation-ladder transition
    # between prefetch and route cannot make ctx.user_text diverge from
    # the text the signals actually saw.  None = not recorded (direct
    # dispatcher callers).
    compressed_view: Optional[bool] = None
    # skip certificate from the cascade evaluator (engine/cascade): which
    # forwards were never submitted/cancelled and why.  None = plain
    # full-fan-out dispatch.
    cascade: Optional[dict] = None


def apply_complexity_composers(signals: SignalMatches,
                               complexity_rules) -> None:
    """Composer escalation, in ONE place: a matched composer forces its
    rule to ":hard", dropping any lower level the family evaluator
    reported.  Shared by the live dispatch fan-out and the replay
    engine's raw re-drive (replay/recorder._reproject) — the two must
    never drift, or replayed projections stop matching what the live
    request computed."""
    from ..decision.engine import eval_rule_node

    for rule in complexity_rules:
        if rule.composer is None:
            continue
        matched, conf, _ = eval_rule_node(rule.composer, signals)
        hard = f"{rule.name}:hard"
        if matched and hard not in signals.matches.get("complexity", ()):
            levels = signals.matches.get("complexity", [])
            signals.matches["complexity"] = [
                n for n in levels if n.split(":", 1)[0] != rule.name]
            signals.add("complexity", hard, max(conf, 0.5))


class _Rider(NamedTuple):
    """A family on a fused item: its task there, the task's kind, and the
    memo key its evaluator will look the result up by."""

    evaluator: SignalEvaluator
    task: str
    kind: str
    key: tuple


@dataclass
class _FusedItem:
    """One request's ``classify_multi`` call on one engine: the families
    of ``active`` whose task reads ``ctx.user_text`` on a common trunk
    group."""

    engine: Any
    riders: List[_Rider] = field(default_factory=list)
    # the token members' span threshold (an item carries one); None
    # while no token member rides
    threshold: Optional[float] = None

    def tasks(self) -> List[str]:
        return sorted({r.task for r in self.riders})

    def evaluators(self) -> List[SignalEvaluator]:
        return [r.evaluator for r in self.riders]


class SignalDispatcher:
    def __init__(self, evaluators: List[SignalEvaluator],
                 projections: Optional[ProjectionEvaluator] = None,
                 used_types: Optional[List[str]] = None,
                 complexity_rules: Optional[list] = None,
                 max_workers: int = 24,
                 metrics=None) -> None:
        self.evaluators = {e.signal_type: e for e in evaluators}
        self._metrics = metrics  # None: the process's default series
        self.projections = projections
        self.used_types = set(used_types) if used_types is not None else None
        self.complexity_rules = list(complexity_rules or [])
        self.pool = ThreadPoolExecutor(max_workers=max_workers,
                                       thread_name_prefix="signal")

    def _series(self):
        if self._metrics is not None:
            return self._metrics
        from ..observability import metrics as M

        return M.default_series

    def active_evaluators(self) -> List[SignalEvaluator]:
        if self.used_types is None:
            return list(self.evaluators.values())
        return [e for t, e in self.evaluators.items() if t in self.used_types]

    def learned_types(self, keep=None) -> List[str]:
        """Families backed by an inference engine (device work) — the
        set the resilience brownout (L2) skips for low-priority
        requests, so fused-bank capacity stays reserved for traffic
        that keeps full service.  Heuristic families never appear here:
        brownout must degrade quality, not kill routing.  ``keep``
        (default SAFETY_FAMILIES via the controller) names families the
        caller must NOT brown out — they are excluded from the skip
        set."""
        keep_set = set(keep or ())
        return sorted(t for t, e in self.evaluators.items()
                      if getattr(e, "engine", None) is not None
                      and t not in keep_set)

    def evaluate(self, ctx: RequestContext,
                 skip_signals: Optional[List[str]] = None
                 ) -> tuple[SignalMatches, DispatchReport]:
        start = time.perf_counter()
        report = DispatchReport()
        skip = set(skip_signals or ())
        active = [e for e in self.active_evaluators() if e.signal_type not in skip]

        run = self._runner(ctx)
        if len(active) <= 1:
            results = [run(e) for e in active]
        else:
            results = self._fan_out(ctx, run, active)

        signals = SignalMatches()
        kb_metrics: dict = {}
        for r in results:
            self._fold_result(r, signals, report, kb_metrics)
        self._finalize(signals, report, kb_metrics)
        report.wall_s = time.perf_counter() - start
        return signals, report

    def _runner(self, ctx: RequestContext):
        """Per-evaluator closure shared with the cascade evaluator
        (engine/cascade): trace re-establishment + fail-open + source
        attribution, identical whether the family runs in the full
        fan-out or in a cascade wave.

        Trace propagation across the thread fan-out: the pool workers
        have no thread-local span context, so without this every
        engine submit under them would detach from the request's trace
        (the batcher's batch.ride spans key off the captured context).
        Capture once here, re-establish per family as a signal.<type>
        child span; no active trace → zero-cost no-op."""
        from ..observability import batchtrace

        parent = batchtrace.capture()

        def run(e: SignalEvaluator) -> SignalResult:
            t0 = time.perf_counter()
            try:
                with batchtrace.activate(parent,
                                         f"signal.{e.signal_type}"):
                    out = e.evaluate(ctx)
                    if not out.source:
                        # decision-record source attribution: evaluators
                        # that don't self-report are heuristic unless
                        # they hold an engine handle
                        out.source = "engine" if getattr(
                            e, "engine", None) is not None else "heuristic"
                    return out
            except Exception as exc:  # fail open per family
                return SignalResult(signal_type=e.signal_type,
                                    latency_s=time.perf_counter() - t0,
                                    error=f"{type(exc).__name__}: {exc}",
                                    source="engine" if getattr(
                                        e, "engine", None) is not None
                                    else "heuristic")

        return run

    def _fan_out(self, ctx: RequestContext, run, active: list
                 ) -> List[SignalResult]:
        """Every active family's result, in ``active``'s order, with the
        request's fused item in flight BESIDE the rest of the fan-out.

        The families no item serves (embedding, preference, complexity,
        the heuristics) start on the pool first, so the embedding item
        reaches its batcher group while the fused item rides.  The
        blocking classify_multi then runs on THIS thread — never queued
        behind pool tasks — and the families it served evaluate here
        too, where they are memo lookups.  An item that failed or timed
        out hands its families to the pool: each classifies by itself,
        in parallel, as without an item."""
        items = self._gather_fused(ctx, active)
        riding = {id(e) for item in items for e in item.evaluators()}
        pooled = {id(e): self.pool.submit(run, e)
                  for e in active if id(e) not in riding}
        for item in items:
            if not self._seed_memo(ctx, item):
                for e in item.evaluators():
                    pooled[id(e)] = self.pool.submit(run, e)
        return [pooled[id(e)].result() if id(e) in pooled else run(e)
                for e in active]

    def _fold_result(self, r: SignalResult, signals: SignalMatches,
                     report: DispatchReport, kb_metrics: dict) -> None:
        """Fold one family's result into the running match set."""
        report.results[r.signal_type] = r
        self._series().signal_results.inc(family=r.signal_type,
                                          source=r.source or "unknown")
        for h in r.hits:
            signals.add(r.signal_type, h.rule, h.confidence)
            if h.detail:
                signals.details.setdefault(r.signal_type, {})[h.rule] = \
                    h.detail.get("keywords", h.detail)
        if r.metrics:  # kb family → kb_metric projection inputs
            kb_metrics.update(r.metrics)

    def _needs_projection(self) -> bool:
        return (
            self.projections is not None
            and (self.used_types is None or SIGNAL_PROJECTION in self.used_types
                 or bool(self.projections.cfg.scores)
                 or bool(self.projections.cfg.partitions))
        )

    def _finalize(self, signals: SignalMatches, report: DispatchReport,
                  kb_metrics: dict) -> None:
        """Post-fan-out derivations, in dispatch order.

        Complexity composers: boolean expressions over sibling families
        that force-escalate a rule to "hard" (reference: the composer
        block on complexity signals — evaluated after the fan-out since
        it references other signals).  Then projections."""
        if self.complexity_rules:
            apply_complexity_composers(signals, self.complexity_rules)
        if self._needs_projection():
            report.projection_trace = self.projections.evaluate(
                signals, kb_metrics=kb_metrics)

    def _gather_fused(self, ctx: RequestContext, active: list
                      ) -> List[_FusedItem]:
        """Tokenize-once + trunk-once for the learned fan-out: ONE item
        a text.

        Every active engine-backed family whose task reads
        ``ctx.user_text`` (its ``prefetch_task``; a token family also
        names its rule's ``prefetch_threshold``) and whose tasks one
        fused execution can serve — a shared TrunkGroup, sequence and
        token members alike — is gathered into one classify_multi call
        per engine, so a request activating K learned signals pays
        exactly one tokenization and one trunk forward.  What the item cannot carry keeps its own
        call: another text (include_history), a second token threshold,
        a task on no trunk group, a generative task.  Fewer than two
        tasks, or an unfusable mix, gather nothing (a serial call would
        serialize what the fan-out runs in parallel)."""
        text = ctx.user_text
        memo = getattr(ctx, "class_memo", None)
        if not text or memo is None:
            return []
        by_engine: Dict[int, _FusedItem] = {}
        for e in active:
            task = getattr(e, "prefetch_task", "")
            engine = getattr(e, "engine", None)
            if not task or engine is None or not engine.has_task(task):
                continue
            kind = engine.task_kind(task)
            threshold = getattr(e, "prefetch_threshold", None)
            if kind == "sequence":
                key = (id(engine), task, text)
            elif kind == "token" and threshold is not None:
                key = (id(engine), task, text, threshold)
            else:
                continue
            if key in memo:
                continue
            item = by_engine.setdefault(id(engine), _FusedItem(engine))
            if kind == "token":
                if item.threshold is None:
                    item.threshold = threshold
                elif item.threshold != threshold:
                    continue
            item.riders.append(_Rider(e, task, kind, key))
        items = []
        for item in by_engine.values():
            fused_covers = getattr(item.engine, "fused_covers", None)
            if fused_covers is None:
                continue
            if item.threshold is not None \
                    and not fused_covers(item.tasks()):
                # the token member sits on no trunk group with the
                # rest: it keeps its own call, the sequence members
                # still share theirs
                item.riders = [r for r in item.riders
                               if r.kind == "sequence"]
                item.threshold = None
            tasks = item.tasks()
            if len(tasks) >= 2 and fused_covers(tasks):
                items.append(item)
        return items

    def _seed_memo(self, ctx: RequestContext, item: _FusedItem) -> bool:
        """Run the item (blocking) and seed the request's memo with each
        task's result — the per-evaluator classify calls become lookups.
        False when the item raised or timed out: its families fall open
        to their own calls."""
        # bounded: a cold compile must not stall the request for the
        # engine's full default — on timeout the evaluators fall back
        # to their own (parallel) classify calls while the abandoned
        # batch keeps warming the jit cache
        kwargs = {} if item.threshold is None \
            else {"threshold": item.threshold}
        try:
            out = item.engine.classify_multi(
                item.tasks(), [ctx.user_text], timeout=PREFETCH_TIMEOUT_S,
                enc_cache=getattr(ctx, "enc_cache", None), **kwargs)
            for rider in item.riders:
                ctx.class_memo[rider.key] = out[rider.task][0]
        except Exception:
            return False  # evaluators classify individually (fail open)
        return True

    def _prefetch_fused(self, ctx: RequestContext, active: list) -> None:
        """The blocking form, for a caller that fans out by itself
        (engine/cascade, one wave at a time): run the items of ``active``
        and return with the memo seeded."""
        for item in self._gather_fused(ctx, active):
            self._seed_memo(ctx, item)

    def shutdown(self) -> None:
        self.pool.shutdown(wait=False, cancel_futures=True)


def build_heuristic_dispatcher(cfg: RouterConfig,
                               extra: Optional[List[SignalEvaluator]] = None
                               ) -> SignalDispatcher:
    """Build a dispatcher with every model-free evaluator wired from config.
    Learned (TPU-backed) evaluators are appended via *extra* by the engine
    bootstrap (see semantic_router_tpu.signals.learned)."""
    from .heuristic import (
        AuthzSignal,
        ContextSignal,
        ConversationSignal,
        EventSignal,
        LanguageSignal,
        ReaskSignal,
        StructureSignal,
    )
    from .keyword import KeywordSignal

    evaluators: List[SignalEvaluator] = [
        KeywordSignal(cfg.signals.keywords),
        ContextSignal(cfg.signals.context),
        StructureSignal(cfg.signals.structure),
        ConversationSignal(cfg.signals.conversation),
        LanguageSignal(cfg.signals.language),
        AuthzSignal(cfg.signals.role_bindings,
                    fail_open=bool(cfg.authz.get("fail_open", True))),
        EventSignal(cfg.signals.events),
        ReaskSignal(cfg.signals.reasks),
    ]
    evaluators.extend(extra or [])
    used = cfg.used_signal_types() or None
    return SignalDispatcher(
        evaluators,
        projections=ProjectionEvaluator(cfg.projections),
        used_types=used,
        complexity_rules=cfg.signals.complexity,
    )
