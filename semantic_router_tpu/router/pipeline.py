"""The request/response routing pipeline — the data plane core.

Re-designs the reference's ExtProc pipeline (pkg/extproc, 57k LoC Go) as an
embeddable Python object with the same stage order (hot path documented in
SURVEY.md §3.2; processor_req_body.go:31 handleRequestBody →
runRequestPreRoutingStages → handleModelRouting):

  parse → skip check → rate limit → (prompt compression) → signal fan-out →
  projections → decision engine → pre-routing plugins (fast-response,
  semantic cache, PII policy) → model selection → request mutation
  (system prompt, tools filter, model rewrite, reasoning fields) →
  x-vsr-* headers

and the response path (processor_res_body.go): response jailbreak screen →
hallucination detection (token spans + NLI gate) → warnings annotation →
cache update → usage/cost metrics → selector feedback.

Every ML call fails open (processor_core.go:74-81 parity): a dead engine
degrades the router to heuristics + default model, never to an outage.
"""

from __future__ import annotations

import json
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ..cache.semantic_cache import CacheBackend, build_cache
from ..config.schema import Decision, ModelRef, RouterConfig
from ..decision.engine import DecisionEngine, DecisionResult, SignalMatches
from ..engine.classify import InferenceEngine
from ..observability import metrics as M
from ..observability.batchtrace import route_done, route_span
from ..observability.logging import component_event
from ..observability.tracing import default_tracer
from ..selection import Feedback, SelectionContext, registry as selectors
from ..signals.base import RequestContext
from ..signals.dispatch import DispatchReport, build_heuristic_dispatcher
from . import headers as H
from .promptcompression import PromptCompressor
from .ratelimit import RateLimiter

LOOPER_ALGORITHMS = ("confidence", "ratings", "remom", "fusion",
                     "workflows")


@dataclass
class RouteResult:
    kind: str  # route | immediate | blocked | rate_limited | cache_hit | passthrough
    model: str = ""
    body: Optional[Dict[str, Any]] = None
    headers: Dict[str, str] = field(default_factory=dict)
    response_body: Optional[Dict[str, Any]] = None
    status: int = 200
    decision: Optional[DecisionResult] = None
    signals: Optional[SignalMatches] = None
    report: Optional[DispatchReport] = None
    selection_reason: str = ""
    routing_latency_s: float = 0.0
    request_id: str = ""
    looper_algorithm: str = ""  # set when the decision wants multi-model exec
    # the request's trace id + root span id (router.route span):
    # frontends inject them as traceparent toward the backend so upstream
    # spans parent under a span that actually exists in the trace
    trace_id: str = ""
    root_span_id: str = ""
    # decision-record id (observability/explain.py): set when this
    # request's routing audit record landed in the explain ring; echoed
    # to clients via the x-vsr-decision-record header
    decision_record_id: str = ""
    # upstream resilience plane (resilience/upstream.py): ranked
    # next-best candidate models for budgeted failover — filled only
    # when the plane is attached, also exported as x-vsr-fallback-models
    fallback_models: List[str] = field(default_factory=list)


@dataclass
class ResponseResult:
    body: Dict[str, Any]
    headers: Dict[str, str] = field(default_factory=dict)
    warnings: List[str] = field(default_factory=list)
    hallucination_spans: List[dict] = field(default_factory=list)


def usage_cost(usage: Dict[str, Any], pricing: Dict[str, float]) -> float:
    """$ cost of one response from its usage block and a model card's
    per-Mtok pricing — the ONE place this formula lives (model cost
    metrics and session telemetry must never diverge)."""
    return ((usage or {}).get("prompt_tokens", 0) / 1e6
            * (pricing or {}).get("prompt", 0.0)
            + (usage or {}).get("completion_tokens", 0) / 1e6
            * (pricing or {}).get("completion", 0.0))


def _immediate_chat_completion(content: str, model: str = "router") -> dict:
    return {
        "id": f"chatcmpl-{uuid.uuid4().hex[:24]}",
        "object": "chat.completion",
        "created": int(time.time()),
        "model": model,
        "choices": [{
            "index": 0,
            "message": {"role": "assistant", "content": content},
            "finish_reason": "stop",
        }],
        "usage": {"prompt_tokens": 0, "completion_tokens": 0,
                  "total_tokens": 0},
    }


class Router:
    """The routing pipeline. Embed directly, or serve via router.server."""

    def __init__(self, cfg: RouterConfig,
                 engine: Optional[InferenceEngine] = None,
                 cache: Optional[CacheBackend] = None,
                 embedding_task: str = "embedding",
                 metrics: "Optional[M.MetricSeries]" = None,
                 tracer=None, flightrec=None, explain=None,
                 resilience=None) -> None:
        self.cfg = cfg
        self.engine = engine
        self.embedding_task = embedding_task
        # instance-bound observability (pkg/routerruntime decoupling):
        # an embedded second router binds its own registry/tracer
        # instead of feeding the process globals
        self.M = metrics or M.default_series
        self.tracer = tracer or default_tracer
        # slow-request flight recorder (observability.flightrec): retains
        # full span trees for the slowest/threshold-breaching requests;
        # registry-bound when embedded, process default otherwise
        from ..observability.flightrec import default_flight_recorder

        self.flightrec = flightrec if flightrec is not None \
            else default_flight_recorder
        # tail-based sampling: a request the recorder retains (threshold
        # breach / slowest-N) pins its trace id as force-sampled on THIS
        # router's tracer — continued activity on that trace gets the
        # detailed batch tracing regardless of sample_rate.  Only wire
        # the pair the caller actually configured together: an
        # explicitly-passed recorder pairs with whatever tracer this
        # router runs, but the PROCESS-DEFAULT recorder must not get
        # pinned to a custom tracer (a later default-posture router
        # would then force-sample onto a tracer it never reads).
        paired = flightrec is not None or self.tracer is default_tracer
        if paired and getattr(self.flightrec, "on_retain", None) is None \
                and hasattr(self.tracer, "force_sample"):
            self.flightrec.on_retain = self.tracer.force_sample
        # decision explainability (observability/explain.py): per-request
        # routing audit records; registry-bound when embedded, process
        # default otherwise
        from ..observability.explain import default_decision_explainer

        self.explain = explain if explain is not None \
            else default_decision_explainer
        # overload control (resilience/controller.py): the shed-ladder
        # gate every request passes; registry-bound when embedded,
        # process default otherwise (disabled + L0 until bootstrap
        # configures it — one integer read per request)
        from ..resilience.controller import default_degradation_controller
        from ..resilience.priority import PriorityResolver

        self.resilience = resilience if resilience is not None \
            else default_degradation_controller
        self.priority = PriorityResolver.from_config(
            cfg.resilience_config())
        self._cfg_hash: Optional[str] = None  # lazy (record provenance)

        extra = []
        if engine is not None:
            from ..signals.learned import build_learned_evaluators

            extra = build_learned_evaluators(engine, cfg)
        # MCP-served classifiers (pkg/classification/mcp_classifier.go):
        # remote classify tools join the signal fan-out, fail-open like
        # every family (lazy connect on first evaluate)
        for spec in (cfg.mcp or {}).get("classifiers", []) or []:
            try:
                from ..mcp import MCPClassifySignal, create_client

                extra.append(MCPClassifySignal(
                    create_client(spec), cfg.signals.domains,
                    tool_name=spec.get("tool", "classify_text"),
                    threshold=float(spec.get("threshold", 0.0))))
            except Exception as exc:
                component_event("router", "mcp_classifier_skipped",
                                error=str(exc), level="warning")
        # external model clients (vllm_classifier.go + pkg/embedding):
        # a vLLM-served guard joins the jailbreak family and a remote
        # OpenAI-compatible embedding provider backs the embedding
        # families — each only when no local task covers the role
        self._remote_embedder_cache = None
        if getattr(cfg, "external_models", None):
            from ..signals.remote import (
                build_external_evaluators,
                embedding_engine_from_config,
            )

            try:
                self._remote_embedder_cache = \
                    embedding_engine_from_config(cfg)
            except Exception as exc:
                component_event("router", "external_model_skipped",
                                role="embedding", error=str(exc),
                                level="warning")
            remote_evs, replaced = build_external_evaluators(
                cfg, engine,
                remote_embedder=self._remote_embedder_cache)
            if replaced:
                extra = [e for e in extra
                         if type(e).__name__ not in replaced]
            extra += remote_evs
        self.dispatcher = build_heuristic_dispatcher(cfg, extra=extra)
        self.decision_engine = DecisionEngine(cfg.decisions, cfg.strategy)
        # learned-family lists per dispatcher, frozen at construction:
        # the resilience gate reads them per request while degraded, and
        # the evaluator set only changes on a router rebuild
        self._learned_types: Dict[int, List[str]] = {
            id(self.dispatcher): self.dispatcher.learned_types()}
        # recipe-aware routing (pkg/config/recipes.go + canonical
        # entrypoints): each named profile gets its own dispatcher and
        # decision engine at construction time; per-request resolution is
        # a dict lookup, never a rebuild
        self._recipe_engines: Dict[str, tuple] = {}
        if cfg.recipes:
            import dataclasses as _dc

            for rec in cfg.recipes:
                sub_cfg = _dc.replace(
                    cfg, signals=rec.signals, projections=rec.projections,
                    decisions=rec.decisions, strategy=rec.strategy)
                self._recipe_engines[rec.name] = (
                    build_heuristic_dispatcher(sub_cfg, extra=extra),
                    DecisionEngine(rec.decisions, rec.strategy))
            for disp, _ in self._recipe_engines.values():
                self._learned_types[id(disp)] = disp.learned_types()
        self.rate_limiter = RateLimiter.from_config(cfg.ratelimit)
        sp_cfg = cfg.skip_processing or {}
        self._skip_enabled = bool(sp_cfg.get("enabled", False))
        self._allow_skip_signals_header = bool(
            sp_cfg.get("allow_skip_signals_header", False))
        self._skip_signals_cfg = [str(s) for s in
                                  (sp_cfg.get("skip_signals", []) or [])]
        pc_cfg = cfg.prompt_compression or {}
        self.compressor = PromptCompressor(
            profile=pc_cfg.get("profile", "default"),
            target_ratio=float(pc_cfg.get("target_ratio", 0.5)),
        ) if pc_cfg.get("enabled") else None
        self.pc_min_tokens = int(pc_cfg.get("min_tokens", 512))

        # semantic_cache.embedding_model selects WHICH embedding task
        # backs the cache (a cheaper/smaller model than the signal
        # families'); empty = the router's default embedding task
        cache_task = cfg.semantic_cache.embedding_model or embedding_task
        if cache is not None:
            self.cache = cache
        elif cfg.semantic_cache.enabled and engine is not None \
                and engine.has_task(cache_task):
            self.cache = build_cache(
                cfg.semantic_cache,
                lambda text: engine.embed(cache_task, [text])[0])
        elif cfg.semantic_cache.enabled \
                and self._remote_embedder_cache is not None:
            # no local embedding task, but a remote provider is
            # configured (pkg/embedding backing the cache embedder) —
            # the same provider instance the signal families use
            remote_embed = self._remote_embedder_cache
            self.cache = build_cache(
                cfg.semantic_cache,
                lambda text: remote_embed.embed("embedding", [text])[0])
        else:
            self.cache = None

        self.model_cards = {m.name: m for m in cfg.model_cards}

        # router learning (pkg/extproc/router_learning*.go): outcome-
        # driven adaptation over the decision's candidates + session
        # protection; disabled unless configured
        self.learning = None
        if (cfg.learning or {}).get("enabled"):
            from ..learning import RouterLearning

            self.learning = RouterLearning(
                cfg.learning,
                model_costs={m.name: float(
                    (m.pricing or {}).get("prompt", 0.0))
                    for m in cfg.model_cards},
                quality_seeds={m.name: m.quality_score
                               for m in cfg.model_cards
                               if m.quality_score > 0})
        # operator-configured tools database for auto-selection; its
        # description embeddings are static config → computed once on
        # first use, not per request
        self._tools_db: List[dict] = list(
            (cfg.tool_selection or {}).get("tools", []) or [])
        self._tools_db_embs = None
        self._selectors: Dict[str, Any] = {}
        self.response_hooks: List[Any] = []  # replay/learning recorders
        # optional subsystems (attach externally or via bootstrap)
        self.vectorstores = None  # vectorstore.VectorStoreManager
        self.memory_store = None  # memory.InMemoryMemoryStore
        # shared state plane (stateplane.StatePlane): attached by
        # bootstrap when stateplane.enabled; None = single-process
        # posture, zero reads on the hot path
        self.stateplane = None
        # learned routing flywheel (flywheel.FlywheelController):
        # attached by bootstrap when flywheel.enabled; None = zero
        # flywheel work anywhere on the hot path
        self.flywheel = None
        # upstream resilience plane (resilience.upstream.UpstreamHealth):
        # attached by bootstrap when resilience.upstream.enabled; None =
        # no health mask, no fallback export — byte-identical routing
        self.upstream_health = None
        # decision-aware signal cascade (engine.cascade.CascadeEvaluator):
        # attached by bootstrap when engine.cascade.enabled; None = the
        # plain full fan-out, byte-identical routing
        self.cascade = None

    def skip_requested(self, headers: Dict[str, str]) -> bool:
        """True when the (operator-enabled) skip-processing header is on
        this request — streamed frontends use it to pass chunks through
        without buffering (handleRequestBodyDispatch,
        processor_core.go:31)."""
        headers = {k.lower(): v for k, v in (headers or {}).items()}
        return self._skip_enabled and headers.get(
            H.SKIP_PROCESSING, "").lower() in ("1", "true")

    # ------------------------------------------------------------------
    # request path
    # ------------------------------------------------------------------

    def _engines_for_model(self, model: str):
        """(dispatcher, decision_engine, via_entrypoint) for a request
        model name: an entrypoint's virtual name selects its recipe's
        engines (recipes.go RecipeForRequestModel); everything else uses
        the default profile. evaluate_signals() resolves through the SAME
        table so a streamed prefetch can never evaluate under a different
        profile than route()."""
        if self._recipe_engines or self.cfg.entrypoints:
            rec = self.cfg.recipe_for_request_model(model)
            if rec is not None:
                pair = self._recipe_engines.get(rec.name)
                if pair is not None:
                    return pair[0], pair[1], True
                return self.dispatcher, self.decision_engine, True
        return self.dispatcher, self.decision_engine, False

    def _prepare_signal_view(self, ctx, headers: Dict[str, str],
                             compress: bool = True) -> List[str]:
        """The ONE place that decides what reaches the classifiers:
        applies prompt compression to ``ctx`` in-place and returns the
        skip-signals list. route() and evaluate_signals() both call this —
        the streamed prefetch's signal reuse is only sound if the two
        paths can never drift.  ``compress=False`` is the L1
        shed-optional posture: compression saves backend tokens at the
        price of router CPU, exactly the trade an overloaded router
        stops making."""
        if compress and self.compressor is not None \
                and ctx.approx_token_count() >= self.pc_min_tokens:
            ctx._user_text = self.compressor.compress(ctx.user_text).text
        # Signal families are dropped from operator config; the request
        # header is honored only behind the same opt-in (a client must not
        # be able to empty e.g. the pii family and dodge the block policy).
        skip = list(self._skip_signals_cfg)
        if self._skip_enabled and self._allow_skip_signals_header:
            skip += [s.strip() for s in
                     headers.get("x-vsr-skip-signals", "").split(",")
                     if s.strip()]
        return skip

    def _compress_allowed(self) -> bool:
        """Prompt compression is optional work: shed while the ladder
        is at L1+.  The ONE read route() and evaluate_signals() share,
        so a streamed prefetch's (possibly compressed) signal view can
        never diverge from the inline path's."""
        return self.resilience is None \
            or not self.resilience.shed_optional_active()

    def begin_pending_trace(self, headers: Optional[Dict[str, str]] = None):
        """Pre-mint the (trace_id, root_span_id) a future route() call
        will adopt — the streamed-prefetch trace seam.  The extproc's
        early signal evaluation runs BEFORE route() opens its root span;
        a prefetch enqueued with this context parents its spans under
        the root span the request will actually get, instead of
        orphaning them in a throwaway trace."""
        from ..observability.tracing import PendingTrace, new_span_id

        headers = {k.lower(): v for k, v in (headers or {}).items()}
        trace_id, parent = self.tracer.extract(headers)
        return PendingTrace(self.tracer, trace_id, new_span_id(), parent)

    def evaluate_signals(self, body: Dict[str, Any],
                         headers: Optional[Dict[str, str]] = None,
                         pending=None):
        """Signal extraction EXACTLY as route() performs it (compression
        + operator skip config) — the overlap-prefetch seam for streamed
        frontends: a chunked body whose messages array is complete can
        start classification while the rest of the body arrives
        (processor_req_body_streamed.go early-detection role).
        ``pending`` (begin_pending_trace) parents the evaluation's spans
        under the request's future router.route root span."""
        headers = {k.lower(): v for k, v in (headers or {}).items()}
        ctx = RequestContext.from_openai_body(body, headers)
        compress = self._compress_allowed()
        skip = self._prepare_signal_view(ctx, headers, compress=compress)
        dispatcher, _, _ = self._engines_for_model(ctx.model)
        # the degradation ladder gates the PREFETCH too: a browned-out
        # priority class must not burn fused-bank capacity on an early
        # evaluation the inline path would have skipped (read-only —
        # shed/admission stay in route(), which can answer the request)
        if self.resilience is not None and self.resilience.level() > 0:
            try:
                if self.resilience.browned_out(
                        self.priority.resolve(ctx)):
                    skip = skip + self._learned_families(
                        dispatcher,
                        getattr(self.resilience, "brownout_keep", ()))
            except Exception:
                pass
        if pending is None:
            signals, report = dispatcher.evaluate(ctx, skip_signals=skip)
        else:
            with self.tracer.span("signals.evaluate",
                                  trace_id=pending.trace_id,
                                  parent_id=pending.root_span_id,
                                  prefetch=True):
                signals, report = dispatcher.evaluate(ctx,
                                                      skip_signals=skip)
        report.compressed_view = compress
        return signals, report

    def route(self, body: Dict[str, Any],
              headers: Optional[Dict[str, str]] = None,
              precomputed_signals=None,
              pending_trace=None) -> RouteResult:
        start = time.perf_counter()
        headers = {k.lower(): v for k, v in (headers or {}).items()}
        request_id = headers.get(H.REQUEST_ID, uuid.uuid4().hex[:16])
        # ONE root span per request, continuing the caller's W3C
        # traceparent when present (Envoy → extproc passes headers
        # through): the signal fan-out and the batcher's batch.wait/
        # batch.ride spans all hang off this trace, so a request's tail
        # latency decomposes end to end instead of ending at
        # signals.evaluate (the pre-batchtrace blind spot)
        if pending_trace is not None:
            # streamed prefetch already opened spans under these ids:
            # adopting both re-parents the early-detection signal spans
            # under THIS request's root span
            trace_id, parent_span = pending_trace.trace_id, \
                pending_trace.parent_id
        else:
            trace_id, parent_span = self.tracer.extract(headers)
        # decision-record draft: the sampling gate runs once here; every
        # capture site downstream is a no-op when rec is None
        rec = None
        if self.explain is not None:
            try:
                rec = self.explain.begin(trace_id, request_id)
            except Exception:
                rec = None
        # beside the tracer's span, the same route on the profiler's
        # clock with its trace id: an operator's profile joins every
        # engine.queue_wait to its route by id
        with self.tracer.span("router.route", trace_id=trace_id,
                              parent_id=parent_span,
                              request_id=request_id) as root, \
                route_span(trace_id):
            if pending_trace is not None:
                # adopt the pre-minted root span id BEFORE any child
                # opens (children read the parent id at creation time)
                root.span_id = pending_trace.root_span_id
            result = self._route_impl(body, headers, request_id, trace_id,
                                      start, precomputed_signals, rec=rec)
            result.trace_id = trace_id
            result.root_span_id = root.span_id
            root.set(kind=result.kind, model=result.model)
        route_done(trace_id, time.perf_counter() - start)
        # degradation echo: while the ladder is above L0 every response
        # carries the level, so clients and LBs see brownouts explicitly
        if self.resilience is not None:
            lvl = self.resilience.level()
            if lvl > 0:
                result.headers.setdefault(H.DEGRADATION, str(lvl))
                if rec is not None:
                    rec.degradation_level = max(rec.degradation_level, lvl)
        self._commit_decision_record(rec, result)
        self._flight_record(result, trace_id, request_id,
                            time.perf_counter() - start)
        return result

    def _config_hash(self) -> str:
        if self._cfg_hash is None:
            try:
                from ..config.versions import config_hash

                self._cfg_hash = config_hash(self.cfg.raw or {})
            except Exception:
                self._cfg_hash = ""
        return self._cfg_hash

    def _commit_decision_record(self, rec, result: RouteResult) -> None:
        """Freeze + ring the request's decision record (fail open:
        explainability must never hurt routing).  Passthrough and
        rate-limited requests never reach the signal fan-out, so there
        is nothing to explain — they are the only unrecorded kinds."""
        if rec is None or result.kind in ("passthrough", "rate_limited",
                                          "shed"):
            return
        try:
            record = rec.finish(
                kind=result.kind, model=result.model,
                latency_ms=result.routing_latency_s * 1e3,
                query=rec.query,
                redact_pii=self.explain.redact_pii,
                config_hash=self._config_hash())
            result.decision_record_id = self.explain.commit(record)
            result.headers[H.DECISION_RECORD] = result.decision_record_id
            self.M.decision_records.inc(kind=result.kind)
        except Exception:
            pass

    def _flight_record(self, result: RouteResult, trace_id: str,
                       request_id: str, duration_s: float) -> None:
        """Offer the finished request to the slow-request flight recorder
        (observability.flightrec); the span tree only serializes when the
        recorder admits the request, and recorder errors never surface
        into routing."""
        if self.flightrec is None:
            return
        try:
            self.flightrec.consider(
                request_id=request_id, trace_id=trace_id,
                duration_s=duration_s,
                span_provider=lambda: self.tracer.trace(trace_id),
                meta={"kind": result.kind, "model": result.model,
                      "decision": result.decision.decision.name
                      if result.decision else ""})
        except Exception:
            pass

    def _route_impl(self, body: Dict[str, Any], headers: Dict[str, str],
                    request_id: str, trace_id: str, start: float,
                    precomputed_signals=None, rec=None) -> RouteResult:
        ctx = RequestContext.from_openai_body(body, headers)

        # rate limit (processor_req_body_prepare.go:143-170) — runs BEFORE
        # any client-controlled skip so a bypass header can't evade limits
        rl = self.rate_limiter.check(ctx.user_id, ctx.model)
        if not rl.allowed:
            return RouteResult(
                kind="rate_limited", status=429, request_id=request_id,
                response_body={"error": {
                    "message": "rate limit exceeded",
                    "type": "rate_limit_exceeded",
                    "retry_after": round(rl.retry_after_s, 2)}},
                headers={"retry-after": str(int(rl.retry_after_s) + 1)})

        # x-vsr-skip-processing is honored ONLY when the operator enabled it
        # (SkipProcessingConfig.Enabled, pkg/config/config.go:186 — default
        # disabled; an unauthenticated client must not get passthrough)
        if self.skip_requested(headers):
            return RouteResult(kind="passthrough", body=body,
                               request_id=request_id)

        # overload gate (resilience/controller.py): the shed ladder
        # speaks BEFORE any signal work.  L0 is one integer read; the
        # gate itself fails open — a broken controller must degrade to
        # full service, never to an outage.  Engines resolve first so
        # the gate costs the request's ACTUAL dispatcher (an entrypoint
        # profile may fan out a different learned set).
        dispatcher, decision_engine, via_entrypoint = \
            self._engines_for_model(ctx.model)
        learned = self._learned_families(dispatcher)
        disp = None
        if self.resilience is not None \
                and self.resilience.level() > 0:
            try:
                disp = self.resilience.admit(
                    self.priority.resolve(ctx),
                    n_signals=len(learned) or 1)
            except Exception:
                disp = None
        if disp is not None and rec is not None:
            rec.degradation_level = disp.level
        if disp is not None and disp.action == "shed":
            # L3/L4 admission: 429 + Retry-After, like the rate limiter
            # but load-driven (DAGOR-style priority shedding)
            return RouteResult(
                kind="shed", status=429, request_id=request_id,
                response_body={"error": {
                    "message": "router overloaded — request shed "
                               f"({disp.reason})",
                    "type": "overloaded",
                    "retry_after": round(disp.retry_after_s, 2)}},
                headers={"retry-after": str(int(disp.retry_after_s) + 1),
                         H.DEGRADATION: str(disp.level),
                         H.PRIORITY: disp.priority})
        if disp is not None and disp.fail_static:
            return self._fail_static(body, ctx, headers, request_id,
                                     trace_id, start, disp, rec=rec)

        # compression + skip config — shared with evaluate_signals() so a
        # prefetched view and the inline view can never diverge (both
        # read _compress_allowed; when signals WERE prefetched the
        # prefetch's recorded decision wins outright, so a ladder
        # transition between prefetch and route can't make ctx.user_text
        # diverge from the text the signals saw). The compression
        # side-effect on ctx is needed even when signals were prefetched:
        # cache lookup / selection / memory all read ctx.user_text
        # downstream.
        compress = self._compress_allowed()
        if precomputed_signals is not None:
            recorded = getattr(precomputed_signals[1],
                               "compressed_view", None)
            if recorded is not None:
                compress = recorded
        skip = self._prepare_signal_view(ctx, headers, compress=compress)
        browned = (disp is not None and not disp.use_learned
                   and precomputed_signals is None)
        if browned and self.cascade is None:
            # L2 brownout: this request's priority class routes on
            # heuristics alone — engine-backed families are skipped,
            # reserving fused-bank capacity for higher classes, EXCEPT
            # the safety floor (disp.keep_families, default jailbreak):
            # browning out the abuse screen is never the right trade.
            # (A streamed prefetch already paid the forward; keep it.)
            # With the cascade attached the same ladder level degrades
            # to "truncate the cascade earlier" instead (see below) —
            # shedding computation, not whole families.
            skip = skip + self._learned_families(dispatcher,
                                                 disp.keep_families)
        if precomputed_signals is not None:
            # streamed-frontend overlap: signals were evaluated while
            # the body was still arriving (same text, same skip config,
            # same recipe — _engines_for_model on both paths)
            signals, report = precomputed_signals
        elif self.cascade is not None:
            with self.tracer.span("signals.evaluate",
                                     request_id=request_id):
                signals, report = self.cascade.evaluate(
                    ctx, dispatcher, decision_engine,
                    signals_cfg=self._signals_cfg_for(dispatcher),
                    brownout=browned, skip_signals=skip)
        else:
            with self.tracer.span("signals.evaluate",
                                     request_id=request_id):
                signals, report = dispatcher.evaluate(
                    ctx, skip_signals=skip)
        for family, res in report.results.items():
            # trace-id exemplar: a slow signal-latency bucket links to a
            # trace that landed there (no-op unless exemplars enabled)
            self.M.signal_latency.observe(res.latency_s, family=family,
                                          exemplar=trace_id)
            if res.error:
                # fail-open families are an SLO input: the in-process
                # monitor divides this by the evaluation count
                self.M.signal_errors.inc(family=family)
        if rec is not None:
            rec.query = ctx.user_text
            rec.capture_signals(signals, report, self.explain.redact_pii)
            if report.cascade is not None:
                rec.capture_cascade(report.cascade)

        # explainability: the trace list makes the engine capture EVERY
        # decision's full rule tree (decision.engine.explain_rule_node),
        # one evaluation either way
        decision_trace = [] if rec is not None else None
        with self.tracer.decision_span():
            decision_res = decision_engine.evaluate(signals,
                                                    trace=decision_trace)
        self.M.decision_latency.observe(decision_engine.last_eval_latency_s)
        if rec is not None:
            rec.capture_rule_trace(decision_trace)

        result = RouteResult(
            kind="route", request_id=request_id, signals=signals,
            report=report, decision=decision_res, body=dict(body))

        if decision_res is None:
            # fall back to the configured default model; an entrypoint's
            # virtual name must never reach a backend (recipes.go:24-29),
            # so the recipe path falls to the model catalog instead
            if via_entrypoint and not self.cfg.default_model:
                result.model = (self.cfg.model_cards[0].name
                                if self.cfg.model_cards else ctx.model)
            else:
                result.model = self.cfg.default_model or ctx.model
            result.headers = {H.SCHEMA: H.SCHEMA_VERSION,
                              H.MODEL: result.model,
                              H.REQUEST_ID: request_id}
            self._stamp_affinity(result, ctx)
            self._finalize_body(result, ctx, None)
            self.M.decision_fallbacks.inc(reason="no_decision_matched")
            if rec is not None:
                rec.fallback_reason = "no_decision_matched"
            result.routing_latency_s = time.perf_counter() - start
            self.M.routing_latency.observe(result.routing_latency_s,
                                           exemplar=trace_id,
                                           model=result.model)
            return result

        decision = decision_res.decision
        self.M.decision_matches.inc(name=decision.name)
        for rule in decision_res.matched_rules:
            # rule-hit frequency (Decisions dashboard row): bounded by
            # the configured rule set
            self.M.rule_hits.inc(rule=rule, decision=decision.name)
        if rec is not None:
            rec.capture_decision(decision_res, decision_engine.strategy)

        # -- pre-routing plugins ---------------------------------------
        blocked = self._apply_policy_plugins(decision, signals, ctx,
                                             result, rec=rec)
        if blocked is not None:
            blocked.routing_latency_s = time.perf_counter() - start
            self.M.routing_latency.observe(blocked.routing_latency_s,
                                           exemplar=trace_id,
                                           model=blocked.model)
            return blocked

        cache_hit = self._check_cache(decision, ctx, result, rec=rec)
        if cache_hit is not None:
            self._stamp_affinity(cache_hit, ctx)
            cache_hit.routing_latency_s = time.perf_counter() - start
            self.M.routing_latency.observe(cache_hit.routing_latency_s,
                                           exemplar=trace_id,
                                           model=cache_hit.model)
            return cache_hit

        # -- selection --------------------------------------------------
        ref, reason = self._select_model(decision, ctx, signals)
        if self.learning is not None and decision.model_refs:
            # outcome-driven adaptation may propose a different
            # candidate (applyRouterLearning role); unknown proposals
            # never escape the decision's own candidate set
            adaptations = dict(
                (decision.extra or {}).get("adaptations", {}) or {})
            learned = self.learning.apply(
                decision.name,
                [r.model for r in decision.model_refs],
                ref.model, headers=ctx.headers, tier=decision.tier,
                mode=adaptations.get("mode"))
            if learned != ref.model:
                new_ref = next((r for r in decision.model_refs
                                if r.model == learned), None)
                if new_ref is not None:
                    ref = new_ref
                    reason = f"{reason} → learning:{learned}"
        if self.flywheel is not None:
            # flywheel shadow/canary hook: shadow logs the candidate
            # policy's choice into the decision record (zero routing
            # effect); canary returns an override ref for the
            # deterministic per-trace-id fraction.  Fail-open — a
            # broken flywheel must never touch routing.
            try:
                override = self.flywheel.on_route(
                    decision, decision.model_refs or [ref], ref, rec,
                    signals, trace_id=trace_id,
                    priority=self.priority.resolve(ctx),
                    query=ctx.user_text)
                if override is not None:
                    ref = override
                    reason = f"{reason} → flywheel:canary"
            except Exception:
                pass
        result.model = ref.model
        result.selection_reason = reason
        if reason.startswith("selector error"):
            self.M.decision_fallbacks.inc(reason="selector_error")
            if rec is not None:
                rec.fallback_reason = "selector_error"
        if rec is not None:
            self._capture_selection(rec, decision, ref, reason, ctx,
                                    signals)

        algo = str(decision.algorithm.get("type", "static"))
        if algo in LOOPER_ALGORITHMS:
            result.looper_algorithm = algo

        # -- request mutation ------------------------------------------
        self._apply_mutation_plugins(decision, ref, ctx, result)
        self._finalize_body(result, ctx, ref)

        if self.upstream_health is not None:
            # ranked next-best candidates for budgeted failover: the
            # reverse-proxy path re-routes through them on upstream
            # failure; the extproc path exports them so an Envoy retry
            # policy can do the same (deploy/envoy/retry-policy.yaml)
            alts = self._ranked_alternates(decision, ref, ctx, signals)
            if alts:
                result.fallback_models = alts
                result.headers[H.FALLBACK_MODELS] = ",".join(alts)

        category = next((n for n in signals.matches.get("domain", ())), "")
        result.headers.update(H.decision_headers(
            decision.name, ref.model, category=category,
            use_reasoning=ref.use_reasoning,
            reasoning_effort=ref.reasoning_effort,
            matched_rules=decision_res.matched_rules))
        result.headers[H.REQUEST_ID] = request_id
        self._stamp_affinity(result, ctx)

        self.M.model_requests.inc(model=ref.model, decision=decision.name)
        result.routing_latency_s = time.perf_counter() - start
        self.M.routing_latency.observe(result.routing_latency_s,
                                       exemplar=trace_id,
                                       model=ref.model)
        component_event("router", "routed", request_id=request_id,
                        decision=decision.name, model=ref.model,
                        latency_ms=round(result.routing_latency_s * 1e3, 2))
        return result

    def _stamp_affinity(self, result: "RouteResult",
                        ctx: RequestContext) -> None:
        """Replica affinity (stateplane ring): which replica's hot
        local state — EncodingCache rows, fused-bank memos — this
        prompt belongs on.  An affinity-aware LB keys its hashing off
        this echo; one blake2b + ring lookup, only when a plane is
        attached, on every routed response (matched or fallback)."""
        if self.stateplane is not None:
            try:
                result.headers[H.AFFINITY] = \
                    self.stateplane.owner_of(ctx.user_text)
            except Exception:
                pass

    def _learned_families(self, dispatcher, keep=()) -> List[str]:
        """Engine-backed signal families for this dispatcher, minus the
        brownout safety floor ``keep`` — the ONE place the keep-filter
        semantics live for both the prefetch and inline brownout paths
        (mirrors SignalDispatcher.learned_types(keep=), reading the
        construction-time memo instead of rescanning evaluators)."""
        types = self._learned_types.get(id(dispatcher))
        if types is None:  # carry-over dispatcher from a hot swap
            types = dispatcher.learned_types()
        return [t for t in types if t not in keep] if keep \
            else list(types)

    def _signals_cfg_for(self, dispatcher):
        """The SignalsConfig a dispatcher was built from — the cascade
        planner resolves projection-partition members to their feeder
        families through it (build_plan).  Recipe dispatchers map back
        to their recipe's signal block; unknown dispatchers (carry-over
        from a hot swap) return None and the planner goes
        conservative."""
        if dispatcher is self.dispatcher:
            return self.cfg.signals
        for name, (disp, _eng) in self._recipe_engines.items():
            if disp is dispatcher:
                rec = self.cfg.recipe_by_name(name)
                return rec.signals if rec is not None else None
        return None

    def _fail_static(self, body: Dict[str, Any], ctx: RequestContext,
                     headers: Dict[str, str], request_id: str,
                     trace_id: str, start: float, disp,
                     rec=None) -> RouteResult:
        """L4 fail-static: route to the configured static model with
        ZERO signal extraction — no classifier forwards, no cache, no
        plugins.  The response is still a valid routed request (the
        reference's fail-open posture, made an explicit ladder rung
        instead of an accident of a dead engine)."""
        model = ""
        if self.resilience is not None:
            model = getattr(self.resilience, "fail_static_model", "")
        model = model or self.cfg.default_model \
            or (self.cfg.model_cards[0].name if self.cfg.model_cards
                else ctx.model)
        result = RouteResult(
            kind="route", request_id=request_id, model=model,
            body=dict(body), selection_reason="fail_static")
        self._finalize_body(result, ctx, None)
        result.headers = {H.SCHEMA: H.SCHEMA_VERSION, H.MODEL: model,
                          H.REQUEST_ID: request_id,
                          H.DEGRADATION: str(disp.level),
                          H.PRIORITY: disp.priority}
        if rec is not None:
            rec.fallback_reason = "fail_static"
            rec.degradation_level = disp.level
        self.M.decision_fallbacks.inc(reason="fail_static")
        self.M.model_requests.inc(model=model, decision="fail_static")
        result.routing_latency_s = time.perf_counter() - start
        self.M.routing_latency.observe(result.routing_latency_s,
                                       exemplar=trace_id, model=model)
        return result

    # -- plugin stages -----------------------------------------------------

    def _selection_ctx(self, decision: Decision, ctx: RequestContext,
                       signals: SignalMatches,
                       embed_fn=None) -> SelectionContext:
        """The ONE SelectionContext construction — selection, the
        decision-record breakdown, and upstream fallback ranking must
        never drift on what a selector gets to see."""
        return SelectionContext(
            query=ctx.user_text,
            decision_name=decision.name,
            category=next(iter(signals.matches.get("domain", ())), ""),
            session_id=ctx.headers.get("x-session-id", ""),
            user_id=ctx.user_id,
            signals=signals,
            token_count=ctx.approx_token_count(),
            model_cards=self.model_cards,
            embed_fn=embed_fn)

    def _capture_selection(self, rec, decision: Decision, ref: ModelRef,
                           reason: str, ctx: RequestContext,
                           signals: SignalMatches) -> None:
        """Per-candidate score breakdown for the decision record (the
        audit view of whichever selector ran).  Read-only and embed-free
        — breakdown must never add device work to the hot path."""
        try:
            algo_type = str((decision.algorithm or {}).get("type",
                                                           "static"))
            refs = decision.model_refs or []
            breakdown: List[dict] = []
            if len(refs) <= 1:
                breakdown = [{"model": r.model, "score": 1.0,
                              "components": {"single_candidate": True}}
                             for r in refs]
            elif algo_type in LOOPER_ALGORITHMS:
                breakdown = [{"model": r.model, "score": r.weight,
                              "components": {"weight": r.weight,
                                             "looper": algo_type}}
                             for r in refs]
            else:
                selector = self._selectors.get(decision.name)
                fn = getattr(selector, "score_breakdown", None)
                if fn is not None:
                    breakdown = fn(refs, self._selection_ctx(
                        decision, ctx, signals))
            rec.capture_selection(algo_type, reason, ref.model, breakdown)
        except Exception:
            rec.capture_selection("", reason, ref.model, [])

    def _ranked_alternates(self, decision: Decision, chosen: ModelRef,
                           ctx: RequestContext,
                           signals: SignalMatches) -> List[str]:
        """Next-best candidate models after ``chosen``, best first:
        selector score (score_breakdown when the selector exposes it,
        configured weight otherwise) re-ranked by upstream health score
        and filtered of open circuits.  Read-only and embed-free — this
        must never add device work; fail-open to no alternates."""
        try:
            refs = [r for r in (decision.model_refs or [])
                    if r.model != chosen.model]
            if not refs:
                return []
            scores: Dict[str, float] = {}
            selector = self._selectors.get(decision.name)
            fn = getattr(selector, "score_breakdown", None)
            if fn is not None:
                try:
                    for row in fn(decision.model_refs,
                                  self._selection_ctx(decision, ctx,
                                                      signals)):
                        scores[str(row.get("model", ""))] = \
                            float(row.get("score", 0.0))
                except Exception:
                    scores = {}
            up = self.upstream_health
            ranked = sorted(
                refs, key=lambda r: -(scores.get(r.model, r.weight)
                                      * up.health_score(r.model)))
            return [r.model for r in ranked
                    if not up.model_open(r.model)][:3]
        except Exception:
            return []

    def _apply_policy_plugins(self, decision: Decision,
                              signals: SignalMatches, ctx: RequestContext,
                              result: RouteResult,
                              rec=None) -> Optional[RouteResult]:
        fast = decision.plugin("fast_response")
        if fast is not None and fast.enabled:
            content = fast.configuration.get(
                "response", "Request handled by policy.")
            self.M.jailbreak_blocks.inc(decision=decision.name)
            if rec is not None:
                rec.capture_plugin("fast_response", "blocked",
                                   decision=decision.name)
            return RouteResult(
                kind="blocked", status=200, request_id=result.request_id,
                decision=result.decision, signals=signals,
                response_body=_immediate_chat_completion(content),
                headers={H.JAILBREAK_BLOCKED: "true",
                         H.DECISION: decision.name})

        pii_plugin = decision.plugin("pii")
        pii_hits = signals.matches.get("pii", [])
        if pii_hits:
            self.M.pii_violations.inc(decision=decision.name)
            action = (pii_plugin.configuration.get("action", "header")
                      if pii_plugin else "header")
            if action == "block":
                if rec is not None:
                    rec.capture_plugin("pii", "blocked",
                                       rules=list(pii_hits))
                return RouteResult(
                    kind="blocked", status=403, request_id=result.request_id,
                    decision=result.decision, signals=signals,
                    response_body={"error": {
                        "message": "request contains disallowed PII",
                        "type": "pii_policy_violation"}},
                    headers={H.PII_VIOLATION: ",".join(pii_hits)})
            result.headers[H.PII_VIOLATION] = ",".join(pii_hits)
            if rec is not None:
                rec.capture_plugin("pii", "annotated",
                                   rules=list(pii_hits))
        return None

    def _check_cache(self, decision: Decision, ctx: RequestContext,
                     result: RouteResult, rec=None
                     ) -> Optional[RouteResult]:
        plugin = decision.plugin("semantic-cache")
        if self.cache is None or plugin is None or not plugin.enabled:
            return None
        threshold = plugin.configuration.get("similarity_threshold")
        try:
            hit = self.cache.find_similar(
                ctx.user_text,
                threshold=float(threshold) if threshold else None)
        except Exception:
            self.M.cache_lookups.inc(outcome="error")
            if rec is not None:
                rec.capture_plugin("semantic-cache", "error")
            return None
        if hit is None:
            self.M.cache_lookups.inc(outcome="miss")
            if rec is not None:
                rec.capture_plugin("semantic-cache", "miss")
            return None
        self.M.cache_lookups.inc(outcome="hit")
        if rec is not None:
            rec.capture_plugin("semantic-cache", "hit",
                               model=hit.model or "cache")
        return RouteResult(
            kind="cache_hit", request_id=result.request_id,
            decision=result.decision, signals=result.signals,
            model=hit.model or "cache",
            response_body=_immediate_chat_completion(hit.response,
                                                     model=hit.model or "cache"),
            headers={H.CACHE_HIT: "true", H.DECISION: decision.name})

    def _upstream_mask(self, refs: List[ModelRef]) -> tuple:
        """Drop candidates whose every endpoint circuit is open
        (resilience/upstream.py) — an unhealthy model is never chosen
        while alternatives exist.  Fail-open twice over: masking never
        empties the candidate set, and plane errors never mask at
        all."""
        if self.upstream_health is None or len(refs) <= 1:
            return refs, ()
        try:
            masked = tuple(sorted({r.model for r in refs
                                   if self.upstream_health.model_open(
                                       r.model)}))
            if masked and len(masked) < len(refs):
                return [r for r in refs
                        if r.model not in masked], masked
        except Exception:
            pass
        return refs, ()

    def _select_model(self, decision: Decision, ctx: RequestContext,
                      signals: SignalMatches) -> tuple[ModelRef, str]:
        refs = decision.model_refs or [
            ModelRef(model=self.cfg.default_model or ctx.model)]
        refs, masked = self._upstream_mask(refs)
        if len(refs) == 1:
            return refs[0], ("single candidate" if not masked else
                             "single healthy candidate (upstream mask: "
                             + ",".join(masked) + ")")
        algo = dict(decision.algorithm or {})
        algo_type = str(algo.get("type", "static"))
        if algo_type in LOOPER_ALGORITHMS:
            # looper strategies execute multiple models downstream; the
            # primary ref here is the highest-weight candidate
            best = max(refs, key=lambda r: r.weight)
            return best, f"looper:{algo_type}"
        selector = self._selectors.get(decision.name)
        if selector is None:
            kwargs = {k: v for k, v in algo.items() if k != "type"}
            kwargs.pop("on_error", None)
            artifact = kwargs.pop("artifact", "")
            if artifact:
                # offline-trained artifact (training/selection_train.py →
                # pkg/modelselection persistence role): the JSON file
                # cold-starts the selector; online learning continues on
                # top. A missing/corrupt artifact falls back to the
                # untrained algorithm rather than failing the request.
                try:
                    from ..training.selection_train import load_selector

                    selector = load_selector(str(artifact))
                except Exception as exc:
                    component_event(
                        "selection", "artifact_load_failed",
                        decision=decision.name, artifact=str(artifact),
                        error=str(exc), level="warning")
            if selector is None:
                try:
                    selector = selectors.create(algo_type, **kwargs)
                except (KeyError, TypeError):
                    selector = selectors.create("static")
            self._selectors[decision.name] = selector
        embed_fn = None
        if self.engine is not None and self.engine.has_task(self.embedding_task):
            eng = self.engine
            task = self.embedding_task
            embed_fn = lambda text: eng.embed(task, [text])[0]
        sctx = self._selection_ctx(decision, ctx, signals,
                                   embed_fn=embed_fn)
        mask_note = (" (upstream mask: " + ",".join(masked) + ")") \
            if masked else ""
        try:
            res = selector.select(refs, sctx)
            return res.ref, res.reason + mask_note
        except Exception:
            return refs[0], "selector error → first candidate" + mask_note

    def _apply_mutation_plugins(self, decision: Decision, ref: ModelRef,
                                ctx: RequestContext,
                                result: RouteResult) -> None:
        body = result.body

        # Order: decision system-prompt first (replace/insert applies to
        # the ORIGINAL system message), then memory/RAG context prepend
        # ahead of it — retrieval context is never clobbered by
        # mode=replace.
        sp = decision.plugin("system_prompt")
        if sp is not None and sp.enabled and body is not None:
            prompt = sp.configuration.get("system_prompt", "")
            mode = sp.configuration.get("mode", "insert")
            if prompt:
                messages = list(body.get("messages", []))
                has_system = messages and messages[0].get("role") == "system"
                if has_system and mode == "replace":
                    messages[0] = {"role": "system", "content": prompt}
                elif has_system and mode == "insert":
                    messages[0] = {
                        "role": "system",
                        "content": prompt + "\n" + messages[0].get("content", "")}
                elif not has_system:
                    messages = [{"role": "system", "content": prompt}] + messages
                body["messages"] = messages
                result.headers[H.INJECTED_SYSTEM_PROMPT] = "true"

        # memory retrieval (req_filter_memory*, memory search + rewrite)
        mem = decision.plugin("memory")
        if mem is not None and mem.enabled and self.memory_store is not None \
                and body is not None and ctx.user_id:
            try:
                items = self.memory_store.search(
                    ctx.user_id, ctx.user_text,
                    limit=int(mem.configuration.get("retrieval_limit", 5)),
                    threshold=float(
                        mem.configuration.get("similarity_threshold", 0.0)))
                if items:
                    facts = "; ".join(i.text for i in items)
                    body["messages"] = (
                        [{"role": "system",
                          "content": f"Known about this user: {facts}"}]
                        + list(body.get("messages", [])))
                    result.headers["x-vsr-memories-used"] = str(len(items))
            except Exception:
                pass

        # RAG: retrieve from the configured vector store and inject context
        # (executeRAGPlugin, req_filter_rag.go)
        rag = decision.plugin("rag")
        if rag is not None and rag.enabled and self.vectorstores is not None \
                and body is not None:
            try:
                store = self.vectorstores.get(
                    rag.configuration.get("store", "default"))
                if store is not None:
                    from ..vectorstore import format_rag_context

                    hits = store.search(
                        ctx.user_text,
                        top_k=int(rag.configuration.get("top_k", 4)),
                        threshold=float(
                            rag.configuration.get("threshold", 0.0)))
                    context = format_rag_context(
                        hits, max_chars=int(
                            rag.configuration.get("max_chars", 4000)))
                    if context:
                        body["messages"] = (
                            [{"role": "system", "content": context}]
                            + list(body.get("messages", [])))
                        result.headers["x-vsr-rag-chunks"] = str(len(hits))
            except Exception:
                pass  # fail open

        tools_plugin = decision.plugin("tools") or decision.plugin("tool_selection")
        if tools_plugin is not None and tools_plugin.enabled \
                and body is not None:
            conf = tools_plugin.configuration
            if body.get("tools"):
                body["tools"] = self._filter_tools(conf, ctx,
                                                   body["tools"])
            elif conf.get("auto_select") and self._tools_db:
                # tools-DB auto-selection: the request carries no tools;
                # inject the best-matching configured tools
                # (req_filter_tools.go auto-selection role)
                selected = self._auto_select_tools(conf, ctx)
                if selected:
                    body["tools"] = selected
                    result.headers["x-vsr-tools-injected"] = \
                        str(len(selected))

    def _filter_tools(self, conf: Dict[str, Any], ctx: RequestContext,
                      tools: List[dict]) -> List[dict]:
        """Allow/block lists + optional embedding-similarity top-k
        (req_filter_tools.go / req_tool_selection_filter_embed.go)."""
        def name_of(t: dict) -> str:
            return (t.get("function", {}) or {}).get("name", t.get("name", ""))

        allow = set(conf.get("allow_tools", []) or [])
        block = set(conf.get("block_tools", []) or [])
        out = [t for t in tools
               if (not allow or name_of(t) in allow)
               and name_of(t) not in block]
        if conf.get("semantic_selection") and self.engine is not None \
                and self.engine.has_task(self.embedding_task) and out:
            try:
                top_k = int(conf.get("top_k", 5))
                descs = [
                    f"{name_of(t)}: "
                    f"{(t.get('function', {}) or {}).get('description', '')}"
                    for t in out]
                embs = self.engine.embed(self.embedding_task, descs)
                q = self.engine.embed(self.embedding_task, [ctx.user_text])[0]
                sims = embs @ q
                thresh = float(conf.get("similarity_threshold", 0.0))
                ranked = sorted(zip(sims, range(len(out))), reverse=True)
                keep = [i for s, i in ranked[:top_k] if s >= thresh]
                if keep or not conf.get("fallback_to_empty", True):
                    out = [out[i] for i in sorted(keep)] if keep else out
                else:
                    out = []
            except Exception:
                pass  # fail open: unfiltered tools
        return out

    def _auto_select_tools(self, conf: Dict[str, Any],
                           ctx: RequestContext) -> List[dict]:
        """Pick top-k tools from the configured DB by description
        similarity; lexical overlap fallback when no embedding engine."""
        top_k = int(conf.get("top_k", 3))
        thresh = float(conf.get("similarity_threshold", 0.1))

        def name_of(t: dict) -> str:
            return (t.get("function", {}) or {}).get("name",
                                                     t.get("name", ""))

        def desc_of(t: dict) -> str:
            f = t.get("function", {}) or {}
            return f"{name_of(t)}: {f.get('description', '')}"

        try:
            if self.engine is not None \
                    and self.engine.has_task(self.embedding_task):
                if self._tools_db_embs is None:
                    self._tools_db_embs = self.engine.embed(
                        self.embedding_task,
                        [desc_of(t) for t in self._tools_db])
                q = self.engine.embed(self.embedding_task,
                                      [ctx.user_text])[0]
                sims = self._tools_db_embs @ q
            else:
                import re as _re

                q_words = set(w.lower() for w in
                              _re.findall(r"\w+", ctx.user_text))
                sims = np.asarray([
                    len(q_words & set(w.lower() for w in _re.findall(
                        r"\w+", desc_of(t)))) / (len(q_words) or 1)
                    for t in self._tools_db])
            order = np.argsort(-sims)
            return [self._tools_db[i] for i in order[:top_k]
                    if sims[i] >= thresh]
        except Exception:
            return []  # fail open: no injection

    def _finalize_body(self, result: RouteResult, ctx: RequestContext,
                       ref: Optional[ModelRef]) -> None:
        """Model rewrite + reasoning fields
        (modifyRequestBodyForAutoRouting, processor_req_body_routing.go:64)."""
        body = result.body
        if body is None:
            return
        model = result.model or (ref.model if ref else "")
        if model:
            body["model"] = model
        if ref is not None and ref.lora_name:
            body["model"] = f"{ref.model}:{ref.lora_name}"
        if ref is not None and ref.use_reasoning:
            if ref.reasoning_effort:
                body["reasoning_effort"] = ref.reasoning_effort
        elif "reasoning_effort" in (body or {}):
            body.pop("reasoning_effort", None)

    # ------------------------------------------------------------------
    # response path
    # ------------------------------------------------------------------

    def process_response(self, route: RouteResult,
                         response_body: Dict[str, Any]) -> ResponseResult:
        out = ResponseResult(body=response_body)
        content = self._response_text(response_body)
        decision = route.decision.decision if route.decision else None

        # response jailbreak screen (res_filter_jailbreak.go)
        if content and self.engine is not None \
                and self.engine.has_task("jailbreak"):
            try:
                r = self.engine.classify("jailbreak", content[:4000])
                if r.label.lower() in ("jailbreak", "unsafe") \
                        and r.confidence >= 0.8:
                    out.warnings.append("response_jailbreak")
                    out.headers[H.JAILBREAK_BLOCKED] = "response"
            except Exception:
                pass

        # hallucination detection gated on the fact-check signal
        # (res_filter_hallucination.go:19 — HaluGate token spans + NLI)
        needs_check = bool(route.signals and "needs_fact_check" in
                           route.signals.matches.get("fact_check", ()))
        halu_plugin = decision.plugin("hallucination") if decision else None
        if content and needs_check and halu_plugin is not None \
                and halu_plugin.enabled and self.engine is not None \
                and self.engine.has_task("hallucination"):
            t0 = time.perf_counter()
            try:
                spans = self._detect_hallucinations(
                    content, use_nli=bool(
                        halu_plugin.configuration.get("use_nli", True)))
                if spans:
                    out.hallucination_spans = spans
                    out.headers[H.HALLUCINATION] = "true"
                    if halu_plugin.configuration.get(
                            "include_hallucination_details"):
                        out.body.setdefault("vsr_annotations", {})[
                            "hallucination_spans"] = spans
            except Exception:
                out.headers[H.UNVERIFIED_FACTUAL] = "true"
            self.M.hallucination_latency.observe(time.perf_counter() - t0)

        if out.warnings:
            out.headers[H.WARNINGS] = ",".join(out.warnings)

        # cache update (processor_res_cache.go) — skipped while the
        # degradation ladder is at L1+ (cache WRITES are the canonical
        # optional work: an embedding forward per response that only
        # pays off later; reads stay on, hits still shed load)
        shed_writes = self.resilience is not None \
            and self.resilience.shed_optional_active()
        if self.cache is not None and route.kind == "route" and content \
                and decision is not None and not shed_writes:
            plugin = decision.plugin("semantic-cache")
            if plugin is not None and plugin.enabled and route.body:
                try:
                    ctx = RequestContext.from_openai_body(route.body)
                    self.cache.add(ctx.user_text, content, model=route.model)
                except Exception:
                    pass

        # usage/cost metrics (processor_res_usage.go + model_pricing.go)
        usage = response_body.get("usage") or {}
        if usage and route.model:
            card = self.model_cards.get(route.model)
            if card and card.pricing:
                self.M.model_cost.inc(usage_cost(usage, card.pricing),
                                 model=route.model)

        # memory auto-store after a successful exchange
        # (processor_res_memory.go)
        if self.memory_store is not None and decision is not None \
                and route.body:
            mem = decision.plugin("memory")
            if mem is not None and mem.enabled \
                    and mem.configuration.get("auto_store") :
                try:
                    ctx = RequestContext.from_openai_body(route.body)
                    if ctx.user_id:
                        # exclude system messages: router-injected context
                        # ("Known about this user", RAG blocks) must not
                        # feed back into extraction
                        convo = [m for m in route.body.get("messages", [])
                                 if m.get("role") != "system"]
                        self.memory_store.auto_store(
                            ctx.user_id,
                            convo + [{"role": "assistant",
                                      "content": content}])
                except Exception:
                    pass

        for hook in self.response_hooks:
            try:
                hook(route, response_body, out)
            except Exception:
                pass
        return out

    def _detect_hallucinations(self, content: str,
                               use_nli: bool = True) -> List[dict]:
        """HaluGate: token-level detector flags spans; the NLI explainer
        filters spans that are entailed (DetectHallucinationsWithNLI,
        semantic-router.go:2808-3016)."""
        res = self.engine.token_classify("hallucination", content,
                                         threshold=0.5)
        spans = [
            {"type": e.type, "start": e.start, "end": e.end,
             "text": e.text, "score": e.score}
            for e in res.entities if e.type.upper() not in ("O", "SUPPORTED")]
        if spans and use_nli and self.engine.has_task("nli"):
            kept = []
            for s in spans:
                r = self.engine.classify("nli", s["text"])
                if r.label.lower() != "entailment":
                    s["nli"] = r.label
                    kept.append(s)
            spans = kept
        return spans

    @staticmethod
    def _response_text(body: Dict[str, Any]) -> str:
        try:
            choices = body.get("choices") or []
            if choices:
                msg = choices[0].get("message") or {}
                return msg.get("content") or ""
        except AttributeError:
            pass
        return ""

    # ------------------------------------------------------------------
    # feedback / lifecycle
    # ------------------------------------------------------------------

    def record_feedback(self, route: RouteResult, success: bool = True,
                        quality: float = 0.0, latency_ms: float = 0.0,
                        ttft_ms: float = 0.0, verdict: str = "") -> None:
        """Feed outcome back to the decision's selector AND the learning
        experience ledgers (router_learning_outcome.go role). ``verdict``
        is one of good_fit/underpowered/overprovisioned/failed; empty
        derives from ``success``."""
        if route.decision is None:
            return
        if self.learning is not None:
            self.learning.record_outcome(
                route.decision.decision.name, route.model,
                verdict=verdict, success=success,
                latency_ms=latency_ms,
                tier=route.decision.decision.tier)
        if self.flywheel is not None and route.decision_record_id:
            # per-request reward label for the next corpus export —
            # the exact-outcome half of the flywheel's reward join
            try:
                self.flywheel.note_outcome(
                    route.decision_record_id,
                    verdict or ("good_fit" if success else "failed"),
                    quality=quality, latency_ms=latency_ms)
            except Exception:
                pass
        selector = self._selectors.get(route.decision.decision.name)
        if selector is None:
            return
        emb = None
        if self.engine is not None and self.engine.has_task(self.embedding_task) \
                and route.body:
            try:
                ctx = RequestContext.from_openai_body(route.body)
                emb = self.engine.embed(self.embedding_task,
                                        [ctx.user_text])[0]
            except Exception:
                emb = None
        query = ""
        if route.body:
            try:
                query = RequestContext.from_openai_body(route.body).user_text
            except Exception:
                query = ""
        selector.update(Feedback(
            model=route.model, success=success, quality=quality,
            latency_ms=latency_ms, ttft_ms=ttft_ms,
            query=query, query_embedding=emb,
            session_id=(route.body or {}).get("user", "")))
        if latency_ms:
            self.M.completion_latency.observe(latency_ms / 1e3, model=route.model)
        if ttft_ms:
            self.M.ttft.observe(ttft_ms / 1e3, model=route.model)

    def shutdown(self) -> None:
        self.dispatcher.shutdown()
        if self.learning is not None:
            self.learning.close()
