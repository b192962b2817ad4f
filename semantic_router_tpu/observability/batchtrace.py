"""Cross-batch trace propagation: request traces that survive the batcher.

The fused batcher executes on background dispatch threads where the
thread-local ``Tracer`` context is lost — before this module, a request's
trace ended at ``signals.evaluate`` and the hottest path (queue wait,
bucket choice, the shared trunk forward, head demux) was invisible.  The
fix mirrors how production LLM servers attribute a request's latency to
the batch iteration it rode in:

1. **Capture** — ``capture()`` snapshots the submitting thread's active
   ``(tracer, trace_id, span_id)`` into the ``BatchItem`` at enqueue time
   (engine.batcher), plus a deterministic per-trace *sampled* bit from
   the tracer's ``sample_rate``.

2. **Step span** — the batch runner opens ONE ``batch.execute`` span per
   device step (its own trace: the step is shared by many requests), with
   batch size / fill ratio / padded-vs-real rows / fused task mix / per-
   stage timings as attributes.

3. **Ride spans** — each originating request's trace receives
   ``batch.wait`` (enqueue → dispatch), ``batch.tokenize`` (host encode
   or EncodingCache hit), and ``batch.ride`` (dispatch → results)
   children, the ride span carrying an OTLP span *link* to the shared
   step span, plus per-stage child spans (stack, h2d, dispatch,
   readback, demux) so tail latency decomposes per request.

4. **One program, two clocks** — every step, traced or not, sampled or
   not, runs the SAME jitted program with no device sync but the
   readback it needs anyway.  ``BatchStep.stage()`` times the five host
   stages of a step (``stack``, ``h2d``, ``dispatch``, ``readback``,
   ``demux``) in two places at once:

   * as ``jax.profiler.TraceAnnotation`` spans (``engine.step`` around
     ``engine.step.<stage>``, constant names, the step's facts as
     keyword arguments), which land on the profiler's host plane beside
     the device's op timeline when an operator has a profiler session
     running (``/debug/profiler/start``) and cost an object and a flag
     test when none is — nothing is encoded;
   * as ``batch.<stage>`` child spans under ``batch.ride`` in a request's
     own trace when that trace is SAMPLED (``Tracer.sample_rate``,
     default 10%).  Unsampled traces keep the continuity spans above.

   A batch with no traced item emits no request span at all.  The batcher
   adds ``engine.queue_wait`` (one per dispatched item: ``trace_id``,
   ``group``, ``wait_us``) and the router ``router.route`` around a route
   and ``router.route.done`` at its end (``trace_id``, ``route_us``), so
   a profile joins an item to its route by id.

5. **A generation's time** — a generation is many steps on one thread,
   and the device idles between them.  Where that time goes is written
   around the steps, never inside them: ``engine.gen.turn`` (``group``,
   ``after``, ``block``) runs from the moment a forward's step closed to
   the moment the next one's opens — the ``engine.gen.forward`` marker,
   the counters and the generator's loop top are inside it — and the one
   after a generation's last forward until the runner has its results;
   ``engine.gen.done`` marks a generation's END and carries its counts
   (``forwards``, ``blocks``, ``tokens``) and, on the host clock, how
   its length divides into steps, turns and the finish
   (``GenerationClock``, which also feeds
   ``llm_runtime_gen_seconds_total`` for an operator without a
   session); ``engine.tokenize`` marks the end of a tokenization on the
   caller's thread (``trace_id``, ``tag``, ``tok_us``, ``tokens``,
   ``cached``), so what stood between two generations — finish,
   callers, tokenization, queue wait, the prefill's head — can be read
   off one clock.

Known tradeoff: the host stages see the device only through the readback
wait — ``dispatch`` is the enqueue, ``readback`` holds the program's whole
device time plus the transfer.  Trunk against heads is not a host stage:
it is read from the device timeline, where ``jax.named_scope`` names the
ops (``trunk``, ``pool``, ``heads``, ``token_heads``: models/modernbert.py,
engine/classify.py).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from .profiler import trace_span
from .tracing import Span, Tracer, active_span, new_span_id, new_trace_id

STEP_SPAN = "batch.execute"
RIDE_SPAN = "batch.ride"
WAIT_SPAN = "batch.wait"
TOKENIZE_SPAN = "batch.tokenize"
STAGE_PREFIX = "batch."
# the profiler-clock names (constant: facts ride as keyword arguments)
STEP_ANNOTATION = "engine.step"
STAGES = ("stack", "h2d", "dispatch", "readback", "demux")
STAGE_ANNOTATIONS = {n: f"{STEP_ANNOTATION}.{n}" for n in STAGES}
QUEUE_WAIT_ANNOTATION = "engine.queue_wait"
GEN_FORWARD_ANNOTATION = "engine.gen.forward"
GEN_TURN_ANNOTATION = "engine.gen.turn"
GEN_DONE_ANNOTATION = "engine.gen.done"
TOKENIZE_ANNOTATION = "engine.tokenize"
ROUTE_ANNOTATION = "router.route"
ROUTE_DONE_ANNOTATION = "router.route.done"


@dataclass
class TraceContext:
    """The portable slice of a request's trace: enough to emit spans into
    it from any thread, plus the tracer that owns the ring/sinks."""

    tracer: Tracer
    trace_id: str
    span_id: str
    sampled: bool = True


def _sampled(tracer: Tracer, trace_id: str) -> bool:
    """Deterministic per-trace sampling from the tracer's sample_rate:
    every span of one trace makes the same choice, so a sampled trace is
    complete and an unsampled one costs nothing downstream.  Traces the
    flight recorder force-kept (tail-based sampling) are always
    detailed, whatever the rate."""
    forced = getattr(tracer, "is_force_sampled", None)
    if forced is not None and forced(trace_id):
        return True
    from .tracing import trace_id_in_ratio

    rate = float(getattr(tracer, "sample_rate", 1.0))
    return trace_id_in_ratio(trace_id, rate, default=True)


def capture() -> Optional[TraceContext]:
    """Snapshot the calling thread's active span as a TraceContext, or
    None when no trace is open (the untraced hot path: one thread-local
    read)."""
    top = active_span()
    if top is None:
        return None
    tracer, span = top
    return TraceContext(tracer, span.trace_id, span.span_id,
                        _sampled(tracer, span.trace_id))


@contextlib.contextmanager
def activate(ctx: Optional[TraceContext], name: str, **attrs):
    """Re-establish a captured context on another thread by opening a
    named child span there (the signal fan-out's propagation seam); a
    None context degrades to a no-op."""
    if ctx is None:
        yield None
        return
    with ctx.tracer.span(name, trace_id=ctx.trace_id,
                         parent_id=ctx.span_id, **attrs) as s:
        yield s


def _mk_span(name: str, trace_id: str, parent_id: str,
             t0_pc: float, t1_pc: float, offset: float,
             **attrs) -> Span:
    """Span from monotonic endpoints: epoch pair derived via the current
    perf→epoch offset, monotonic pair kept exact for duration_s."""
    s = Span(name, trace_id, new_span_id(), parent_id,
             start_t=t0_pc + offset, attributes=dict(attrs))
    s.start_pc = t0_pc
    s.end_pc = t1_pc
    s.end_t = t1_pc + offset
    return s


class _Stage:
    """One stage of a step, on both clocks: the profiler annotation
    always, the ``(name, t0, t1)`` pair only for a sampled step."""

    __slots__ = ("_step", "_name", "_ann", "_t0")

    def __init__(self, step: "BatchStep", name: str) -> None:
        self._step, self._name = step, name
        self._ann = trace_span(STAGE_ANNOTATIONS[name])

    def __enter__(self) -> None:
        self._ann.__enter__()
        if self._step.detailed:
            self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        if self._step.detailed:
            self._step.stages.append(
                (self._name, self._t0, time.perf_counter()))
        self._ann.__exit__(*exc)


class BatchStep:
    """One device step's tracing state, from ``start_step`` to
    ``finish()``: the ``engine.step`` profiler annotation (every step),
    and, when ≥1 item carries a trace context, the step span plus every
    per-request wait/tokenize/ride span tree that ``finish()`` emits
    (call it in a ``finally`` so failing batches still trace).
    ``detailed`` is True when any of those traces is sampled: then
    ``stage()`` also keeps the host stage pairs that become
    ``batch.<stage>`` children of the sampled items' ride spans."""

    def __init__(self, name: str, traced: List[Tuple[Any, TraceContext]],
                 attrs: Dict[str, Any], detailed: bool = True,
                 facts: Optional[Dict[str, Any]] = None) -> None:
        self.trace_id = new_trace_id()
        self.span_id = new_span_id()
        self.name = name
        self.attrs = dict(attrs)
        self.traced = traced
        self.detailed = detailed
        self.start_pc = time.perf_counter()
        self.stages: List[Tuple[str, float, float]] = []
        self._finished = False
        self._ann = trace_span(STEP_ANNOTATION, **(facts or {}))
        self._ann.__enter__()

    def stage(self, name: str) -> _Stage:
        """``with step.stage("h2d"): ...`` — one of ``STAGES``."""
        return _Stage(self, name)

    def finish(self) -> None:
        if self._finished:  # idempotent: callers run it in a finally
            return
        self._finished = True
        self._ann.__exit__(None, None, None)
        if not self.traced:
            return
        end_pc = time.perf_counter()
        offset = time.time() - time.perf_counter()
        stage_attrs = {f"stage.{n}_ms": round((t1 - t0) * 1e3, 3)
                       for n, t0, t1 in self.stages}
        step = _mk_span(self.name, self.trace_id, "",
                        self.start_pc, end_pc, offset,
                        **self.attrs, **stage_attrs)
        step.span_id = self.span_id
        tracers = []
        for _, ctx in self.traced:
            if all(t is not ctx.tracer for t in tracers):
                tracers.append(ctx.tracer)
        for t in tracers:
            t.record(step)

        for item, ctx in self.traced:
            payload = getattr(item, "payload", None)
            enq = getattr(item, "enqueue_t", self.start_pc)
            wait = _mk_span(WAIT_SPAN, ctx.trace_id, ctx.span_id,
                            enq, self.start_pc, offset,
                            wait_ms=round((self.start_pc - enq) * 1e3, 3))
            ctx.tracer.record(wait)
            tok_s = float(getattr(payload, "tok_s", 0.0) or 0.0)
            if tok_s > 0.0 or getattr(payload, "tok_cached", False):
                sub = float(getattr(payload, "submit_t", enq) or enq)
                tok = _mk_span(
                    TOKENIZE_SPAN, ctx.trace_id, ctx.span_id,
                    sub - tok_s, sub, offset,
                    cache_hit=bool(getattr(payload, "tok_cached", False)))
                ctx.tracer.record(tok)
            ride = _mk_span(RIDE_SPAN, ctx.trace_id, ctx.span_id,
                            self.start_pc, end_pc, offset, **self.attrs)
            ride.add_link(self.trace_id, self.span_id)
            if ctx.sampled:
                for n, t0, t1 in self.stages:
                    ctx.tracer.record(_mk_span(
                        STAGE_PREFIX + n, ctx.trace_id, ride.span_id,
                        t0, t1, offset))
            ctx.tracer.record(ride)


def start_step(items, *, group: str, bucket: int, max_batch: int,
               padded_rows: int, flavour: str, kind: str = "fused",
               rows: Optional[int] = None, tokens_real: int = 0,
               name: str = STEP_SPAN, **more_facts: int) -> BatchStep:
    """Open one step: always the ``engine.step`` profiler annotation
    (``group``, ``flavour``, ``bucket``, ``rows``, ``padded_rows``,
    ``tokens_real``), and request tracing iff any batch item carries a
    trace context — the common untraced case is one list scan.  The step
    is ``detailed`` (host stage pairs kept) only when some traced item's
    trace is sampled."""
    traced = [(it, it.trace) for it in items
              if getattr(it, "trace", None) is not None]
    facts = {"group": group, "flavour": flavour,
             "bucket": int(bucket),
             "rows": len(items) if rows is None else int(rows),
             "padded_rows": int(padded_rows),
             "tokens_real": int(tokens_real),
             **{k: int(v) for k, v in more_facts.items()}}
    if not traced:
        return BatchStep(name, traced, {}, detailed=False, facts=facts)
    detailed = any(ctx.sampled for _, ctx in traced)
    mix: Dict[str, int] = {}
    for it in items:
        for task in getattr(getattr(it, "payload", None), "tasks", ()) or ():
            mix[task] = mix.get(task, 0) + 1
    attrs = {
        "group": group,
        "bucket": int(bucket),
        "kind": kind,
        "batch_size": len(items),
        "padded_rows": int(padded_rows),
        "real_rows": len(items),
        "fill_ratio": round(len(items) / max(1, max_batch), 4),
    }
    if mix:
        attrs["task_mix"] = ",".join(
            f"{t}:{n}" for t, n in sorted(mix.items()))
    return BatchStep(name, traced, attrs, detailed=detailed, facts=facts)


def queue_wait(trace_id: str, group: str, wait_s: float,
               **facts: int) -> None:
    """``engine.queue_wait``: one item left the batcher's queue now,
    after ``wait_s``.  The interval is in the past, where an annotation
    cannot start: the event marks its END and carries its length.
    ``facts``: what the engine says of the item's group beyond its name (a
    generative item's prompt ``bucket``: one task's short and long prompts
    wait in one queue)."""
    with trace_span(QUEUE_WAIT_ANNOTATION, trace_id=trace_id, group=group,
                    wait_us=int(wait_s * 1e6), **facts):
        pass


def gen_forward(group: str, flavour: str, load, keys=None,
                rows_per_group=None, forwards: int = 1, drafts=None,
                bucket: Optional[int] = None, cache_bytes=None,
                attn_tiles=None) -> None:
    """``engine.gen.forward``: a step of a generation ended now, and
    this is what only its readback knew: the ``forwards`` the device ran
    in it (a block generator's step is a block's loop) and ``load
    [forwards x layers, 4]`` per forward and expert layer: the busiest
    expert's routed pairs, the pairs computed, the experts that got any,
    the busiest's pairs over the mean; the facts are ``layers``, ``pairs``
    and ``experts_touched`` (sums over the forwards' layers) and
    ``load_milli`` (the ratio's mean over them, in thousandths; a dense
    decoder that reports ``load [0, 4]`` reads 0 in all four).
    ``keys [rows, 2]`` of a model with a learned selection of keys adds
    ``keys_selected`` and ``keys_visible``: what the forward's queries
    selected and what they could see, over its rows and full layers.
    ``rows_per_group`` of a prefill mapped over groups of rows adds the
    fact of that name: the rows one grouped matmul served.  ``drafts =
    (drafted, accepted, committed_tokens)`` of a step of a model that drafts
    for itself adds those three: the drafts verified (one a live row), those
    that were right, and the tokens the step committed (one or two a row).
    ``bucket``: the generation's prompt bucket (two buckets of one task
    alternate in one window).  ``cache_bytes`` of a prefill, by kind of
    state (the model's ``cache_bytes``), adds ``cache_bytes_<kind>`` for
    each: a whole K/V cache's ``full`` beside a ring's ``window``.
    ``attn_tiles = (visited, grid)`` of a prefill whose flash calls are
    handed the rows' lengths adds ``attn_tiles_visited`` and
    ``attn_tiles_grid``: the tiles folded and those the bucket's grid
    folds without the lengths, over layers and heads."""
    facts = {} if keys is None else {
        "keys_selected": int(keys[:, 0].sum(dtype="int64")),
        "keys_visible": int(keys[:, 1].sum(dtype="int64"))}
    if bucket is not None:
        facts["bucket"] = int(bucket)
    for kind, size in (cache_bytes or {}).items():
        facts[f"cache_bytes_{kind}"] = int(size)
    if rows_per_group is not None:
        facts["rows_per_group"] = int(rows_per_group)
    if attn_tiles is not None:
        facts.update(zip(("attn_tiles_visited", "attn_tiles_grid"),
                         map(int, attn_tiles)))
    if drafts is not None:
        facts.update(zip(("drafted", "accepted", "committed_tokens"),
                         map(int, drafts)))
    with trace_span(GEN_FORWARD_ANNOTATION, group=group, flavour=flavour,
                    forwards=int(forwards), layers=len(load),
                    pairs=int(load[:, 1].sum()),
                    experts_touched=int(load[:, 2].sum()),
                    load_milli=int(load[:, 3].mean() * 1000)
                    if len(load) else 0, **facts):
        pass


def route_span(trace_id: str):
    """``router.route``: a route on the profiler's clock, beside the
    tracer's span of the same name, carrying the id that its items'
    ``engine.queue_wait`` events carry."""
    return trace_span(ROUTE_ANNOTATION, trace_id=trace_id)


def route_done(trace_id: str, route_s: float) -> None:
    """``router.route.done``: a route ended now, after ``route_s``.  The
    ``router.route`` annotation around a route is lost when the profiler
    session began after the route did (a long route, a short session);
    this marker, like ``engine.queue_wait``, marks the END and carries
    the length, so every route that completes in a session is seen."""
    with trace_span(ROUTE_DONE_ANNOTATION, trace_id=trace_id,
                    route_us=int(route_s * 1e6)):
        pass


def tokenized(tag: str, tok_s: float, tokens: int, cached: bool) -> None:
    """``engine.tokenize``: a tokenization (or the lookup that spared one)
    ended now on this thread, after ``tok_s``: the marker at its END with
    its length and the calling thread's trace id (what
    ``engine.queue_wait`` and ``router.route.done`` carry; empty when
    none).  Nothing else happens here — no lock, no span, no system call:
    between two generations every caller passes this way, one after
    another (the request's own ``batch.tokenize`` span is written later
    from the same seconds, by the step its item rides)."""
    top = active_span()
    with trace_span(TOKENIZE_ANNOTATION,
                    trace_id=top[1].trace_id if top is not None else "",
                    tag=tag, tok_us=int(tok_s * 1e6), tokens=int(tokens),
                    cached=int(cached)):
        pass


class GenerationClock:
    """Where a generation's host time went, between its steps: one
    ``time.perf_counter()`` read where a step opens and one
    where it closes, so that the steps (open to close: the device wait),
    the turns between them and the finish after the last tile the
    generation exactly.  ``step_opens()`` / ``step_closed()`` bracket
    every step (``forwards`` counts the forwards the device ran in
    them, several in a block generator's) and keep the ``engine.gen.turn``
    annotation open in between; ``end()`` closes the last turn and, for a
    generation that came to its results, writes ``engine.gen.done`` and
    returns the seconds by phase."""

    def __init__(self, group: str, **facts: int) -> None:
        self.group, self.facts = group, facts
        self.forwards = self.blocks = self.tokens = 0
        self._began: Optional[float] = None
        self._mark = 0.0  # the last edge: a step's open or its close
        self._steps_s = self._turns_s = self._turn_max_s = 0.0
        self._turn_max_after = -1
        self._turn = None

    def _close_turn(self, now: float) -> float:
        self._turn.__exit__(None, None, None)
        self._turn = None
        return now - self._mark

    def step_opens(self) -> None:
        now = time.perf_counter()
        if self._began is None:
            self._began = now
        if self._turn is not None:
            turn = self._close_turn(now)
            self._turns_s += turn
            if turn > self._turn_max_s:
                self._turn_max_s = turn
                self._turn_max_after = self.forwards - 1
        self._mark = now

    def step_closed(self, after: str, block: int, forwards: int = 1) -> None:
        now = time.perf_counter()
        self._steps_s += now - self._mark
        self._mark = now
        self.forwards += forwards
        self._turn = trace_span(GEN_TURN_ANNOTATION, group=self.group,
                                after=after, block=int(block))
        self._turn.__enter__()

    def end(self, done: bool) -> Optional[Dict[str, float]]:
        if self._turn is None:
            return None
        now = time.perf_counter()
        finish = self._close_turn(now)
        if not done:
            return None
        us = {k: int(v * 1e6) for k, v in (
            ("generation_us", now - self._began),
            ("steps_us", self._steps_s), ("turns_us", self._turns_s),
            ("finish_us", finish), ("turn_max_us", self._turn_max_s))}
        with trace_span(GEN_DONE_ANNOTATION, group=self.group,
                        forwards=self.forwards, blocks=self.blocks,
                        tokens=self.tokens,
                        turn_max_after=self._turn_max_after,
                        **self.facts, **us):
            pass
        return {"forward": self._steps_s, "turn": self._turns_s,
                "finish": finish}
