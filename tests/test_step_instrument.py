"""The one step instrument (observability.batchtrace + profiler): under a
profiler session every device step is an ``engine.step`` annotation around
its five stage annotations with the step's facts as stats, every queued
item an ``engine.queue_wait`` carrying its route's trace id; sampled or
not, traced or not, a step runs the same jitted program with no device
sync of its own; warm-up compiles only programs the runners can reach."""

import contextlib
import glob
import logging
import os
import re
import threading
import time

import jax
import pytest

from semantic_router_tpu.engine.testing import (
    make_embedding_engine,
    make_shared_trunk_engine,
)
from semantic_router_tpu.observability import batchtrace
from semantic_router_tpu.observability.tracing import Tracer

PII = ("pii", ["O", "B-PER", "I-PER"])
SEQ_TASKS = ["intent", "fact_check"]


def _profiled(log_dir, fn):
    """Run ``fn`` under a profiler session; the program's annotations as
    (line index, name, start_ns, end_ns, stats) rows."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    rows = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("engine.", "router.route")):
                    rows.append((i, e.name, e.start_ns,
                                 e.start_ns + e.duration_ns,
                                 dict(e.stats)))
    return out, rows


def _steps(rows):
    """[(step row, [stage rows inside it on its thread])]."""
    out = []
    for line, name, s, e, stats in rows:
        if name != batchtrace.STEP_ANNOTATION:
            continue
        inner = sorted((r for r in rows
                        if r[0] == line and r[1].startswith(
                            batchtrace.STEP_ANNOTATION + ".")
                        and s <= r[2] and r[3] <= e), key=lambda r: r[2])
        out.append(((line, name, s, e, stats), inner))
    return out


def _unfused(tracer):
    eng = make_shared_trunk_engine(fuse=False)
    eng.classify("intent", "warm the shape first")

    def go():
        with tracer.span("router.route") as root:
            eng.classify("intent", "per task path, one row")
        return [root.trace_id], 1

    return eng, go, {"group": "task:intent", "flavour": "intent",
                     "rows": 1, "padded_rows": 1}


def _fused_unpacked(tracer):
    eng = make_shared_trunk_engine(token_tasks=[PII])
    eng.token_classify("pii", "warm the shape first")

    def go():
        with tracer.span("router.route") as root:
            eng.token_classify("pii", "one row rides unpacked")
        return [root.trace_id], 1

    return eng, go, {"group": "trunk:trunk0", "flavour": "tok",
                     "rows": 1, "padded_rows": 1}


def _fused_packed(tracer):
    eng = make_shared_trunk_engine(token_tasks=[PII])
    texts = ["two short prompts", "share one packed row"]
    eng.classify_multi(SEQ_TASKS, texts)

    def go():
        with tracer.span("router.route") as root:
            eng.classify_multi(SEQ_TASKS, texts)
        return [root.trace_id], 2

    return eng, go, {"group": "trunk:trunk0", "flavour": "seq",
                     "rows": 1, "padded_rows": 1}


def _embedding(tracer):
    eng = make_embedding_engine()
    eng.embed("embedding", ["warm the shape first"])

    def go():
        with tracer.span("router.route") as root:
            eng.embed("embedding", ["an embedding step"])
        return [root.trace_id], 1

    return eng, go, {"group": "task:embedding", "flavour": "embed",
                     "rows": 1, "padded_rows": 1}


@pytest.mark.parametrize("make", [_unfused, _fused_unpacked, _fused_packed,
                                  _embedding])
def test_profiler_session_holds_step_stages_and_queue_waits(make, tmp_path):
    tracer = Tracer(sample_rate=1.0)
    eng, go, facts = make(tracer)
    try:
        (trace_ids, n_items), rows = _profiled(tmp_path, go)
    finally:
        eng.shutdown()
    steps = _steps(rows)
    # one engine.step per device step the request traces saw
    executed = [s for s in tracer.spans(batchtrace.STEP_SPAN)]
    assert len(steps) == len(executed) == 1
    (_, _, s, e, stats), inner = steps[0]
    # the five stages, nested, in order, one after the other
    assert [r[1] for r in inner] == [
        batchtrace.STAGE_ANNOTATIONS[n] for n in batchtrace.STAGES]
    assert s <= inner[0][2] and inner[-1][3] <= e
    for a, b in zip(inner, inner[1:]):
        assert a[3] <= b[2]
    # the step's facts are stats, not part of the name
    for k, v in facts.items():
        assert stats[k] == v, (k, stats)
    assert stats["bucket"] == 32 and stats["tokens_real"] > 0
    # one queue wait per item, each with its route's trace id
    waits = [r for r in rows if r[1] == batchtrace.QUEUE_WAIT_ANNOTATION]
    assert len(waits) == n_items
    for w in waits:
        assert w[4]["trace_id"] in trace_ids
        assert w[4]["wait_us"] >= 0 and w[4]["group"]
        assert w[3] <= s  # it left the queue before its step began


def test_router_route_annotation_joins_items_to_routes(tmp_path):
    from semantic_router_tpu.config.schema import (
        DomainRule,
        NamedRule,
        RouterConfig,
        SignalsConfig,
    )
    from semantic_router_tpu.router.pipeline import Router

    engine = make_shared_trunk_engine()
    cfg = RouterConfig(
        default_model="backend-model",
        signals=SignalsConfig(
            domains=[DomainRule(name=lbl) for lbl in
                     ("business", "law", "health", "computer science",
                      "other")],
            fact_check=[NamedRule(name="fact_check")]))
    router = Router(cfg, engine=engine, tracer=Tracer(sample_rate=0.0))

    def body(text):
        return {"model": "auto",
                "messages": [{"role": "user", "content": text}]}

    try:
        router.route(body("warm every shape"))
        results, rows = _profiled(tmp_path, lambda: [
            router.route(body(f"which court hears this appeal #{i}"))
            for i in range(4)])
    finally:
        router.shutdown()
        engine.shutdown()
    routes = {r[4]["trace_id"]: r for r in rows
              if r[1] == batchtrace.ROUTE_ANNOTATION}
    assert set(routes) == {res.trace_id for res in results}
    # ... and a marker at each route's end that carries its length, for
    # the routes a session does not see begin
    done = {r[4]["trace_id"]: r for r in rows
            if r[1] == batchtrace.ROUTE_DONE_ANNOTATION}
    assert set(done) == set(routes)
    for tid, (_, _, s, e, _) in routes.items():
        began = done[tid][3] * 1e-9 - done[tid][4]["route_us"] * 1e-6
        assert abs(began - s * 1e-9) < 5e-3 and e <= done[tid][3]
    waits = [r for r in rows if r[1] == batchtrace.QUEUE_WAIT_ANNOTATION]
    assert {w[4]["trace_id"] for w in waits} == set(routes)
    for w in waits:  # an item waits inside the route that sent it
        _, _, s, e, _ = routes[w[4]["trace_id"]]
        assert s <= w[2] and w[3] <= e
    assert len(_steps(rows)) >= 1


def _together(eng, calls):
    """Run the engine calls on threads so that their items ride ONE step:
    the picker is held (in-flight cap 0) until every item is queued."""
    b = eng.batcher
    b._inflight_cap = lambda key: 0
    out = [None] * len(calls)
    threads = [threading.Thread(
        target=lambda i=i, c=c: out.__setitem__(i, c()))
        for i, c in enumerate(calls)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 30.0
    while b.queue_depths()["pending_items"] < len(calls):
        assert time.monotonic() < deadline, "items never queued"
        time.sleep(0.001)
    del b._inflight_cap  # the class's own again
    with b._wake:
        b._wake.notify()
    for t in threads:
        t.join(60.0)
    return out


@pytest.fixture
def spied_engine(monkeypatch):
    """Shared-trunk engine with a token head, every flavour compiled,
    whose group programs and ``jax.block_until_ready`` record their
    calls."""
    eng = make_shared_trunk_engine(token_tasks=[PII])
    eng.classify_multi(SEQ_TASKS, ["warm seq"])
    eng.token_classify("pii", "warm tok")
    _together(eng, [lambda: eng.classify_multi(SEQ_TASKS, ["warm both"]),
                    lambda: eng.token_classify("pii", "warm both")])
    g = next(iter(eng._groups_by_gid.values()))
    calls, syncs = [], []
    fns = dict(g.fns)
    for key, fn in g.fns.items():
        if callable(fn):
            fns[key] = (lambda *a, _k=key, _f=fn:
                        (calls.append(_k), _f(*a))[1])
    g.fns = fns
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (syncs.append(1), x)[1])
    yield eng, calls, syncs
    eng.shutdown()


TEXT = "Maria Keller asked whether tracing changes the numbers"


def _seq(eng, tracer):
    with _span(tracer):
        out = eng.classify_multi(SEQ_TASKS, [TEXT])
    return {task: out[task][0].probs for task in SEQ_TASKS}


def _tok(eng, tracer):
    with _span(tracer):
        out = eng.token_classify("pii", TEXT)
    return [(s.start, s.end, s.type, s.score) for s in out.entities]


def _span(tracer):
    return contextlib.nullcontext() if tracer is None \
        else tracer.span("router.route")


@pytest.mark.parametrize("asks,program", [
    ((_seq,), "seq"), ((_tok,), "tok"), ((_seq, _tok), "both")])
def test_sampling_never_changes_the_program(spied_engine, asks, program):
    eng, calls, syncs = spied_engine
    got = {}
    for label, tracer in (("sampled", Tracer(sample_rate=1.0)),
                          ("unsampled", Tracer(sample_rate=0.0)),
                          ("untraced", None)):
        del calls[:]
        got[label] = _together(
            eng, [lambda ask=ask: ask(eng, tracer) for ask in asks])
        if tracer is not None:
            names = {s.name for s in tracer.spans("batch.")}
            assert "batch.ride" in names
            assert ("batch.dispatch" in names) == (label == "sampled")
        # the same jitted function, whatever the trace says
        assert calls == [program], (label, calls)
    assert syncs == []  # no step fences the device
    assert not hasattr(batchtrace.BatchStep, "fence")
    # bit-identical, not merely close
    assert got["sampled"] == got["unsampled"] == got["untraced"]


def test_warmup_compiles_only_programs_the_runners_reach():
    eng = make_shared_trunk_engine(token_tasks=[PII])
    compiled = []

    class Names(logging.Handler):
        def emit(self, record):
            m = re.match(r"Compiling (\S+) with global shapes",
                         record.getMessage())
            if m:
                compiled.append(m.group(1))

    handler = Names()
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles(True):
            eng.warmup(buckets=[32], batch_sizes=[1, 2])
            g = next(iter(eng._groups_by_gid.values()))
            reachable = {f"jit({fn.__name__})" for fn in g.fns.values()
                         if callable(fn)}
            assert compiled and set(compiled) <= reachable, compiled
            # ... and a sampled trace at a warmed shape compiles nothing
            del compiled[:]
            tracer = Tracer(sample_rate=1.0)
            with tracer.span("router.route"):
                eng.classify_multi(SEQ_TASKS, ["a sampled request"])
                eng.token_classify("pii", "a sampled token request")
            assert compiled == []
    finally:
        logger.removeHandler(handler)
        eng.shutdown()


# -- one step, written once (engine/step.py), under every runner --------------


class _Samples:
    """Stands where the engine's RuntimeStats stands and keeps what the
    runners hand it."""

    def __init__(self):
        self.steps, self.generations, self.finished = [], [], []

    def record_step(self, group, bucket, variant, rows, padded_rows,
                    seconds, **kw):
        self.steps.append(dict(group=group, bucket=bucket, variant=variant,
                               rows=rows, padded_rows=padded_rows,
                               seconds=seconds, **kw))

    def record_generation(self, task, flavour, **kw):
        self.generations.append((task, flavour))

    def record_generation_done(self, task, seconds):
        self.finished.append((task, seconds))


class _Rows:
    """A token a character, twelve at most."""

    def encode(self, text, max_length=0):
        from semantic_router_tpu.utils.tokenization import Encoding

        ids = [3 + ord(c) % 200 for c in text[:12]]
        return Encoding(ids=ids, attention_mask=[1] * len(ids),
                        offsets=[(0, 0)] * len(ids))

    def decode(self, ids):
        return " ".join(str(int(i)) for i in ids)


def _toy_generator():
    from semantic_router_tpu.models.generate import GreedyGenerator
    from semantic_router_tpu.models.qwen3 import Qwen3Config, Qwen3ForCausalLM

    cfg = Qwen3Config(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      tie_word_embeddings=True)
    params = Qwen3ForCausalLM(cfg).init(
        jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), jax.numpy.int32))
    return GreedyGenerator(cfg, params, _Rows())


def _generative_engine():
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine

    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=1.0, seq_len_buckets=[32]))
    eng.register_generative("guard", _toy_generator())
    return eng


TWO = ["two short prompts", "share one packed row"]

# name -> (engine, the call, group, [(variant, rows, padded_rows) a program])
RUNNERS = {
    "task_seq": lambda: (
        make_shared_trunk_engine(fuse=False),
        lambda e: e.classify("intent", TEXT),
        "task:intent", [("split", 1, 1)]),
    "task_tok": lambda: (
        make_shared_trunk_engine(token_tasks=[PII], fuse=False),
        lambda e: e.token_classify("pii", TEXT),
        "task:pii", [("split", 1, 1)]),
    "embed": lambda: (
        make_embedding_engine(),
        lambda e: e.embed("embedding", [TEXT, "a second row", "a third"]),
        "task:embedding", [("split", 3, 4)]),
    "fused_seq": lambda: (
        make_shared_trunk_engine(token_tasks=[PII]),
        lambda e: e.classify_multi(SEQ_TASKS, [TEXT]),
        "trunk:trunk0", [("fused", 1, 1)]),
    "fused_tok": lambda: (
        make_shared_trunk_engine(token_tasks=[PII]),
        lambda e: e.token_classify("pii", TEXT),
        "trunk:trunk0", [("fused", 1, 1)]),
    "fused_both": lambda: (
        make_shared_trunk_engine(token_tasks=[PII]),
        lambda e: e.classify_multi(SEQ_TASKS + ["pii"], [TEXT]),
        "trunk:trunk0", [("fused", 1, 1)]),
    # the tiny trunk's attention is dense, so two short rows pack into one
    "packed_seq": lambda: (
        make_shared_trunk_engine(token_tasks=[PII]),
        lambda e: e.classify_multi(SEQ_TASKS, TWO),
        "trunk:trunk0", [("packed", 1, 1)]),
    "packed_both": lambda: (
        make_shared_trunk_engine(token_tasks=[PII]),
        lambda e: e.classify_multi(SEQ_TASKS + ["pii"], TWO),
        "trunk:trunk0", [("packed", 1, 1)]),
    "generator": lambda: (
        _generative_engine(),
        lambda e: e.generate("guard", ["a prompt", "another"],
                             max_new_tokens=3),
        "gen:guard", [("gen.prefill", 2, 2), ("gen.decode", 2, 2)]),
}


@pytest.fixture
def opened(monkeypatch):
    """Every step a runner opens: (its ``engine.step`` facts, its
    BatchStep)."""
    seen, real = [], batchtrace.start_step

    def spy(items, **facts):
        step = real(items, **facts)
        seen.append((facts, step))
        return step

    monkeypatch.setattr(batchtrace, "start_step", spy)
    return seen


@pytest.mark.parametrize("runner", sorted(RUNNERS))
def test_every_runner_leaves_one_sample_a_program(runner, opened):
    eng, call, group, programs = RUNNERS[runner]()
    samples = eng._runtime_stats = _Samples()
    try:
        call(eng)
    finally:
        eng.shutdown()
    assert [(s["variant"], s["rows"], s["padded_rows"])
            for s in samples.steps] == programs
    assert len(opened) == len(programs)
    variants = set()
    for (facts, step), sample in zip(opened, samples.steps):
        assert sample["group"] == facts["group"] == group
        assert (sample["rows"], sample["padded_rows"], sample["bucket"]) \
            == (facts["rows"], facts["padded_rows"], facts["bucket"])
        # a program's first run is the one accounted as its compile
        assert sample["compiled"] == (sample["variant"] not in variants)
        variants.add(sample["variant"])
        assert sample["seconds"] > 0 and step._finished
    if runner == "generator":
        assert [s["variant"] for s in samples.steps] == \
            [facts["flavour"] for facts, _ in opened] == \
            [flavour for _, flavour in samples.generations]
    elif runner.startswith(("fused", "packed")):
        assert opened[0][0]["flavour"] == runner.split("_")[1]
        assert samples.steps[0]["tokens_padded"] == 32
        assert samples.steps[0]["segments"] == (
            2 if runner.startswith("packed") else 1)


def _break_program(eng, runner):
    """Make the runner's device program raise; returns the undo."""
    def boom(*a, **kw):
        raise RuntimeError("program down")

    if runner == "generator":
        gen = eng._tasks["guard"].generator
        real = gen._prefill_fn
        gen._prefill_fn = lambda shape: boom
        return lambda: setattr(gen, "_prefill_fn", real)
    if runner.startswith("task") or runner == "embed":
        t = eng._tasks[{"task_seq": "intent", "task_tok": "pii",
                        "embed": "embedding"}[runner]]
        real = t.apply_fn
        t.apply_fn = boom
        return lambda: setattr(t, "apply_fn", real)
    g = next(iter(eng._groups_by_gid.values()))
    real = g.fns
    g.fns = {k: boom if callable(v) else v for k, v in real.items()}
    return lambda: setattr(g, "fns", real)


@pytest.mark.parametrize("runner", sorted(RUNNERS))
def test_a_raising_program_finishes_its_step_and_fails_its_batch_only(
        runner, opened):
    eng, call, group, programs = RUNNERS[runner]()
    samples = eng._runtime_stats = _Samples()
    tracer = Tracer(sample_rate=1.0)
    try:
        undo = _break_program(eng, runner)
        with tracer.span("router.route"):
            with pytest.raises(RuntimeError, match="program down"):
                call(eng)
        # the step ended on both clocks and left no sample of a program
        # that did not run
        assert len(opened) == 1 and opened[0][1]._finished
        assert samples.steps == []
        (step,) = tracer.spans(batchtrace.STEP_SPAN)
        assert step.attributes["group"] == group
        assert tracer.spans(batchtrace.RIDE_SPAN)
        # ... and the next batch is served
        undo()
        call(eng)
        assert [s["variant"] for s in samples.steps] == \
            [p[0] for p in programs]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("via", ["classify", "classify_multi"])
def test_per_task_and_fused_decode_alike_with_fewer_labels_than_width(via):
    """One width-tolerant decode: a label list shorter than the head names
    the classes beyond it by position, on either path."""
    split = make_shared_trunk_engine(fuse=False)
    fused = make_shared_trunk_engine()
    try:
        answers = []
        for eng in (split, fused):
            eng._tasks["intent"].labels = ["business", "law", "health"]
            if via == "classify":
                answers.append(eng.classify("intent", "word " * 600))
            else:
                answers.append(eng.classify_multi(
                    ["intent"], ["word " * 600])["intent"][0])
        a, b = answers
        assert list(a.probs) == list(b.probs) == \
            ["business", "law", "health", "3", "4"]
        assert (a.label, a.index, a.truncated) == \
            (b.label, b.index, b.truncated)
        assert a.truncated  # 600 words against a max_seq_len of 512
        assert a.confidence == pytest.approx(b.confidence, abs=1e-5)
        assert a.probs == pytest.approx(b.probs, abs=1e-5)
        assert a.latency_s > 0 and b.latency_s > 0
    finally:
        split.shutdown()
        fused.shutdown()


# -- a generation's host time, between its steps -------------------------------


def _toy_block_generator():
    from semantic_router_tpu.models import sdar_moe
    from tests import test_sdar_moe as toy

    state = toy.family.generate_state(toy.CONFIG, 7)
    cfg = sdar_moe.SdarMoeConfig.from_hf(toy.MODEL)
    gen = toy.generator(
        (state, cfg, sdar_moe.params_from_state(state.__getitem__, cfg)))
    gen.tokenizer = _Rows()
    return gen


def _greedy_loop():
    return _toy_generator(), 4, {"gen.prefill": 1, "gen.decode": 3}


def _block_loop():
    # prompts of 8 and 7 tokens and 8 new ones: three blocks, the second
    # and third begun by the forward that commits the one before
    return _toy_block_generator(), 8, {"gen.prefill": 1, "gen.denoise": 10,
                                       "gen.commit": 2}


LOOPS = {"greedy": _greedy_loop, "blockdiff": _block_loop}
# the host's steps: the prefill and the LOOP of decode steps in the one, a
# BLOCK's forwards a program in the other (a prefill and three blocks)
STEPS = {"greedy": 2, "blockdiff": 4}
PROMPTS = ["a prompt", "another"]


@pytest.fixture(params=sorted(LOOPS))
def loop(request):
    """(engine, new tokens, forwards by flavour, steps) of a toy
    generation through one of the two loops, its programs compiled."""
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine

    gen, new_tokens, forwards = LOOPS[request.param]()
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=1.0, seq_len_buckets=[32]))
    eng.register_generative("guard", gen)
    eng.generate("guard", PROMPTS, max_new_tokens=new_tokens)
    yield eng, new_tokens, forwards, STEPS[request.param]
    eng.shutdown()


def _named(rows, name):
    return sorted((r for r in rows if r[1] == name), key=lambda r: r[2])


def _gen_counters(eng):
    rs = eng._runtime_stats
    return {"forwards": sum(rs.gen_forwards._values.values()),
            "programs": sum(rs.gen_programs._values.values()),
            "by_flavour": {f: (rs.gen_programs.get(task="guard", flavour=f),
                               rs.gen_forwards.get(task="guard", flavour=f))
                           for f in ("gen.prefill", "gen.decode",
                                     "gen.denoise", "gen.commit")},
            "blocks": rs.gen_blocks.get(task="guard"),
            "tokens": rs.gen_tokens.get(task="guard"),
            "generations": rs.gen_generations.get(task="guard"),
            **{phase: rs.gen_seconds.get(task="guard", phase=phase)
               for phase in ("forward", "turn", "finish")}}


def test_steps_and_turns_tile_a_generation(loop, tmp_path):
    eng, new_tokens, forwards, n_steps = loop
    _, rows = _profiled(tmp_path, lambda: eng.generate(
        "guard", PROMPTS, max_new_tokens=new_tokens))
    steps = _named(rows, batchtrace.STEP_ANNOTATION)
    turns = _named(rows, batchtrace.GEN_TURN_ANNOTATION)
    (done,) = _named(rows, batchtrace.GEN_DONE_ANNOTATION)
    assert len(steps) == len(turns) == n_steps
    # one thread: step, turn, step, turn, ..., the last turn, done
    assert {r[0] for r in steps + turns + [done]} == {steps[0][0]}
    pieces = sorted(steps + turns, key=lambda r: r[2])
    assert [r[1] for r in pieces] == [
        batchtrace.STEP_ANNOTATION, batchtrace.GEN_TURN_ANNOTATION
    ] * len(steps)
    # they do not overlap, and what lies under neither (a turn's exit to
    # the next annotation's enter) is microseconds on a quiet machine, a
    # thread switch on one that runs other tests
    for a, b in zip(pieces, pieces[1:] + [done]):
        assert a[3] <= b[2]
        assert b[2] - a[3] < 50_000_000, (a[1], b[1], b[2] - a[3])
    # a turn says which forward it follows
    for step, turn in zip(steps, turns):
        assert turn[4]["after"] == step[4]["flavour"]
        assert turn[4]["group"] == "gen:guard"
        assert turn[4]["block"] == step[4].get("block", -1)
    # the forward's marker stays where its readers look for it: after its
    # step's end and before the next step's start (inside the turn)
    marks = _named(rows, batchtrace.GEN_FORWARD_ANNOTATION)
    if "gen.commit" in forwards:  # the expert model reports its load
        assert len(marks) == len(steps)
        for step, turn, mark in zip(steps, turns, marks):
            assert mark[4]["flavour"] == step[4]["flavour"]
            assert step[3] <= mark[2] and turn[2] <= mark[2] <= turn[3]
            assert mark[4]["forwards"] >= 1
        # a program's forwards are on its marker; a generation's on its end
        assert sum(m[4]["forwards"] for m in marks) == done[4]["forwards"] \
            == sum(forwards.values())
        # a block's state is made on the device: a turn holds no copies
        assert not [r for r in rows
                    if r[1].startswith(batchtrace.GEN_TURN_ANNOTATION + ".")]
    else:
        assert marks == []


def test_gen_done_carries_the_counters_deltas_and_a_tiling(loop, tmp_path):
    eng, new_tokens, forwards, n_steps = loop
    before = _gen_counters(eng)
    _, rows = _profiled(tmp_path, lambda: eng.generate(
        "guard", PROMPTS, max_new_tokens=new_tokens))
    after = _gen_counters(eng)
    (done,) = _named(rows, batchtrace.GEN_DONE_ANNOTATION)
    facts = done[4]
    assert (facts["group"], facts["rows"], facts["padded_rows"],
            facts["bucket"]) == ("gen:guard", 2, 2, 32)
    for count in ("forwards", "blocks", "tokens"):
        assert facts[count] == after[count] - before[count], count
    assert facts["forwards"] == sum(forwards.values())
    assert after["generations"] - before["generations"] == 1
    # one program a turn of the host, and every forward under its flavour:
    # the decode loop's steps all under gen.decode, over ONE program
    assert after["programs"] - before["programs"] == n_steps
    for flavour, n in forwards.items():
        programs, ran = (a - b for a, b in zip(
            after["by_flavour"][flavour], before["by_flavour"][flavour]))
        assert ran == n, flavour
        assert programs == (n if flavour == "gen.commit" else 1), flavour
    # the three parts tile the whole, on the host clock ...
    parts = facts["steps_us"] + facts["turns_us"] + facts["finish_us"]
    assert abs(parts - facts["generation_us"]) < 1000
    assert 0 < facts["turn_max_us"] <= facts["turns_us"]
    assert 0 <= facts["turn_max_after"] < facts["forwards"] - 1
    # ... and are what /metrics got
    for phase, fact in (("forward", "steps_us"), ("turn", "turns_us"),
                        ("finish", "finish_us")):
        assert (after[phase] - before[phase]) * 1e6 == pytest.approx(
            facts[fact], abs=2)
    # and the same generation as on the profiler's clock, first step's
    # open to the marker (two clocks read microseconds apart, on a machine
    # that may run other tests: loosely)
    steps = _named(rows, batchtrace.STEP_ANNOTATION)
    turns = _named(rows, batchtrace.GEN_TURN_ANNOTATION)
    assert (done[2] - steps[0][2]) / 1e3 == pytest.approx(
        facts["generation_us"], rel=0.5)
    assert sum(t[3] - t[2] for t in turns[:-1]) / 1e3 <= \
        facts["turns_us"] + 1000


def _break_a_later_forward(eng):
    """The generator's second kind of program raises, so that a turn is
    open when it does."""
    def boom(*a, **kw):
        raise RuntimeError("program down")

    gen = eng._tasks["guard"].generator
    if hasattr(gen, "programs"):
        real = gen.programs
        gen.programs = lambda *a: (real(*a)[0], boom, boom)
        return lambda: setattr(gen, "programs", real)
    real = gen._loop_fn
    gen._loop_fn = lambda key: boom
    return lambda: setattr(gen, "_loop_fn", real)


def test_a_raising_forward_ends_its_turn_and_writes_no_done(loop, tmp_path):
    eng, new_tokens, forwards, n_steps = loop
    before = _gen_counters(eng)
    undo = _break_a_later_forward(eng)

    def go():
        with pytest.raises(RuntimeError, match="program down"):
            eng.generate("guard", PROMPTS, max_new_tokens=new_tokens)

    _, rows = _profiled(tmp_path / "down", go)
    steps = _named(rows, batchtrace.STEP_ANNOTATION)
    turns = _named(rows, batchtrace.GEN_TURN_ANNOTATION)
    # the prefill and the forward that raised: both steps ended, and so
    # did the turn after each (an annotation left open leaves no event)
    assert [s[4]["flavour"] for s in steps] == [
        "gen.prefill", "gen.denoise" if "gen.commit" in forwards
        else "gen.decode"]
    assert len(turns) == 2
    for step, turn in zip(steps, turns):
        assert step[3] <= turn[2] and turn[4]["after"] == step[4]["flavour"]
    assert _named(rows, batchtrace.GEN_DONE_ANNOTATION) == []
    after = _gen_counters(eng)
    assert after["generations"] == before["generations"]
    assert after["forward"] == before["forward"]
    # ... and the next generation is whole
    undo()
    _, rows = _profiled(tmp_path / "up", lambda: eng.generate(
        "guard", PROMPTS, max_new_tokens=new_tokens))
    (done,) = _named(rows, batchtrace.GEN_DONE_ANNOTATION)
    assert done[4]["forwards"] == sum(forwards.values())


def _spy_programs(eng, calls):
    """Every call of the generator's programs: (which, its small array
    arguments as bytes) — the parameters and the cache are left out."""
    import numpy as np

    def small(a):
        return isinstance(a, (int, float)) or (
            hasattr(a, "shape") and hasattr(a, "dtype")
            and np.prod(a.shape) < 4096)

    def spied(name, fn):
        def call(*args):
            calls.append((name, tuple(np.asarray(a).tobytes()
                                      for a in args if small(a))))
            return fn(*args)
        return call

    gen = eng._tasks["guard"].generator
    if hasattr(gen, "programs"):
        real = gen.programs
        gen.programs = lambda *a: tuple(
            spied(n, f) for n, f in zip(("prefill", "denoise", "commit"),
                                        real(*a)))
    else:
        pre, step = gen._prefill_fn, gen._loop_fn
        gen._prefill_fn = lambda key: spied("prefill", pre(key))
        gen._loop_fn = lambda key: spied("decode", step(key))


def test_a_session_never_changes_a_generations_programs(loop, tmp_path):
    eng, new_tokens, forwards, n_steps = loop
    calls, got = [], {}
    _spy_programs(eng, calls)

    def ask(tracer):
        with _span(tracer):
            out = eng.generate("guard", PROMPTS, max_new_tokens=new_tokens)
        return [r.token_ids for r in out], list(calls)

    for label, tracer in (("untraced", None),
                          ("sampled", Tracer(sample_rate=1.0))):
        del calls[:]
        got[label] = ask(tracer)
    del calls[:]
    got["session"], _ = _profiled(tmp_path, lambda: ask(None))
    tokens, programs = got["untraced"]
    # a step is a program, but for a block with a block before it: the
    # forward that commits, then the loop, queued one behind the other
    assert len(programs) == n_steps + forwards.get("gen.commit", 0)
    assert [name for name, _ in programs].count("prefill") == 1
    # the same programs with the same arguments, bit for bit, whatever
    # watches: a request trace, a profiler session, nobody
    assert got["sampled"] == got["session"] == (tokens, programs)


def _through_generate(tracer):
    eng = _generative_engine()
    eng.generate("guard", ["warm the programs"], max_new_tokens=2)

    def go():
        with tracer.span("router.route") as root:
            eng.generate("guard", ["a traced prompt"], max_new_tokens=2)
        return root.trace_id

    return eng, go, "guard", [0], 0


def _through_the_fused_bank(tracer):
    from semantic_router_tpu.utils.tokenization import EncodingCache

    eng = make_shared_trunk_engine(token_tasks=[PII])
    eng.classify_multi(SEQ_TASKS, ["warm the shape first"])

    def go():
        cache = EncodingCache()
        with tracer.span("router.route") as root:
            eng.classify_multi(SEQ_TASKS, [TEXT], enc_cache=cache)
            eng.classify_multi(SEQ_TASKS, [TEXT], enc_cache=cache)
        return root.trace_id

    return eng, go, "trunk0", [0, 1], 2


@pytest.mark.parametrize("make", [_through_generate,
                                  _through_the_fused_bank])
def test_tokenize_marker_carries_the_routes_trace_id(make, tmp_path):
    tracer = Tracer(sample_rate=0.0)
    eng, go, tag, cached, spans_written = make(tracer)
    try:
        trace_id, rows = _profiled(tmp_path, go)
    finally:
        eng.shutdown()
    toks = _named(rows, batchtrace.TOKENIZE_ANNOTATION)
    waits = _named(rows, batchtrace.QUEUE_WAIT_ANNOTATION)
    # one marker a tokenization, a cache hit among them, each with the id
    # that its item's queue wait carries
    assert [t[4]["cached"] for t in toks] == cached
    assert len(waits) == len(toks)
    for tok, wait in zip(toks, waits):
        assert tok[4]["trace_id"] == wait[4]["trace_id"] == trace_id
        assert tok[4]["tag"] == tag and tok[4]["tokens"] > 0
        assert tok[4]["tok_us"] >= 0
        assert tok[3] <= wait[3]  # tokenized, then queued
    # the marker is all a caller writes where it tokenized; the request's
    # batch.tokenize span comes from the same seconds when its item's step
    # ends (a generation's prompts have none, as before)
    spans = tracer.spans(batchtrace.TOKENIZE_SPAN)
    assert [s.attributes["cache_hit"] for s in spans] == \
        [bool(c) for c in cached][:spans_written]
    for span, tok in zip(spans, toks):
        assert span.trace_id == trace_id
        assert span.duration_s * 1e6 == pytest.approx(tok[4]["tok_us"],
                                                      abs=2)
