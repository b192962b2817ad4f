"""The readers of the program's own profiler annotations
(``chipbench/layer_metrics/_program_spans.py`` and the fifteen metrics over
it), on two cuts of one traced run of ``bank_long_context`` on a v5e (my
chip run, PR 25, seed 2000000011; cut by time with tensorflow's
``xplane_pb2``, device op names shortened, every kept event's times and
stats as recorded):

* ``data/step.xplane.pb`` — 1.17 s of it: the whole ``both`` step of two
  rows (the embedding program before it still on the device while it
  stacks, its own program, its demux) up to the next program's first ops,
  with every device op in between and PjRt's linked host events;
* ``data/routes.xplane.pb`` — the host annotations of the whole session
  (6 steps, 25 queue waits, 8 completed routes), no device plane.

Expectations are recomputed here from the raw events by plain sweeps, or
are numbers read off the events by hand — not by the code under test.

    python -m pytest chipbench/tests/test_program_spans.py -q
"""

import os

import pytest

from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = os.path.join(HERE, "data", "step.xplane.pb")
ROUTES = os.path.join(HERE, "data", "routes.xplane.pb")
SMALL = os.path.join(HERE, "data", "small.xplane.pb")

NEW = ["route_queue_wait_ms", "step_stack_ms", "step_h2d_ms",
       "step_dispatch_ms", "step_demux_ms", "step_readback_tail_ms",
       "trunk_rows_per_route.seq", "trunk_rows_per_route.tok",
       "trunk_rows_per_route.both", "trunk_rows_per_route.embed",
       "device_idle_share.step_head", "device_idle_share.step_tail",
       "device_idle_share.between_steps", "trunk_device_ms_per_route",
       "head_bank_device_ms_per_route"]
FUSED = ("trunk:trunk0", 8192, "fused")
EMBED = ("task:embedding", 8192, "split")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def read(name, run):
    return cells.load_module("layer_metrics", name).read(run)


def raw(path):
    """{plane: {line name: [(name, start_s, end_s, stats)]}} (lines of one
    name merged: threads here never share a name and a span)."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                for e in line.events)
    return out


def host_events(path, prefix):
    return sorted((ev for evs in raw(path)["/host:CPU"].values()
                   for ev in evs if ev[0].startswith(prefix)),
                  key=lambda ev: ev[1])


def stage_of(path, step, stage):
    """The one ``engine.step.<stage>`` span inside ``step`` on its own
    thread: the spans of a step's stages do not overlap one another, those
    of a neighbour's on another thread do."""
    inside = [e for e in host_events(path, "engine.step.")
              if step[1] <= e[1] and e[2] <= step[2]]
    mine = []
    for e in inside:  # the step's own five follow one another in order
        if not mine or e[1] >= mine[-1][2]:
            mine.append(e)
    assert [e[0].rsplit(".", 1)[1] for e in mine] == [
        "stack", "h2d", "dispatch", "readback", "demux"]
    return next(e for e in mine if e[0].endswith("." + stage))


def counters(fused_rows, embed_rows):
    zero = {"executes": 0, "execute_s": 0.0, "compiles": 0, "rows_real": 0,
            "rows_padded": 0}
    after = {FUSED: dict(zero, executes=1, rows_real=fused_rows),
             EMBED: dict(zero, executes=1, rows_real=embed_rows)}
    return ({FUSED: dict(zero), EMBED: dict(zero)}, after)


def make_run(path, busy_s, window_s, n_completed, steps):
    completed = [object()] * n_completed
    return {"completed": completed, "steps": steps,
            "trace": {"path": path, "busy_s": busy_s,
                      "window": (2.0, 2.0 + window_s),
                      "completed": completed, "peaks": PEAKS}}


@pytest.fixture(scope="module")
def step_run():
    ops = raw(STEP)["/device:TPU:0"]["XLA Ops"]
    busy = sum(e - s for _, s, e, _ in ops)  # they never overlap
    every = [ev for plane in raw(STEP).values() for evs in plane.values()
             for ev in evs]
    span = max(e for _, _, e, _ in every) - min(s for _, s, _, _ in every)
    # the harness's window is a little longer than the session's events
    return make_run(STEP, busy, span + 0.02, 2, counters(5, 2)), ops, span


def test_benchmark_lists_the_new_metrics_after_the_old():
    names = [m["name"] for m in cells.load_benchmark()["per_layer"]]
    assert names[-len(NEW):] == NEW
    for m in cells.load_benchmark()["per_layer"][-len(NEW):]:
        assert m["workloads"] == ["bank_long_context"]
        assert m["moves"] == "routes_per_s"


def test_stage_spans_of_the_recorded_step(step_run):
    run, _, _ = step_run
    (step,) = host_events(STEP, "engine.step")[:1]
    assert step[0] == "engine.step" and step[3]["flavour"] == "both"
    assert step[3]["rows"] == 2 and step[3]["padded_rows"] == 2
    # read off the events by hand: stack 1.29 ms, the ids' H2D 49.0 ms
    # (behind the embedding program still running), the enqueue 0.72 ms,
    # the demux of two rows 2.52 ms
    for stage, by_hand in (("stack", 1.29), ("h2d", 49.0),
                           ("dispatch", 0.72), ("demux", 2.52)):
        # (the cut also holds stage spans of its neighbours, whose own
        # engine.step is not wholly inside it: they belong to no step)
        ev = stage_of(STEP, step, stage)
        assert (ev[2] - ev[1]) * 1e3 == pytest.approx(by_hand, abs=0.006)
        assert read(f"step_{stage}_ms", run) == pytest.approx(
            (ev[2] - ev[1]) * 1e3, rel=1e-9)


def test_readback_tail_is_measured_from_the_steps_own_program(step_run):
    run, ops, _ = step_run
    (step,) = host_events(STEP, "engine.step")[:1]
    (mine,) = raw(STEP)["/device:TPU:0"]["XLA Modules"]
    assert "both_fn" in mine[0]
    readback = stage_of(STEP, step, "readback")
    # the step's run is the one its dispatch span enqueued, not whatever
    # is on the device: the embedding program's ops run while it stacks
    assert sum(1 for _, s, e, _ in ops if e <= mine[1]) > 1000
    last_op = max(e for _, s, e, _ in ops if mine[1] <= s and e <= mine[2])
    tail_ms = (readback[2] - last_op) * 1e3
    assert 0 < tail_ms < 20  # 6.4 ms by hand: [2, 8192, 9] + [2, 2, 14]
    assert read("step_readback_tail_ms", run) == pytest.approx(
        tail_ms, abs=0.05)


def test_idle_by_cause_sums_to_the_lines_idle_share(step_run):
    run, ops, span = step_run
    (step,) = host_events(STEP, "engine.step")[:1]
    (mine,) = raw(STEP)["/device:TPU:0"]["XLA Modules"]
    first_op = mine[1]  # where its program begins on the device
    every = [ev for plane in raw(STEP).values() for evs in plane.values()
             for ev in evs]
    lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    head = tail = between = 0.0
    cursor = lo
    for _, s, e, _ in sorted(ops, key=lambda o: o[1]) + [("", hi, hi, {})]:
        a, b = cursor, s  # an idle stretch, cut at the step's three edges
        for x, y in zip([a] + [c for c in (step[1], first_op, step[2])
                               if a < c < b],
                        [c for c in (step[1], first_op, step[2])
                         if a < c < b] + [b]):
            if y <= x:
                continue
            mid = (x + y) / 2
            if not step[1] <= mid < step[2]:
                between += y - x
            elif mid < first_op:
                head += y - x
            else:
                tail += y - x
        cursor = max(cursor, e)
    window_s = run["trace"]["window"][1] - run["trace"]["window"][0]
    assert head > 0 and tail > 0 and between > 0
    got = {c: read(f"device_idle_share.{c}", run)
           for c in ("step_head", "step_tail", "between_steps")}
    assert got["step_head"] == pytest.approx(head / window_s * 100, rel=1e-6)
    assert got["step_tail"] == pytest.approx(tail / window_s * 100, rel=1e-6)
    # what the session's edges leave of the harness's window (0.02 s here)
    # is under no step
    assert got["between_steps"] == pytest.approx(
        (between + 0.02) / window_s * 100, rel=1e-6)
    assert sum(got.values()) == pytest.approx(
        (1 - run["trace"]["busy_s"] / window_s) * 100, rel=1e-9)


def test_trunk_against_heads_by_named_scope(step_run):
    run, ops, _ = step_run
    trunk = read("trunk_device_ms_per_route", run)
    heads = read("head_bank_device_ms_per_route", run)
    busy_ms = run["trace"]["busy_s"] / 2 * 1e3
    # by hand from the ops' tf_op: 1.0769 s under .../trunk/..., 0.31 ms
    # under heads + token_heads + pool, 2.4 ms embed_tokens, 19.8 ms of
    # copies that carry no tf_op at all; two routes
    assert trunk == pytest.approx(1076.9 / 2, abs=0.1)
    assert heads == pytest.approx(0.3117 / 2, abs=0.001)
    assert trunk + heads < busy_ms < trunk + heads + 25 / 2
    from chipbench.layer_metrics import _program_spans

    assert _program_spans._scope(
        "jit(both_fn)/ModernBertModel/trunk/layers_3/attn/pallas_call:") \
        == "trunk"
    assert _program_spans._scope("jit(tok_fn)/token_heads/div:") \
        == "token_heads"
    assert _program_spans._scope("jit(<lambda>)/dot_general:") == ""


def test_flash_roofline_takes_time_and_rows_from_the_same_steps(
        step_run, monkeypatch):
    """The recorded ``both`` step: 2 real rows, 10030 real tokens, PR 25's
    128x128 blocks.  The cut also holds 21 kernel calls of the embedding
    program that ran before it, whose ``engine.step`` span is not in the
    cut: neither its time nor its rows may count."""
    run, ops, _ = step_run
    oc = cells.load_module("opcount", "flash_attention")
    # the cut shortened the op names: `attn f32[24,8192,64] #8504`
    monkeypatch.setattr(oc, "EVENT_PATTERN", r"^attn ")
    (mine,) = raw(STEP)["/device:TPU:0"]["XLA Modules"]
    attn = [(s, e) for n, s, e, _ in ops if n.startswith("attn ")]
    own = [e - s for s, e in attn if mine[1] <= s and e <= mine[2]]
    assert len(attn) == 43 and len(own) == 22
    run = dict(run, config={"model": {
        "num_attention_heads": 12, "hidden_size": 768,
        "num_hidden_layers": 22, "global_attn_every_n_layers": 3,
        "local_attention": 128}})
    # by hand, two rows at the step's mean of 10030 / 2 = 5015 tokens: 8 global layers of 4 * 5015^2 * 64
    # * 12 = 6.1809e11, 14 windowed of 4 * (5015 * 129 - 64 * 65) * 64 * 12
    # = 2.7644e10; two rows 1.29147e12 operations = 6.5557 ms at the peak
    least = 2 * (8 * 4 * 5015 ** 2 + 14 * 4 * (5015 * 129 - 64 * 65)) \
        * 64 * 12 / 197e12
    assert least == pytest.approx(6.5557e-3, rel=1e-4)
    got = read("flash_attention_roofline", run)
    assert got == pytest.approx(least / sum(own) * 100, rel=1e-9)
    assert got == pytest.approx(1.3657, rel=1e-3)  # 0.4800 s of kernel
    # the table of peaks has no such device: nothing to read
    run["trace"] = dict(run["trace"], peaks=None)
    assert read("flash_attention_roofline", run) is None


def test_rows_by_flavour_sum_to_the_step_counters_figure(step_run):
    run, _, _ = step_run
    whole = read("trunk_rows_per_route", run)
    assert whole == pytest.approx(7 / 2)
    got = {f: read(f"trunk_rows_per_route.{f}", run)
           for f in ("seq", "tok", "both", "embed")}
    # the one step of this cut is a `both` step
    assert got == {"seq": 0.0, "tok": 0.0, "both": whole, "embed": 0.0}
    # the whole session: 22 rows seen in six steps — tok 1 + 6, embed 5 + 2,
    # both 2, seq 6 — against counters that saw 147 rows and 41 routes
    run = make_run(ROUTES, 9.0, 10.0, 8, counters(98, 49))
    run["completed"] = [object()] * 41
    got = {f: read(f"trunk_rows_per_route.{f}", run)
           for f in ("seq", "tok", "both", "embed")}
    steps = [e for e in host_events(ROUTES, "engine.step")
             if e[0] == "engine.step"]
    assert [(s[3]["flavour"], s[3]["rows"]) for s in steps] == [
        ("tok", 1), ("embed", 5), ("tok", 6), ("embed", 2), ("both", 2),
        ("seq", 6)]
    assert got["tok"] == pytest.approx(7 / 22 * 147 / 41)
    assert got["both"] == pytest.approx(2 / 22 * 147 / 41)
    assert sum(got.values()) == pytest.approx(
        read("trunk_rows_per_route", run), rel=1e-12)


def test_route_queue_wait_joins_items_to_routes_by_trace_id():
    run = make_run(ROUTES, 9.0, 10.0, 8, counters(98, 49))
    waits = host_events(ROUTES, "engine.queue_wait")
    done = host_events(ROUTES, "router.route.done")
    assert len(waits) == 25 and len(done) == 8
    every = [ev for evs in raw(ROUTES)["/host:CPU"].values() for ev in evs]
    session_start = min(e[1] for e in every)
    waited = []
    for _, _, end, st in done:
        start = end - st["route_us"] * 1e-6
        seen_from = max(start, session_start)
        if end - seen_from < 0.5 * (end - start):
            continue  # one route ended 1.6 s into the session: not judged
        mine = sorted((max(e - w["wait_us"] * 1e-6, seen_from), min(e, end))
                      for _, _, e, w in waits
                      if w.get("trace_id") == st["trace_id"])
        covered, cursor = 0.0, seen_from
        for a, b in mine:  # a plain sweep
            a = max(a, cursor)
            if b > a:
                covered += b - a
                cursor = b
        waited.append(covered / (end - seen_from) * (end - start))
    assert len(waited) == 7
    waited.sort()
    assert read("route_queue_wait_ms", run) == pytest.approx(
        waited[3] * 1e3, rel=1e-9)
    # each of these routes began before the cut's first event and waited
    # 1-3 s of the 5-6 s that the cut holds of it
    assert 1500 < waited[3] * 1e3 < 1900


@pytest.mark.parametrize("name", NEW + ["flash_attention_roofline"])
def test_a_tree_without_the_spans_reads_none(name):
    """The driver runs these files against the parent too, whose trace has
    none of the annotations: no value, and no exception."""
    run = make_run(SMALL, 1e-5, 0.03, 3, counters(5, 2))
    assert read(name, run) is None
    run["trace"] = None
    assert read(name, run) is None

