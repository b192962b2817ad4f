"""The readers of a generation's host time
(``chipbench/layer_metrics/_generation_host.py`` and the eleven metrics over
it) on recorded toy traces: two generations of two rows through each loop of
``semantic_router_tpu/models/generate.py``, the host plane as the program
wrote it on the CPU, the device plane built to be busy whenever a program
runs (``record_generation_trace.py`` has how).  Expectations are recomputed
here from the raw events by plain sweeps, not by the code under test.

    python -m pytest chipbench/tests/test_generation_host.py -q
"""

import os

import pytest

from chipbench import cells
from chipbench.layer_metrics import _generation_host
from chipbench.tests.test_program_spans import STEP, host_events, raw, read

HERE = os.path.dirname(os.path.abspath(__file__))
LOOPS = {"greedy": 4, "blockdiff": 9}  # forwards a generation
TURN = [f"gen_turn_ms.{p}" for p in _generation_host.TURN_PIECES]
GAP = [f"gen_gap_ms.{p}" for p in _generation_host.GAP_PIECES]
SHARE = "gen_idle_accounted_share"
GUARDS = ["guard_chat_blockdiff", "guard_longdoc_ar", "guard_longctx_sparse"]
US = 1e-6


def trace(loop, suffix=""):
    return os.path.join(HERE, "data", f"generation_{loop}{suffix}.xplane.pb")


def run_of(path):
    return {"trace": {"path": path}}


def programs(path):
    """The device's program runs by start: (start, end)."""
    return sorted((s, e) for _, s, e, _ in
                  raw(path)["/device:TPU:0"]["XLA Modules"])


def generations(path, forwards):
    """The recorded program runs, a generation a list: the session holds
    two whole generations, one after the other."""
    runs = programs(path)
    assert len(runs) == 2 * forwards
    return runs[:forwards], runs[forwards:]


def test_benchmark_appends_the_eleven_over_the_three_guard_cells():
    added = cells.load_benchmark()["per_layer"][-11:]
    assert [m["name"] for m in added] == TURN + GAP + [SHARE]
    for m in added:
        assert m["workloads"] == GUARDS and m["moves"] == "routes_per_s"
        assert m["layer"] == "generation, host side"
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if m["name"] == SHARE else ("ms", "lower"))


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_turn_pieces_sum_to_each_pairs_device_gap(loop):
    path = trace(loop)
    acc = _generation_host.account(run_of(path))
    want = [b[0] - a[1] for gen in generations(path, LOOPS[loop])
            for a, b in zip(gen, gen[1:])]
    assert len(acc.turns) == len(want) == 2 * (LOOPS[loop] - 1)
    got = sorted(acc.turns, key=lambda r: r["interval"])
    for row, gap in zip(got, want):
        pieces = [row[p] for p in _generation_host.TURN_PIECES]
        assert all(p > 0 for p in pieces), row
        assert sum(pieces) == pytest.approx(gap, abs=US)
    # the metrics are the pieces' means, so they sum to the mean gap
    assert sum(read(n, run_of(path)) for n in TURN) == pytest.approx(
        sum(want) / len(want) * 1e3, abs=1e-3)
    # a turn's ``between`` is the program's own span between two steps
    # and what lies at its edges (a traced prefill's request spans are
    # written after its step's annotation ended; the next step's facts are
    # made before its annotation begins): 0.2 ms at most on this recording
    turns = host_events(path, "engine.gen.turn")
    spans = sorted(e - s for name, s, e, _ in turns
                   if name == "engine.gen.turn")
    assert len(spans) == 2 * LOOPS[loop]  # the two finishes among them
    between = sorted(r["between"] for r in got)
    for b in between:
        assert any(0 <= b - s < 200 * US for s in spans), b


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_gap_pieces_sum_to_the_gap_between_two_generations(loop):
    path = trace(loop)
    first, second = generations(path, LOOPS[loop])
    gap = second[0][0] - first[-1][1]
    acc = _generation_host.account(run_of(path))
    (row,) = acc.gaps
    assert (row["rows"], row["items"]) == (2, 2)
    pieces = {p: row[p] for p in _generation_host.GAP_PIECES}
    assert sum(pieces.values()) == pytest.approx(gap, abs=US)
    assert all(v >= 0 for v in pieces.values()), pieces
    assert sum(read(n, run_of(path)) for n in GAP) == pytest.approx(
        gap * 1e3, abs=1e-3)
    # the marker's place and the callers' tokenizations, from the raw events
    done, _ = host_events(path, "engine.gen.done")
    steps = [ev for ev in host_events(path, "engine.step")
             if ev[0] == "engine.step"]
    last, prefill = steps[LOOPS[loop] - 1], steps[LOOPS[loop]]
    assert prefill[3]["flavour"] == "gen.prefill"
    assert pieces["finish"] == pytest.approx(done[2] - last[2], abs=US)
    assert pieces["prefill_head"] == pytest.approx(
        second[0][0] - prefill[1], abs=US)
    toks = [e for e in host_events(path, "engine.tokenize")
            if done[2] <= e[2] <= prefill[1]]
    assert len(toks) == 2  # the two callers of the second generation
    lengths = sum(e[3]["tok_us"] for e in toks) * US
    assert 0 < pieces["tokenize"] <= lengths + US
    waits = [e for e in host_events(path, "engine.queue_wait")
             if done[2] <= e[2] <= prefill[1]]
    assert {w[3]["trace_id"] for w in waits} == \
        {t[3]["trace_id"] for t in toks}
    first_enqueue = min(w[2] - w[3]["wait_us"] * US for w in waits)
    assert pieces["callers"] + pieces["queue_wait"] + pieces["tokenize"] \
        == pytest.approx(prefill[1] - done[2], abs=US)
    assert pieces["queue_wait"] <= prefill[1] - first_enqueue + US


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_every_idle_second_of_a_covered_trace_is_accounted_for(loop):
    assert read(SHARE, run_of(trace(loop))) == pytest.approx(100.0, abs=1e-6)
    # a program's one op fills its run: no idle time inside a program
    assert _generation_host.account(run_of(trace(loop))).in_programs_s == 0


def test_the_share_falls_by_the_gap_whose_turn_is_cut_out():
    whole, cut = trace("blockdiff"), trace("blockdiff", "_cut")
    runs = programs(whole)
    assert programs(cut) == runs  # the device's side is the same
    idle = sum(b[0] - a[1] for a, b in zip(runs, runs[1:]))
    kept = {(r["interval"]) for r in
            _generation_host.account(run_of(cut)).turns}
    (lost,) = [r for r in _generation_host.account(run_of(whole)).turns
               if r["interval"] not in kept]
    assert lost["after"] == "gen.denoise"
    gap = lost["interval"][1] - lost["interval"][0]
    share = read(SHARE, run_of(cut))
    assert share == pytest.approx((idle - gap) / idle * 100.0, abs=1e-6)
    assert 90.0 < share < 99.9
    # the gap between the generations does not hang on a turn
    for name in GAP:
        assert read(name, run_of(cut)) == read(name, run_of(whole))


def test_a_device_clock_behind_the_hosts_is_set_right():
    """The same session with the device's timeline 1.5 ms early: the
    enqueue and completion events pin the offset, and every piece reads
    what it reads where the clocks agree."""
    true, skewed = (_generation_host.account(run_of(trace("greedy", sfx)))
                    for sfx in ("", "_skewed"))
    assert true.offset_bounds == pytest.approx((0.0, 0.0), abs=1e-9)
    assert skewed.offset_bounds == pytest.approx((1.5e-3, 1.5e-3), abs=1e-9)
    for rows, other, pieces in (
            (true.turns, skewed.turns, _generation_host.TURN_PIECES),
            (true.gaps, skewed.gaps, _generation_host.GAP_PIECES)):
        assert len(rows) == len(other) > 0
        for a, b in zip(rows, other):
            assert [a[p] for p in pieces] == pytest.approx(
                [b[p] for p in pieces], abs=US)
    assert skewed.accounted_s == pytest.approx(skewed.idle_s, abs=US)
    # uncorrected, a program would seem to begin before its step did
    early = [r["launch"] - 1.5e-3 for r in skewed.turns]
    assert min(early) < 0


@pytest.mark.parametrize("name", TURN + GAP + [SHARE])
def test_a_tree_without_the_spans_reads_none(name):
    """``step.xplane.pb`` is PR 25's bank trace: ``engine.step`` and no
    generation (the parent's side of the driver's pair)."""
    assert read(name, run_of(STEP)) is None
