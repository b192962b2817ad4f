"""The harness's own checks, on the CPU:
``python -m pytest chipbench/tests -q -p no:cacheprovider``.

They are not part of the repo's tier-1 suite (``tests/``); PERF.md names
them.  The whole-loop tests drive ``run.run_cell`` on a toy configuration
made from ``mmbert32k-bank``'s files, with the look for a chip skipped;
what they print never carries a device metric's name.
"""

from __future__ import annotations

import json
import os
import re
import sys

import numpy as np
import pytest

from chipbench import cells, correctness, loadgen, stats

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- traffic ---------------------------------------------------------------


LONG = {"lengths": {"law": "loguniform", "min": 2049, "max": 8000},
        "arrivals": {"mode": "closed", "clients": 8, "pool_per_s": 4}}
CHAT = {"lengths": {"law": "lognormal", "median": 48, "sigma": 1.0,
                    "min": 4, "max": 500},
        "arrivals": {"mode": "open", "rate_per_s": 40}}


def _gen():
    return cells.load_module("traffic", "seeded_words")


@pytest.mark.parametrize("params", [LONG, CHAT], ids=["closed", "open"])
def test_generator_is_deterministic_in_the_seed(params):
    g = _gen()
    a = g.generate(params, 2**31 + 7, 10, 256000)
    b = g.generate(params, 2**31 + 7, 10, 256000)
    c = g.generate(params, 2**31 + 8, 10, 256000)
    assert [r.text for r in a.requests] == [r.text for r in b.requests]
    assert [r.due_s for r in a.requests] == [r.due_s for r in b.requests]
    assert [r.text for r in a.requests] != [r.text for r in c.requests]
    # every seed: the same lengths and gaps, in another order
    assert sorted(r.n_tokens for r in a.requests) == \
        sorted(r.n_tokens for r in c.requests)
    assert len({r.text for r in a.requests}) == len(a.requests)
    assert not {r.text for r in a.requests} & {r.text for r in a.warmup}


def test_generator_does_not_import_the_program():
    import subprocess

    code = ("import sys; sys.path.insert(0, %r); from chipbench import cells;"
            "cells.load_module('traffic', 'seeded_words');"
            "assert not [m for m in sys.modules if "
            "m.startswith('semantic_router_tpu') or m == 'jax']" % cells.ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_open_loop_schedule_fills_the_window_at_the_rate():
    t = _gen().generate(CHAT, 3, 10, 256000)
    due = sorted(r.due_s for r in t.requests)
    assert len(due) == 400 and 0 <= due[0] and due[-1] < 10
    lengths = [r.n_tokens for r in t.requests]
    assert min(lengths) >= 4 and max(lengths) <= 500
    assert 0.80 < sum(n <= 128 for n in lengths) / len(lengths) < 0.88


def test_words_are_token_ids():
    t = _gen().generate(LONG, 5, 2, 256000)
    r = t.requests[0]
    assert [int(w[1:]) for w in r.text.split()] == r.ids.tolist()
    offs = cells.load_module("families", "encoder_bank").word_offsets(
        r.ids[:3])
    assert [r.text[s:e] for s, e in offs] == r.text.split()[:3]


# -- arithmetic --------------------------------------------------------------


def test_percentile_on_known_samples():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10.0 and stats.percentile(xs, 100) == 50.0
    assert stats.percentile(list(range(1, 102)), 95) == pytest.approx(96.0)
    assert stats.percentile([7.0], 95) == 7.0


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.union_length(iv, clip=(0.5, 3.5)) == pytest.approx(2.0)
    assert stats.gaps(iv, (0.0, 5.0)) == [(2.0, 3.0), (4.0, 5.0)]


def test_open_loop_latency_counts_from_the_due_time():
    class Req:
        def __init__(self, i, due):
            self.index, self.n_tokens, self.due_s, self.text = i, 1, due, ""

    import time

    def send(req):
        time.sleep(0.05)
        return True, ""

    records, unfinished = loadgen.run_open(
        send, [Req(0, 0.0), Req(1, 0.01), Req(2, 0.02)], senders=1,
        seconds=0.1, grace_s=5.0)
    assert unfinished == 0 and all(r.ok for r in records)
    by = {r.index: r for r in records}
    # one sender: the third request starts ~0.1 s in, 0.08 s late, and its
    # latency counts that wait
    assert by[2].start - by[2].due > 0.06
    assert by[2].latency > 0.12


# -- opcount ------------------------------------------------------------------


@pytest.mark.parametrize("n,window", [(5, 0), (50, 8), (300, 128), (64, 128)])
def test_flash_opcount_against_a_dense_count(n, window):
    oc = cells.load_module("opcount", "flash_attention")
    i = np.arange(n)
    allowed = np.ones((n, n), bool) if not window else \
        np.abs(i[:, None] - i[None, :]) <= window // 2
    heads, d = 3, 16
    flops, nbytes = oc.call_cost(n, heads, d, window)
    assert flops == 4 * int(allowed.sum()) * d * heads
    assert nbytes == 4 * n * heads * d * 4


def test_flash_forward_cost_counts_the_layer_pattern():
    oc = cells.load_module("opcount", "flash_attention")
    model = {"num_attention_heads": 12, "hidden_size": 768,
             "num_hidden_layers": 22, "global_attn_every_n_layers": 3,
             "local_attention": 128}
    c = oc.forward_cost(8192, model)
    g, _ = oc.call_cost(8192, 12, 64, 0)
    l, _ = oc.call_cost(8192, 12, 64, 128)
    assert c["calls"] == 22 and c["flops"] == 8 * g + 14 * l
    assert g == 4 * 8192 * 8192 * 64 * 12
    t, bound = oc.least_seconds(g, 1.0, {"bf16_flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and t == pytest.approx(g / 197e12)


# -- BENCHMARK.json -------------------------------------------------------------


def test_benchmark_names_and_units():
    b = cells.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = []
    for c in b["configs"]:
        names.append(c["name"])
        assert os.path.exists(os.path.join(cells.ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("chipbench/")
    for w in b["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        cells.load_workload(w["traffic"])
        names.append(w["name"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert callable(cells.load_module("layer_metrics", m["name"]).read)
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in b["workloads"]}
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    assert len(json.dumps(b)) < 64 * 1024


# -- trace -----------------------------------------------------------------------


def test_reduce_trace_on_the_recorded_trace():
    """``data/small.xplane.pb``: four runs of one small jitted program on a
    v5e under ``chipbench.router.route`` / ``chipbench.engine.embed``
    annotations (my chip run, PR 23).  The expectations are recomputed here
    from the raw events, not by the code under test."""
    from jax.profiler import ProfileData

    from chipbench import reduce_trace

    path = os.path.join(HERE, "data", "small.xplane.pb")
    r = reduce_trace.reduce(path)
    ops = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
    assert r["devices"] == ["/device:TPU:0"] and len(ops) == 12
    assert r["work_events"] == 12
    covered, cursor = 0, None
    for _, s, e in sorted(ops, key=lambda o: o[1]):  # a plain sweep
        s = s if cursor is None else max(s, cursor)
        if e > s:
            covered += e - s
            cursor = e
    assert r["busy_s"] == pytest.approx(covered * 1e-9, rel=1e-6)
    fusion = [n for n in r["op_seconds"] if n.startswith("%fusion = ")]
    assert len(fusion) == 1 and r["op_calls"][fusion[0]] == 4
    assert r["op_seconds"][fusion[0]] == pytest.approx(
        sum(e - s for n, s, e in ops if n == fusion[0]) * 1e-9, rel=1e-6)
    assert reduce_trace.short_name(fusion[0]) == "fusion f32[512]"
    assert reduce_trace.seconds_matching(r, r"^%fusion = ")[1] == 4
    span = r["span"][1] - r["span"][0]
    assert 0 < r["busy_s"] < span
    assert r["host_spans"] == 8  # 4 routes, 4 engine calls
    # three gaps between the four runs, each under the route's annotation
    # (the engine call has returned; the route sleeps 2 ms, then 3 ms none)
    assert len(r["idle_gaps"]) >= 3
    assert {name for name, _ in r["idle_gaps"][:3]} <= {
        "router.route", "engine.embed", "no_chipbench_span"}
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        span - r["busy_s"], rel=1e-6)


# -- the whole loop, on the CPU, on the toy configuration ------------------------


TOY_MODEL = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
             "num_hidden_layers": 3, "num_attention_heads": 2,
             "max_position_embeddings": 512,
             "rope_scaling": {"rope_type": "yarn", "factor": 4.0,
                              "original_max_position_embeddings": 128}}


@pytest.fixture()
def toy_bench():
    """A toy configuration made from ``mmbert32k-bank``'s files (its
    router config with a small engine block, its model.json with toy
    sizes), written where a run may write; never a cell."""
    import shutil

    real = cells.load_benchmark()
    src = os.path.join(cells.HERE, "configs", "mmbert32k-bank")
    toy = os.path.join(cells.WORK_DIR, "toy_config")
    os.makedirs(toy, exist_ok=True)
    with open(os.path.join(src, "model.json")) as f:
        model = json.load(f)
    model.update(TOY_MODEL)
    model["tasks"]["pii"]["classifier_std"] = 0.1
    with open(os.path.join(toy, "model.json"), "w") as f:
        json.dump(model, f)
    with open(os.path.join(src, "router_config.yaml")) as f:
        text = f.read()
    assert "max_batch_size: 8" in text and "  - 128\n  - 512\n  - 8192" in text
    with open(os.path.join(toy, "router_config.yaml"), "w") as f:
        f.write(text.replace("max_batch_size: 8", "max_batch_size: 2")
                .replace("  - 128\n  - 512\n  - 8192", "  - 32\n  - 128"))
    yield {"configs": [{"name": "toy", "file": os.path.relpath(
               os.path.join(toy, "model.json"), cells.ROOT)}],
           "workloads": [{"name": "toy", "config": "toy", "traffic": "-",
                          "chips": 1}],
           "end_to_end": real["end_to_end"],
           "per_layer": [dict(m, workloads=["toy"])
                         for m in real["per_layer"]]}
    shutil.rmtree(toy, ignore_errors=True)


TOY = {"generator": "seeded_words",
       "lengths": {"law": "loguniform", "min": 8, "max": 100},
       "arrivals": {"mode": "closed", "clients": 2, "pool_per_s": 400},
       "shapes": {"buckets": [32, 128], "rows": [1, 2]},
       "warmup_requests": 4, "correctness_sample": 4}


@pytest.fixture()
def cpu_only(monkeypatch):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("the toy loop is a CPU rehearsal")


def test_whole_loop_on_the_cpu_is_correct_and_names_no_device_metric(
        cpu_only, toy_bench, capsys):
    from chipbench import run

    res = run.run_cell(toy_bench, "toy", 2**31 + 11, 3.0, False,
                       require_chip=False, workload=TOY)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10
    assert res["metrics"] == {} and res["device"]["platform"] == "cpu"
    assert set(res["cpu_rehearsal_values"]) == {
        "routes_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "compiles inside the window: 0" in out
    assert "compare seq_logit_rel_sq_err" in out \
        and "compare pii_score_mean_sq_diff" in out \
        and "compare embedding_one_minus_cos" in out
    assert not os.path.exists(os.path.join(cells.WORK_DIR, "toy"))


def test_broken_timed_path_comes_out_not_correct(cpu_only, toy_bench,
                                                 monkeypatch, capsys):
    """An answer altered where it is produced: the engine's softmax
    sharpened by a fifth.  Everything else of the run is as it is."""
    from chipbench import run
    from semantic_router_tpu.engine import classify

    sound = classify._softmax
    monkeypatch.setattr(classify, "_softmax", lambda x: sound(1.2 * x))
    res = run.run_cell(toy_bench, "toy", 2**31 + 12, 3.0, False,
                       require_chip=False, workload=TOY)
    assert res["correct"] is False
    assert "NOT CORRECT" in capsys.readouterr().out


def test_lower_precision_control_fails_the_limits():
    """The reference in bfloat16 at the published depth and width (short
    sequences, small vocabulary: what a test run can hold) must not pass."""
    from chipbench.tests.control_lower_precision import control_numbers

    bench = cells.load_benchmark()
    config = cells.load_config(bench, "mmbert32k-bank")
    numbers = control_numbers(config, 7, [120, 300], [128, 512],
                              vocab_size=2048)
    ok, lines = correctness.judge(
        cells.load_family(config).expected_numbers(config), numbers,
        correctness.load_limits(config))
    assert not ok, lines


def test_run_refuses_without_a_chip(cpu_only):
    from chipbench import run

    with pytest.raises(run.NoChip):
        run.run_cell(cells.load_benchmark(), "bank_long_context", 1, 1.0,
                     False)
