"""The linear-attention guard's family (``families/linear_attn_ar_guard.py``)
on the CPU at a toy size (``data/linear_attn_ar_toy/``: hidden 60, three
gated-delta-rule layers of 3 heads x 12 x 24 to one full layer, float32),
through the whole loop of ``run.py``: a route goes ``Router.route`` ->
``signals/learned.py`` -> ``engine.guard_classify`` -> ``generate`` -> the
batcher -> the generative runner -> ``GreedyGenerator`` over
``models.olmo_hybrid``; the cell is ``correct``; with reversed taps, a beta
not doubled or one altered token it is not; the float8 control is over a
limit and the bfloat16-state control reads what it reads.  The readers run on
a synthetic trace, the opcount against a hand count.  The toy is dropped
into a COPY of ``chipbench/`` (its entries are never in ``BENCHMARK.json``);
what it prints carries no device metric's name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "linear_attn_ar_toy")

DRIVER = r"""
import json, sys
import chipbench
from chipbench import cells, run
from chipbench.tests import control_float8_weights, control_state_precision
assert chipbench.__file__.startswith(sys.argv[1]), chipbench.__file__
bench = cells.load_benchmark()
SEED = 2**31 + 47
def cell():
    return run.run_cell(bench, "toy_linear_attn_ar", SEED, 3.0, False,
                        require_chip=False)
out = {"sound": cell()}
out["both"] = control_float8_weights.sound_and_control(
    bench, "toy_linear_attn_ar", SEED + 1, 2)
out["controls"] = control_state_precision.sound_and_controls(
    bench, "toy_linear_attn_ar", SEED + 1, 2)
from semantic_router_tpu.models import generate, olmo_hybrid

# the taps in the wrong order: tap 0 on the token itself
taps = olmo_hybrid._taps
olmo_hybrid._taps = lambda p, window: taps(
    dict(p, conv_w=p["conv_w"][::-1]), window)
out["wrong_tap"] = cell()
olmo_hybrid._taps = taps

# beta not doubled: the config's negative eigenvalues ignored
from_hf = olmo_hybrid.OlmoHybridConfig.from_hf
olmo_hybrid.OlmoHybridConfig.from_hf = classmethod(
    lambda cls, hf, **kw: from_hf(
        hf, **dict(kw, linear_allow_neg_eigval=False)))
out["beta_not_doubled"] = cell()
olmo_hybrid.OlmoHybridConfig.from_hf = from_hf

# one served token is not the one the model chose
inner = generate.GreedyGenerator.generate
def altered(self, *args, **kwargs):
    res = inner(self, *args, **kwargs)
    for r in res:
        if len(r.trajectory) > 2:
            e = r.trajectory[2]
            e["token"] = 2 + (e["token"] - 1) % 500
    return res
generate.GreedyGenerator.generate = altered
out["altered"] = cell()
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("checkout"))
    copy = os.path.join(tmp, "chipbench")
    shutil.copytree(cells.HERE, copy, ignore=shutil.ignore_patterns(
        "__pycache__"))
    for d, _, files in os.walk(TOY):
        for f in files:
            if f == "entries.json":
                continue
            rel = os.path.relpath(os.path.join(d, f), TOY)
            os.makedirs(os.path.dirname(os.path.join(copy, rel)),
                        exist_ok=True)
            shutil.copy(os.path.join(d, f), os.path.join(copy, rel))
    bench = cells.load_benchmark()
    with open(os.path.join(TOY, "entries.json")) as f:
        for key, new in json.load(f).items():
            bench[key] = bench[key] + new
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", DRIVER, tmp], cwd=tmp, env=env,
                       capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    (line,) = [l for l in p.stdout.splitlines() if l.startswith("RESULTS ")]
    return dict(json.loads(line[len("RESULTS "):]), stdout=p.stdout)


def test_the_guards_cell_runs_and_is_correct(toy_run):
    sound = toy_run["sound"]
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["attempted"] >= 3
    assert sound["metrics"] == {} and sound["device"]["platform"] == "cpu"
    c = sound["compared"]
    assert c["lin_logit_rel_sq_err"]["value"] < 1e-8
    assert c["lin_transfer_gap_max"]["value"] < 1e-3
    assert c["compiles_in_window"]["value"] == 0
    out = toy_run["stdout"]
    # the warm-up went through engine.warmup: two programs a row count
    for rows in (1, 2, 4):
        assert f"warmup gen:jailbreak bucket=128 rows={rows} " in out
    assert "tasks ['jailbreak']" in out


@pytest.mark.parametrize("fault, number", [
    ("wrong_tap", "lin_logit_rel_sq_err"),
    ("beta_not_doubled", "lin_logit_rel_sq_err"),
    ("altered", "lin_transfer_gap_max")])
def test_a_fault_fails_a_limit(toy_run, fault, number):
    broken = toy_run[fault]
    assert broken["correct"] is False and broken["failed"] == 0
    shown = broken["compared"][number]
    assert shown["value"] > shown["limit"], broken["compared"]


def test_float8_weights_in_the_programs_place_are_not_correct(toy_run):
    both = toy_run["both"]
    assert both["sound"]["lin_logit_rel_sq_err"] < 1e-8
    assert both["control"]["lin_logit_rel_sq_err"] > 1e-6


def test_the_state_control_reads_both_controls_on_one_trajectory(toy_run):
    """``control_state_precision``: the float8 weights and the bfloat16
    state in the program's place, over the SAME served trajectories; the
    state's rounding is the smaller fault by orders, and is read."""
    c = toy_run["controls"]
    assert c["sound"]["lin_logit_rel_sq_err"] < 1e-8
    f8 = c["float8_e4m3_weights"]["lin_logit_rel_sq_err"]
    bf = c["bfloat16_state"]["lin_logit_rel_sq_err"]
    assert f8 > 1e-6 and 0 < bf < f8


def test_a_program_without_the_decoder_is_refused_at_once(monkeypatch):
    """What the new files do on the parent commit: the family's first call
    ends the run with an error that names the type, before anything is
    built."""
    import semantic_router_tpu.models as models

    family = cells.load_module("families", "linear_attn_ar_guard")
    monkeypatch.setitem(sys.modules,
                        "semantic_router_tpu.models.olmo_hybrid", None)
    monkeypatch.delattr(models, "olmo_hybrid", raising=False)
    with pytest.raises(SystemExit, match="model_type olmo_hybrid"):
        family.write_checkpoints("/nonexistent", {"tasks": {}}, 1)


MODEL = {"linear_num_key_heads": 30, "linear_num_value_heads": 30,
         "linear_key_head_dim": 96, "linear_value_head_dim": 192,
         "num_attention_heads": 30, "hidden_size": 3840,
         "intermediate_size": 11008, "vocab_size": 100352,
         "layer_types": ["linear_attention"] * 3 + ["full_attention"]}


def test_the_delta_rules_count_is_the_hand_count():
    oc = cells.load_module("opcount", "gated_delta_rule")
    cost = oc.row_cost(8192, MODEL)
    # decay + S^T k + rank-one update + S^T q: 1 + 2 + 2 + 2 a state entry
    assert cost["flops"] == 7 * 96 * 192 * 30 * 8192 == 31708938240
    # q, k, v, o in bfloat16; g, beta float32; the state in and out
    assert cost["bytes"] == (2 * (96 + 192) * 30 * 8192 * 2
                             + 2 * 30 * 8192 * 4
                             + 2 * 30 * 96 * 192 * 4) == 289505280
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = cells.load_module("opcount", "flash_attention") \
        .least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "memory" and 0.34e-3 < least < 0.36e-3
    assert oc.SCOPE == "linear_attn/scan"


def test_the_full_cores_count_is_the_hand_count():
    oc = cells.load_module("opcount", "mha_attention")
    cost = oc.row_cost(4096, MODEL)
    assert cost["flops"] == 4 * (4096 * 4097 // 2) * 128 * 30
    assert cost["bytes"] == 4 * 4096 * 30 * 128 * 2


def test_the_readers_on_a_synthetic_trace(monkeypatch):
    """The eleven readers over two traced prefills (rows of 8000 + 4000 and
    of 2100 tokens) whose steps, markers and scope times are given: each
    number is the hand count, and on a program without the markers and
    scopes — this PR's parent — every reader gives None and raises
    nothing."""
    lin = cells.load_module("layer_metrics", "_lin_spans")
    gen = cells.load_module("layer_metrics", "_gen_spans")
    mix = cells.load_module("layer_metrics", "_mix_spans")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"trace": {"peaks": peaks, "path": "none", "completed": [1, 2, 3]},
           "config": {"model": MODEL,
                      "engine": {"seq_len_buckets": [8192]}}}
    steps = [types.SimpleNamespace(start=0.0, end=2.0, device=(0.1, 1.9),
                                   facts={"flavour": "gen.prefill"}),
             types.SimpleNamespace(start=3.0, end=4.0, device=(3.1, 3.9),
                                   facts={"flavour": "gen.prefill"})]
    rows = [[8000, 4000], [2100]]
    monkeypatch.setattr(mix, "_lengths_by_step",
                        lambda run, which: list(zip(steps, rows)))
    seconds = {"linear_attn/scan": 0.05, "attn/core": 0.02,
               "linear_attn/conv1d": 0.03, "attn": 0.06, "mlp": 0.6}
    monkeypatch.setattr(gen, "scope_seconds",
                        lambda run, scope, within=None: seconds[scope])
    monkeypatch.setattr(gen, "steps", lambda run: steps)
    marks = [(st, {"flavour": "gen.prefill", "cache_bytes_full": 950,
                   "cache_bytes_state": 40, "cache_bytes_conv": 10})
             for st in steps]
    monkeypatch.setattr(mix, "forwards", lambda run, flavour, which: marks)

    def read(name):
        return cells.load_module("layer_metrics", name).read(run)

    scan = cells.load_module("opcount", "gated_delta_rule")
    least = sum(3 * scan.row_cost(n, MODEL)["bytes"]
                for n in (8000, 4000, 2100)) / 819e9
    assert read("lin_delta_rule_roofline.prefill") == pytest.approx(
        least / 0.10 * 100.0)
    core = cells.load_module("opcount", "mha_attention")
    flops = sum(core.row_cost(n, MODEL)["flops"] for n in (8000, 4000, 2100))
    assert read("lin_flash_roofline.full") == pytest.approx(
        flops / 197e12 / 0.04 * 100.0)
    assert read("lin_state_cache_share") == pytest.approx(5.0)
    assert read("lin_scan_device_ms_per_route") == pytest.approx(50 / 3)
    assert read("lin_conv_device_ms_per_route") == pytest.approx(10.0)
    assert read("lin_attn_full_device_ms_per_route") == pytest.approx(20.0)
    assert read("lin_mlp_device_ms_per_route") == pytest.approx(200.0)
    assert read("lin_step_ms.prefill") == pytest.approx(1500.0)
    assert read("lin_step_ms.decode") is None
    # 2 x the matrix parameters a token passes: a period's 3 linear + 1 full
    per_token = 2 * (3 * (3840 * (2 * 2880 + 2 * 5760 + 60) + 5760 * 3840)
                     + 4 * 3840 * 3840 + 4 * 3 * 3840 * 11008)
    assert lin.token_flops(MODEL) == per_token
    want = sum(n * per_token + 2 * 100352 * 3840
               + 3 * scan.row_cost(n, MODEL)["flops"]
               + core.row_cost(n, MODEL)["flops"]
               for n in (8000, 4000, 2100))
    mfu = read("lin_prefill_mfu")
    assert mfu == pytest.approx(want / (3.0 * 197e12) * 100.0) and mfu < 100

    # the parent: no steps, no markers, no scopes
    monkeypatch.setattr(mix, "_lengths_by_step", lambda run, which: [])
    monkeypatch.setattr(mix, "forwards", lambda run, flavour, which: [])
    monkeypatch.setattr(gen, "steps", lambda run: None)
    monkeypatch.setattr(gen, "scope_seconds",
                        lambda run, scope, within=None: None)
    for m in cells.load_benchmark()["per_layer"]:
        if m["name"].startswith("lin_") \
                and m["name"] != "lin_device_idle_share":
            assert read(m["name"]) is None, m["name"]


def test_benchmark_appends_the_cell_and_its_eleven_metrics():
    bench = cells.load_benchmark()
    assert bench["configs"][-1]["name"] == "olmo-hybrid-7b-guard"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("guard_longdoc_linear", "olmo-hybrid-7b-guard",
            "guard_longdoc_linear", 1)
    mine = [m for m in bench["per_layer"] if m["name"].startswith("lin_")]
    assert [m["name"] for m in bench["per_layer"][-11:]] \
        == [m["name"] for m in mine] and len(mine) == 11
    for m in mine:
        assert m["workloads"] == ["guard_longdoc_linear"]
        assert m["moves"] == "routes_per_s"
        cells.load_module("layer_metrics", m["name"])
    config = cells.load_config(bench, "olmo-hybrid-7b-guard")
    assert config["model"]["layer_types"] \
        == (["linear_attention"] * 3 + ["full_attention"]) * 3
    assert cells.load_workload("guard_longdoc_linear") \
        == cells.load_workload("guard_longdoc_ar")
