"""The token-at-a-time hybrid guard's family (``families/hybrid_ar_guard.py``)
on the CPU at a toy size (``data/hybrid_ar_toy/``: hidden 64, conv + attention
layers, 8 experts top-2 behind the sigmoid router, float32), through the whole
loop of ``run.py``: a route goes ``Router.route`` -> ``signals/learned.py`` ->
``engine.guard_classify`` -> ``generate`` -> the batcher -> the generative
runner -> ``GreedyGenerator`` over ``models.lfm2_moe``; the cell is ``correct``;
with a wrong conv tap, a dropped selection bias or one altered token it is
not; and the float8 control is over a limit.  The toy is dropped into a COPY
of ``chipbench/`` (its entries are never in ``BENCHMARK.json``); what it
prints carries no device metric's name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "hybrid_ar_toy")

DRIVER = r"""
import json, sys
import chipbench
from chipbench import cells, run
from chipbench.tests import control_float8_weights
assert chipbench.__file__.startswith(sys.argv[1]), chipbench.__file__
bench = cells.load_benchmark()
SEED = 2**31 + 32
def cell():
    return run.run_cell(bench, "toy_hybrid_ar", SEED, 3.0, False,
                        require_chip=False)
out = {"sound": cell()}
out["both"] = control_float8_weights.sound_and_control(
    bench, "toy_hybrid_ar", SEED + 1, 2)
from semantic_router_tpu.models import generate, lfm2_moe

# the taps in the wrong order: tap 0 on the token itself
taps = lfm2_moe._taps
lfm2_moe._taps = lambda p, window: taps(
    dict(p, conv_w=p["conv_w"][::-1]), window)
out["wrong_tap"] = cell()
lfm2_moe._taps = taps

# the selection bias dropped: the top k of the bare sigmoids
from_hf = lfm2_moe.Lfm2MoeConfig.from_hf
lfm2_moe.Lfm2MoeConfig.from_hf = classmethod(
    lambda cls, hf, **kw: from_hf(hf, **dict(kw, use_expert_bias=False)))
out["no_bias"] = cell()
lfm2_moe.Lfm2MoeConfig.from_hf = from_hf

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
    assert c["ar_logit_rel_sq_err"]["value"] < 1e-8
    assert c["ar_transfer_gap_max"]["value"] < 1e-3
    assert c["ar_route_disagreement_share"]["value"] == 0.0
    assert c["compiles_in_window"]["value"] == 0
    out = toy_run["stdout"]
    # the warm-up went through engine.warmup: two programs a row count
    for rows in (1, 2, 4):
        assert f"warmup gen:jailbreak bucket=128 rows={rows} " in out
    assert "tasks ['jailbreak']" in out


@pytest.mark.parametrize("fault, number", [
    ("wrong_tap", "ar_logit_rel_sq_err"),
    ("no_bias", "ar_route_disagreement_share"),
    ("altered", "ar_transfer_gap_max")])
def test_a_fault_fails_a_limit(toy_run, fault, number):
    broken = toy_run[fault]
    assert broken["correct"] is False and broken["failed"] == 0
    shown = broken["compared"][number]
    assert shown["value"] > shown["limit"], broken["compared"]


def test_float8_weights_in_the_programs_place_are_not_correct(toy_run):
    both = toy_run["both"]
    assert both["sound"]["ar_logit_rel_sq_err"] < 1e-8
    assert both["control"]["ar_logit_rel_sq_err"] > 1e-6
    assert both["control"]["ar_route_disagreement_share"] > 0.02


def test_a_program_without_the_decoder_is_refused_at_once(monkeypatch):
    """What the new files do on the parent commit: the family's first call
    ends the run with an error, before anything is built."""
    import semantic_router_tpu.models as models

    family = cells.load_module("families", "hybrid_ar_guard")
    monkeypatch.setitem(sys.modules, "semantic_router_tpu.models.lfm2_moe",
                        None)
    monkeypatch.delattr(models, "lfm2_moe", raising=False)
    with pytest.raises(SystemExit, match="model_type lfm2_moe"):
        family.write_checkpoints("/nonexistent", {"tasks": {}}, 1)
