"""The block-diffusion guard's family (``families/blockdiff_guard.py``) on
the CPU at a toy size (``data/blockdiff_toy/``: hidden 64, 8 experts top-2,
2 layers, block 4, float32), through the whole loop of ``run.py``: a route
goes ``Router.route`` -> ``signals/learned.py`` -> ``engine.guard_classify``
-> ``generate`` -> the batcher -> the generative runner, the cell is
``correct``, with one served token altered it is not, and the float8
control is over a limit.  The toy is dropped into a COPY of ``chipbench/``
(its entries are never in ``BENCHMARK.json``); what it prints carries no
device metric's name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "blockdiff_toy")

DRIVER = r"""
import json, sys
import chipbench
from chipbench import cells, run
from chipbench.tests import control_float8_weights
assert chipbench.__file__.startswith(sys.argv[1]), chipbench.__file__
bench = cells.load_benchmark()
sound = run.run_cell(bench, "toy_blockdiff", 2**31 + 28, 3.0, False,
                     require_chip=False)
both = control_float8_weights.sound_and_control(bench, "toy_blockdiff",
                                                2**31 + 29, 2)
from semantic_router_tpu.models import generate
inner = generate.BlockDiffusionGenerator.generate
def altered(self, *args, **kwargs):
    out = inner(self, *args, **kwargs)
    for r in out:
        e = next(e for e in r.trajectory if e["kind"] == "denoise")
        j = int(e["filled"].argmax())
        e["tokens_after"] = e["tokens_after"].copy()
        e["tokens_after"][j] = 2 + (e["tokens_after"][j] - 1) % 500
    return out
generate.BlockDiffusionGenerator.generate = altered
broken = run.run_cell(bench, "toy_blockdiff", 2**31 + 28, 3.0, False,
                      require_chip=False)
print("RESULTS " + json.dumps([sound, broken, both]))
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
    sound, broken, both = json.loads(line[len("RESULTS "):])
    return {"sound": sound, "broken": broken, "both": both,
            "stdout": p.stdout}


def test_the_guards_cell_runs_and_is_correct(toy_run):
    sound = toy_run["sound"]
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["attempted"] >= 3
    assert sound["metrics"] == {} and sound["device"]["platform"] == "cpu"
    c = sound["compared"]
    assert c["gen_logit_rel_sq_err"]["value"] < 1e-8
    assert c["gen_transfer_gap_max"]["value"] < 1e-3
    assert c["moe_route_disagreement_share"]["value"] == 0.0
    assert c["compiles_in_window"]["value"] == 0
    out = toy_run["stdout"]
    # the warm-up went through engine.warmup: three programs a row count
    for rows in (1, 2, 4):
        assert f"warmup gen:jailbreak bucket=128 rows={rows} " in out
    assert "tasks ['jailbreak']" in out


def test_one_altered_token_is_not_correct(toy_run):
    broken = toy_run["broken"]
    assert broken["correct"] is False and broken["failed"] == 0
    shown = broken["compared"]["gen_transfer_gap_max"]
    assert shown["value"] > shown["limit"]
    assert broken["compared"]["gen_logit_rel_sq_err"]["value"] < 1e-8


def test_float8_weights_in_the_programs_place_are_not_correct(toy_run):
    both = toy_run["both"]
    assert both["sound"]["gen_logit_rel_sq_err"] < 1e-8
    assert both["control"]["gen_logit_rel_sq_err"] > \
        1e4 * max(both["sound"]["gen_logit_rel_sq_err"], 1e-12)
    assert both["control"]["gen_logit_rel_sq_err"] > 1e-8


def test_the_program_without_trajectories_is_refused_at_once(monkeypatch):
    """What the new files do on the parent commit: the family's first call
    ends the run with an error."""
    from semantic_router_tpu.models import generate

    family = cells.load_module("families", "blockdiff_guard")
    fields = dict(generate.GenerationResult.__dataclass_fields__)
    fields.pop("trajectory")
    monkeypatch.setattr(generate.GenerationResult, "__dataclass_fields__",
                        fields)
    with pytest.raises(SystemExit, match="per-forward trajectories"):
        family.write_checkpoints("/nonexistent", {"tasks": {}}, 1)


_SOUND = {"gen_logit_rel_sq_err": 1e-4, "gen_transfer_gap_max": 0.2,
          "moe_route_disagreement_share": 0.04}
_LOW = {"gen_logit_rel_sq_err": 1e-3, "gen_transfer_gap_max": 0.4,
        "moe_route_disagreement_share": 0.49}


@pytest.mark.parametrize("sound, control, code", [
    (_SOUND, _LOW, 0), (_SOUND, _SOUND, 1), (_LOW, _LOW, 1)],
    ids=["control_outside", "control_within", "program_outside"])
def test_the_control_script_fails_when_the_control_does_not(
        monkeypatch, sound, control, code):
    """Its exit code says whether the limits did their work: the program
    within them and the float8 control outside, on the cell's own
    ``limits.json``."""
    from chipbench.tests import control_float8_weights as script

    monkeypatch.setattr(
        script, "sound_and_control",
        lambda *a: {"sound": dict(sound), "control": dict(control)})
    assert script.main(["--workload", "guard_chat_blockdiff",
                        "--seeds", "5"]) == code
