"""The sparse-attention latent guard's family
(``families/sparse_latent_ar_guard.py``) on the CPU at a toy size
(``data/sparse_latent_ar_toy/``: hidden 64, full layers whose indexer keeps 8
keys, sliding layers over a window of 5, 16 experts top-2 beside a shared
one with 8 of them and half the vocabulary held, float32), through the whole
loop of ``run.py``: a route goes ``Router.route`` -> ``signals/learned.py`` ->
``engine.guard_classify`` -> ``generate`` -> the batcher -> the generative
runner -> ``GreedyGenerator`` over ``models.dots3_note``; the cell is
``correct``; each fault in the mechanisms this configuration brings makes it
not so, by the number that is there for it; and the float8 control is over a
limit.  The toy is dropped into a COPY of ``chipbench/`` (its entries are
never in ``BENCHMARK.json``); what it prints carries no device metric's
name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "sparse_latent_ar_toy")

DRIVER = r"""
import json, sys
import chipbench
from chipbench import cells, run
from chipbench.tests import control_float8_weights
assert chipbench.__file__.startswith(sys.argv[1]), chipbench.__file__
bench = cells.load_benchmark()
SEED = 2**31 + 35
def cell():
    return run.run_cell(bench, "toy_sparse_latent_ar", SEED, 3.0, False,
                        require_chip=False)
out = {"sound": cell()}
out["both"] = control_float8_weights.sound_and_control(
    bench, "toy_sparse_latent_ar", SEED + 1, 2)
import jax.numpy as jnp
from semantic_router_tpu.models import generate, lfm2_moe
from semantic_router_tpu.models import dots3_note as M

def faulty(name, **patches):
    kept = {k: getattr(M, k) for k in patches}
    for k, v in patches.items():
        setattr(M, k, v)
    try:
        out[name] = cell()
    finally:
        for k, v in kept.items():
            setattr(M, k, v)

from_hf = M.Dots3NoteConfig.from_hf
def with_config(**changes):
    class Patched(M.Dots3NoteConfig):
        @classmethod
        def from_hf(cls, hf, **kw):
            import dataclasses
            return dataclasses.replace(from_hf(hf, **kw), **changes)
    return Patched

# the latents go on without the sqrt(hidden / rank)
faulty("no_rescale",
       Dots3NoteConfig=with_config(apply_mla_qkv_lora_rescale=False))
# a window of 4 keys where the model's is 5
faulty("window_off_by_one", Dots3NoteConfig=with_config(sliding_window_size=4))
# every head's gate is 1
gate_out = M._gate_out
faulty("no_gate", _gate_out=lambda cfg, g, p, h, o: gate_out(
    cfg, g, dict(p, gate_proj=p["gate_proj"] * 0), h, o * 2))
# the indexer's heads all weigh the same
weights = M._index_weights
faulty("no_head_weights",
       _index_weights=lambda cfg, p, h: jnp.ones_like(weights(cfg, p, h)))
# the latest index_topk keys instead of the indexer's
select = M.select_keys
faulty("latest_keys", select_keys=lambda s, visible, k: select(
    jnp.broadcast_to(jnp.arange(s.shape[-1], dtype=s.dtype), s.shape),
    visible, k))
# the top k of the bare sigmoids
route = M.route
faulty("no_bias", route=lambda cfg, p, x: route(
    cfg, dict(p, expert_bias=p["expert_bias"] * 0), x))
# the routed experts alone
moe = M.moe
faulty("no_shared", moe=lambda cfg, p, x, valid: moe(cfg, dict(
    p, shared=dict(p["shared"], down=p["shared"]["down"] * 0)), x, valid))

# one served token is not the one the model chose
inner = generate.GreedyGenerator.generate
def altered(self, *args, **kwargs):
    res = inner(self, *args, **kwargs)
    for r in res:
        if len(r.trajectory) > 2:
            e = r.trajectory[2]
            e["token"] = 2 + (e["token"] - 1) % 250
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
    assert c["sa_logit_rel_sq_err"]["value"] < 1e-8
    assert c["sa_transfer_gap_max"]["value"] < 1e-3
    assert c["sa_route_disagreement_share"]["value"] == 0.0
    assert c["sa_select_disagreement_share"]["value"] < 0.02
    assert c["compiles_in_window"]["value"] == 0
    out = toy_run["stdout"]
    # the warm-up went through engine.warmup: two programs a row count
    for rows in (1, 2, 4):
        assert f"warmup gen:jailbreak bucket=128 rows={rows} " in out
    assert "tasks ['jailbreak']" in out


@pytest.mark.parametrize("fault, number", [
    ("no_rescale", "sa_logit_rel_sq_err"),
    ("no_gate", "sa_logit_rel_sq_err"),
    ("no_head_weights", "sa_select_disagreement_share"),
    ("latest_keys", "sa_select_disagreement_share"),
    ("window_off_by_one", "sa_logit_rel_sq_err"),
    ("no_bias", "sa_route_disagreement_share"),
    ("no_shared", "sa_logit_rel_sq_err"),
    ("altered", "sa_transfer_gap_max")])
def test_a_fault_fails_a_limit(toy_run, fault, number):
    broken = toy_run[fault]
    assert broken["correct"] is False and broken["failed"] == 0
    shown = broken["compared"][number]
    assert shown["value"] > shown["limit"], broken["compared"]


def test_float8_weights_in_the_programs_place_are_not_correct(toy_run):
    both = toy_run["both"]
    assert both["sound"]["sa_logit_rel_sq_err"] < 1e-8
    assert both["sound"]["sa_select_disagreement_share"] < 0.02
    assert both["control"]["sa_logit_rel_sq_err"] > 1e-6
    assert both["control"]["sa_select_disagreement_share"] > 0.02


def test_a_program_without_the_decoder_is_refused_at_once(monkeypatch):
    """What the new files do on the parent commit: the family's first call
    ends the run with an error, before anything is built."""
    import semantic_router_tpu.models as models

    family = cells.load_module("families", "sparse_latent_ar_guard")
    monkeypatch.setitem(sys.modules, "semantic_router_tpu.models.dots3_note",
                        None)
    monkeypatch.delattr(models, "dots3_note", raising=False)
    with pytest.raises(SystemExit, match="model_type dots3_note"):
        family.write_checkpoints("/nonexistent", {"tasks": {}}, 1)
