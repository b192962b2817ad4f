"""The self-drafting latent guard's family
(``families/latent_mtp_ar_guard.py``) on the CPU at a toy size
(``data/latent_mtp_ar_toy/``: hidden 64, 3 layers of 4 latent heads, 16
experts top-2 beside a shared one with 8 of them held, an MTP module,
float32), through the whole loop of ``run.py``: a route goes ``Router.route``
-> ``signals/learned.py`` -> ``engine.guard_classify`` -> ``generate`` -> the
batcher -> the generative runner -> ``GreedyGenerator`` over
``models.joyai_llm_flash``, whose decode steps commit one or two tokens a
row; the cell is ``correct``; each fault in what this configuration brings
makes it not so, by the number that is there for it; and the float8 control
is over a limit.  The toy is dropped into a COPY of ``chipbench/`` (its
entries are never in ``BENCHMARK.json``); what it prints carries no device
metric's name.  Then the family's contract, the walk the reference derives,
and the ``mtp_*`` readers on a recorded toy run."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "latent_mtp_ar_toy")

DRIVER = r"""
import json, sys
import chipbench
from chipbench import cells, run
from chipbench.tests import control_float8_weights
assert chipbench.__file__.startswith(sys.argv[1]), chipbench.__file__
bench = cells.load_benchmark()
SEED = 2**31 + 40
def cell():
    return run.run_cell(bench, "toy_latent_mtp_ar", SEED, 3.0, False,
                        require_chip=False)
out = {"sound": cell()}
out["both"] = control_float8_weights.sound_and_control(
    bench, "toy_latent_mtp_ar", SEED + 1, 2)
import jax.numpy as jnp
from semantic_router_tpu.models import generate
from semantic_router_tpu.models import joyai_llm_flash as M

def faulty(name, module=M, **patches):
    kept = {k: getattr(module, k) for k in patches}
    for k, v in patches.items():
        setattr(module, k, v)
    try:
        out[name] = cell()
    finally:
        for k, v in kept.items():
            setattr(module, k, v)

# a step's second position does not see the first
seen = M._seen
faulty("second_blind", _seen=lambda pos, m: seen(pos, m) & ~(
    (jnp.arange(m)[None, None, :] == pos[:, :1, None])
    & (jnp.arange(pos.shape[1])[None, :, None] == 1)))
# W_eh reads [h ; Emb] where the module's is [Emb ; h]
mtp_input = M._mtp_input
faulty("eh_swapped", _mtp_input=lambda cfg, m, e, h: mtp_input(
    cfg, dict(m, enorm=m["hnorm"], hnorm=m["enorm"]), h, e))
# the top k of the bare sigmoids (the expert layer is dots3_note's)
from semantic_router_tpu.models import dots3_note
route = dots3_note.route
faulty("no_bias", dots3_note, route=lambda cfg, p, x: route(
    cfg, dict(p, expert_bias=p["expert_bias"] * 0), x))
# a rejected draft's column stays counted: two columns on, whatever
faulty("rejected_counted", generate, advance=lambda positions, accepted, last:
       jnp.minimum(positions + 2, last))

# the accept bit is not the comparison of the draft with the choice
inner = generate.GreedyGenerator.generate
def flipped(self, *args, **kwargs):
    res = inner(self, *args, **kwargs)
    for r in res:
        for e in r.trajectory:
            if "accepted" in e:
                e["accepted"] = not e["accepted"]
    return res
generate.GreedyGenerator.generate = flipped
out["accept_flipped"] = cell()
# one served token is not the one the model chose
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
    assert c["mtp_logit_rel_sq_err"]["value"] < 1e-8
    assert c["mtp_draft_logit_rel_sq_err"]["value"] < 1e-8
    assert c["mtp_transfer_gap_max"]["value"] < 1e-3
    assert c["mtp_route_disagreement_share"]["value"] == 0.0
    assert c["mtp_accept_disagreement_share"]["value"] == 0.0
    assert c["compiles_in_window"]["value"] == 0
    out = toy_run["stdout"]
    # the warm-up went through engine.warmup: two programs a row count
    for rows in (1, 2, 4):
        assert f"warmup gen:jailbreak bucket=128 rows={rows} " in out
    assert "tasks ['jailbreak']" in out


@pytest.mark.parametrize("fault, number", [
    ("second_blind", "mtp_logit_rel_sq_err"),
    ("rejected_counted", "mtp_logit_rel_sq_err"),
    ("eh_swapped", "mtp_draft_logit_rel_sq_err"),
    ("no_bias", "mtp_route_disagreement_share"),
    ("accept_flipped", "mtp_accept_disagreement_share"),
    ("altered", "mtp_transfer_gap_max")])
def test_a_fault_fails_a_limit(toy_run, fault, number):
    broken = toy_run[fault]
    assert broken["correct"] is False and broken["failed"] == 0
    shown = broken["compared"][number]
    assert shown["value"] > shown["limit"], broken["compared"]


def test_float8_weights_in_the_programs_place_are_not_correct(toy_run):
    both = toy_run["both"]
    assert both["sound"]["mtp_logit_rel_sq_err"] < 1e-8
    assert both["sound"]["mtp_draft_logit_rel_sq_err"] < 1e-8
    assert both["control"]["mtp_logit_rel_sq_err"] > 1e-6
    assert both["control"]["mtp_draft_logit_rel_sq_err"] > 1e-6


def test_a_program_without_the_decoder_is_refused_at_once(monkeypatch):
    """What the new files do on the parent commit: the family's first call
    ends the run with an error, before anything is built."""
    import semantic_router_tpu.models as models

    family = cells.load_module("families", "latent_mtp_ar_guard")
    monkeypatch.setitem(
        sys.modules, "semantic_router_tpu.models.joyai_llm_flash", None)
    monkeypatch.delattr(models, "joyai_llm_flash", raising=False)
    with pytest.raises(SystemExit, match="model_type joyai_llm_flash"):
        family.write_checkpoints("/nonexistent", {"tasks": {}}, 1)


# -- the family's contract, and the cell's own files ----------------------------------


def test_the_family_keeps_the_contract_and_the_cell_names_its_files():
    from chipbench import families

    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, "guard_chat_mtp")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-flash-guard", "guard_chat_mtp", 1)
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    config = cells.load_config(bench, cell["config"])
    family = cells.load_family(config)
    for name in families.CONTRACT:
        assert hasattr(family, name), name
    assert config["model"]["model_type"] == "joyai_llm_flash"
    assert family.share(config) == (0, 128)
    assert family.published_model(config)["n_routed_experts"] == 256
    from chipbench import correctness

    limits = correctness.load_limits(config)
    for number in family.expected_numbers(config):
        assert {"limit", "sound", "control", "why"} <= set(limits[number])
    wl = cells.load_workload(cell["traffic"])
    assert wl["arrivals"]["clients"] == 16 and wl["shapes"] == {
        "buckets": [512], "rows": [1, 2, 4, 8, 16]}
    assert wl["arrivals"]["pool_per_s"] % 16 == 0 \
        and wl["arrivals"]["pool_per_s"] >= 96
    # every new metric has its reader, and reads nothing on a run with no
    # trace (the parent's traced runs of the other cells never call them)
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["guard_chat_mtp"]]
    assert len(mine) == 13 and all(m["name"].startswith("mtp_")
                                   and m["moves"] == "routes_per_s"
                                   for m in mine)
    # appended in one piece behind what was there (not held to be the
    # LAST: the next cell's metrics will come behind these)
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + 13] == mine
    assert not [m for m in bench["per_layer"][:first]
                if m["name"].startswith("mtp_")]
    for m in mine:
        assert cells.load_module("layer_metrics", m["name"]).read(
            {"trace": None}) is None


def test_the_cut_is_what_the_file_says():
    """The parameters of the held model, counted from the file's numbers:
    4,422 M by ISSUE 40's count."""
    bench = cells.load_benchmark()
    m = cells.load_config(bench, "joyai-flash-guard")["model"]
    H, I, E = m["hidden_size"], m["moe_intermediate_size"], 256
    heads, rq, rkv = (m["num_attention_heads"], m["q_lora_rank"],
                      m["kv_lora_rank"])
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    attn = H * rq + rq + rq * heads * (nope + rope) + H * (rkv + rope) \
        + rkv + rkv * heads * (nope + v) + heads * v * H
    norms = 2 * H
    expert_layer = attn + norms + H * E + E \
        + (m["n_routed_experts"] + m["n_shared_experts"]) * 3 * H * I
    dense_layer = attn + norms + 3 * H * m["intermediate_size"]
    n = dense_layer + (m["num_hidden_layers"] - 1) * expert_layer \
        + 2 * m["vocab_size"] * H + H \
        + (2 * H * H + 3 * H + expert_layer)
    assert abs(n / 1e6 - 4422) < 1, n


def test_the_reference_derives_the_walk():
    ref = cells.load_module("reference", "joyai_llm_flash")
    tokens = [3, 4, 10, 11, 12, 13, 14, 15]  # a prompt of 2
    drafts = {1: 11, 2: 0, 3: 0, 4: 14, 5: 0}  # drafts[i] is for i + 2
    walk, steps = ref.accept_walk(tokens, drafts, 2)
    assert walk == [(2, True), (4, False), (5, True)] and steps == 3
    family = cells.load_module("families", "latent_mtp_ar_guard")
    traj = [{"position": 1, "token": 10},
            {"position": 2, "token": 11, "accepted": True},
            {"position": 3, "token": 12},
            {"position": 4, "token": 13, "accepted": False}]
    assert [e["position"] for e in family.steps_of(traj)] == [2, 4]
    assert family._row_of(traj) == {1: 0, 2: 1, 3: 2, 4: 3}


# -- the readers, on a toy generation recorded here -----------------------------------


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A toy generation of four rows through a real ``InferenceEngine``
    under a profiler session on the CPU (no device plane: the readers of
    the markers' counts need none)."""
    import glob

    import jax

    sys.path.insert(0, os.path.join(cells.ROOT, "tests"))
    import test_joyai_llm_flash as toy
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine
    from semantic_router_tpu.models.generate import GreedyGenerator

    _, _, cfg, params = toy.variant()
    gen = GreedyGenerator(cfg, params, toy.WordTokenizer(),
                          model=toy.M.CachedModel(cfg), gen_length=9,
                          top_logits=4)
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=1.0, seq_len_buckets=[32]))
    eng.register_generative("guard", gen)
    prompts = [toy.words(r) for r in toy.prompts(8, (30, 12, 21, 5))]
    log_dir = str(tmp_path_factory.mktemp("trace"))
    try:
        eng.generate("guard", prompts, max_new_tokens=9)  # compiles
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            out = eng.generate("guard", prompts, max_new_tokens=9)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return {"trace": {"path": path, "completed": [1] * 4, "peaks": None}}, out


def test_the_readers_count_what_the_steps_did(recorded):
    run, out = recorded
    bits = [[e["accepted"] for e in r.trajectory if "accepted" in e]
            for r in out]
    drafted = sum(len(b) for b in bits)
    accepted = sum(sum(b) for b in bits)
    read = lambda name: cells.load_module(  # noqa: E731
        "layer_metrics", name).read(run)
    assert read("mtp_accept_rate") == pytest.approx(accepted / drafted)
    # 8 tokens a row after the prefill's, over the (row, step) pairs
    assert read("mtp_tokens_per_step") == pytest.approx(4 * 8 / drafted)
    assert read("mtp_steps_per_route") == pytest.approx(drafted / 4)
    assert 4 <= drafted / 4 <= 8
    # two expert layers and the drafter's block, of 8 held
    assert 0 < read("mtp_experts_touched_per_step") <= 8
    assert read("mtp_step_ms.decode") > 0 and read("mtp_step_ms.prefill") > 0
    # what needs the device's ops reads nothing on a CPU's trace
    for name in ("mtp_verify_device_ms_per_route", "mtp_moe_gmm_roofline.decode",
                 "mtp_decode_turnaround_ms"):
        assert read(name) is None
    spans = cells.load_module("layer_metrics", "_mtp_spans")
    marks = spans.steps(run)
    assert sum(int(m["committed_tokens"]) for m in marks) == 4 * 8
    assert all(int(m["layers"]) == 3 for m in marks)
    assert np.all([int(m["drafted"]) <= 4 for m in marks])
