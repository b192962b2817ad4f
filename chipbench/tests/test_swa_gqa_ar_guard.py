"""The window-and-full guard's family (``families/swa_gqa_ar_guard.py``) on
the CPU at a toy size (``data/swa_gqa_ar_toy/``: hidden 64, full layers of 4
heads and sliding layers of 6 over 2 k/v heads of 16, a window of 8, YaRN
over half a head in the full layers, 16 experts top-3 beside a gated shared
one with 8 of them and half the vocabulary held, float32; TWO prompt buckets,
96 and 256, and a 3:1 mixture of short and long prompts in one queue),
through the whole loop of ``run.py``: a route goes ``Router.route`` ->
``signals/learned.py`` -> ``engine.guard_classify`` -> ``generate`` -> the
batcher -> the generative runner -> ``GreedyGenerator`` over
``models.laguna``; the cell is ``correct`` with both buckets warmed and
served; each fault in the mechanisms this configuration brings makes it not
so, by the number that is there for it; the float8 control is over a limit;
and the ``mix_*`` readers on a recorded toy run with both buckets in it.  The
toy is dropped into a COPY of ``chipbench/`` (its entries are never in
``BENCHMARK.json``); what it prints carries no device metric's name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "swa_gqa_ar_toy")

DRIVER = r"""
import dataclasses, json, sys
import chipbench
from chipbench import cells, run
from chipbench.tests import control_float8_weights
assert chipbench.__file__.startswith(sys.argv[1]), chipbench.__file__
bench = cells.load_benchmark()
SEED = 2**31 + 42
def cell():
    return run.run_cell(bench, "toy_swa_gqa_ar", SEED, 3.0, False,
                        require_chip=False)
out = {"sound": cell()}
out["both"] = control_float8_weights.sound_and_control(
    bench, "toy_swa_gqa_ar", SEED + 1, 4)
from semantic_router_tpu.models import gated_window, generate
from semantic_router_tpu.models import laguna as M

def faulty(name, **patches):
    kept = {k: getattr(M, k) for k in patches}
    for k, v in patches.items():
        setattr(M, k, v)
    try:
        out[name] = cell()
    finally:
        for k, v in kept.items():
            setattr(M, k, v)

from_hf = M.LagunaConfig.from_hf
def with_config(change):
    class Patched(M.LagunaConfig):
        @classmethod
        def from_hf(cls, hf, **kw):
            return change(from_hf(hf, **kw))
    return Patched

def replace(**changes):
    return with_config(lambda cfg: dataclasses.replace(cfg, **changes))

# a window of 7 keys where the model's is 8
faulty("window_off_by_one", LagunaConfig=replace(sliding_window=7))
# a sliding layer sees every earlier key: a whole cache where a ring should be
faulty("whole_cache_for_a_ring", LagunaConfig=replace(sliding_window=512))
# all 16 dims of a full layer's heads rotate
faulty("full_width_rotary", LagunaConfig=with_config(
    lambda cfg: dataclasses.replace(cfg, rope=(
        dataclasses.replace(cfg.rope[0], rotary_dim=16), cfg.rope[1]))))
# every head's gate is 1
faulty("no_gate", gate_out=lambda p, h, o, dtype: gated_window.gate_out(
    dict(p, gate_proj=p["gate_proj"] * 0), h, o * 2, dtype))
# the chosen experts' weights sum to 1, not to 2.5
faulty("no_routed_scaling", LagunaConfig=replace(moe_routed_scaling_factor=1.0))

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
    assert sound["attempted"] >= 4
    assert sound["metrics"] == {} and sound["device"]["platform"] == "cpu"
    c = sound["compared"]
    assert c["mix_logit_rel_sq_err"]["value"] < 1e-8
    assert c["mix_transfer_gap_max"]["value"] < 1e-3
    assert c["mix_route_disagreement_share"]["value"] == 0.0
    assert c["compiles_in_window"]["value"] == 0
    out = toy_run["stdout"]
    # the warm-up went through engine.warmup: two programs a (rows, bucket)
    for bucket in (96, 256):
        for rows in (1, 2, 4):
            assert (f"warmup gen:jailbreak bucket={bucket} rows={rows} "
                    in out), (bucket, rows)
    assert "tasks ['jailbreak']" in out
    # the longest request compared is a long row, and short ones beside it
    (line,) = [l for l in out.splitlines()
               if l.startswith("reference: 3 requests of ")][:1]
    sizes = json.loads(line.split(" requests of ")[1].split(" tokens")[0])
    assert max(sizes) > 96 and min(sizes) <= 96, sizes


@pytest.mark.parametrize("fault, number", [
    ("window_off_by_one", "mix_logit_rel_sq_err"),
    ("whole_cache_for_a_ring", "mix_logit_rel_sq_err"),
    ("full_width_rotary", "mix_logit_rel_sq_err"),
    ("no_gate", "mix_logit_rel_sq_err"),
    ("no_routed_scaling", "mix_logit_rel_sq_err"),
    ("altered", "mix_transfer_gap_max")])
def test_a_fault_fails_a_limit(toy_run, fault, number):
    broken = toy_run[fault]
    assert broken["correct"] is False and broken["failed"] == 0
    shown = broken["compared"][number]
    assert shown["value"] > shown["limit"], broken["compared"]


def test_float8_weights_in_the_programs_place_are_not_correct(toy_run):
    both = toy_run["both"]
    assert both["sound"]["mix_logit_rel_sq_err"] < 1e-8
    assert both["sound"]["mix_route_disagreement_share"] == 0.0
    assert both["control"]["mix_logit_rel_sq_err"] > 1e-6
    assert both["control"]["mix_route_disagreement_share"] > 0.02


def test_a_program_without_the_decoder_is_refused_at_once(monkeypatch):
    """What the new files do on the parent commit: the family's first call
    ends the run with an error, before anything is built."""
    import semantic_router_tpu.models as models

    family = cells.load_module("families", "swa_gqa_ar_guard")
    monkeypatch.setitem(sys.modules, "semantic_router_tpu.models.laguna",
                        None)
    monkeypatch.delattr(models, "laguna", raising=False)
    with pytest.raises(SystemExit, match="model_type laguna"):
        family.write_checkpoints("/nonexistent", {"tasks": {}}, 1)


def test_the_contract_and_the_cells_files():
    """The family has the contract's names, the cell's configuration holds
    every number of its checkpoint's config, and the held counts are the
    configuration's own."""
    from chipbench import families

    family = cells.load_module("families", "swa_gqa_ar_guard")
    for name in families.CONTRACT:
        assert hasattr(family, name), name
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, "guard_mixed_swa")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s21-guard", "guard_mixed_swa", 1)
    config = cells.load_config(bench, cell["config"])
    assert family.shares(config) == ((0, 128), (0, 50176))
    hf = family.published_model(config)
    assert (hf["num_experts"], hf["vocab_size"]) == (256, 100352)
    assert config["engine"]["seq_len_buckets"] == [512, 8192]
    wl = cells.load_workload(cell["traffic"])
    assert wl["shapes"] == {"buckets": [512, 8192], "rows": [1, 2, 4, 8]}
    lengths = cells.load_module("traffic", "seeded_words")._law_quantiles(
        wl["lengths"], 64)
    assert sum(n <= 400 for n in lengths) == 48  # three in four are short
    assert min(n for n in lengths if n > 400) >= 2049 and max(lengths) <= 8000
    from chipbench import correctness

    assert set(family.expected_numbers(config)) <= set(
        correctness.load_limits(config))


def test_the_attention_count_is_the_least_work():
    oc = cells.load_module("opcount", "gqa_attention")
    model = {"layer_types": ["full_attention", "sliding_attention"],
             "num_attention_heads_per_layer": [48, 72],
             "num_key_value_heads": 8, "head_dim": 128,
             "sliding_window": 512}
    assert oc.causal_pairs(5) == 15 and oc.causal_pairs(5, 8) == 15
    assert oc.causal_pairs(10, 4) == sum(min(t + 1, 4) for t in range(10))
    n = 8000
    full = oc.row_cost(n, "full_attention", model)
    band = oc.row_cost(n, "sliding_attention", model)
    assert full["flops"] == 4.0 * (n * (n + 1) // 2) * 128 * 48
    assert band["flops"] == 4.0 * sum(
        min(t + 1, 512) for t in range(n)) * 128 * 72
    # less than a dense causal kernel's blocks, and than the symmetric band
    assert band["flops"] < full["flops"] * 72 / 48 / 7
    assert band["bytes"] == 2.0 * n * (72 + 8) * 128 * 2


# -- the readers, on a toy generation recorded here -----------------------------------


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Toy generations of BOTH buckets through a real ``InferenceEngine``
    under a profiler session on the CPU (no device plane: the readers of
    the markers' and events' facts need none)."""
    import glob
    from concurrent.futures import ThreadPoolExecutor

    import jax

    sys.path.insert(0, os.path.join(cells.ROOT, "tests"))
    import test_laguna as toy
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine
    from semantic_router_tpu.models.generate import GreedyGenerator

    _, _, cfg, params = toy.variant()
    gen = GreedyGenerator(cfg, params, toy.WordTokenizer(),
                          model=toy.M.CachedModel(cfg), gen_length=6,
                          top_logits=4)
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=30.0, seq_len_buckets=[64, 128]))
    eng.register_generative("guard", gen)
    prompts = [toy.words(r) for r in toy.prompts(8, (30, 100, 21, 5, 90))]

    def serve():
        with ThreadPoolExecutor(len(prompts)) as pool:
            return list(pool.map(
                lambda p: eng.generate("guard", [p], 6)[0], prompts))

    log_dir = str(tmp_path_factory.mktemp("trace"))
    try:
        eng.warmup(tasks=["guard"], batch_sizes=[1, 2, 4])
        serve()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            out = serve()
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    config = {"engine": {"seq_len_buckets": [64, 128]}, "model": toy.MODEL}
    return {"trace": {"path": path, "completed": [1] * 5, "peaks": None},
            "config": config}, out


def test_the_readers_split_the_window_by_bucket(recorded):
    run, out = recorded
    assert len(out) == 5
    read = lambda name: cells.load_module(  # noqa: E731
        "layer_metrics", name).read(run)
    spans = cells.load_module("layer_metrics", "_mix_spans")
    short = spans.steps(run, "gen.prefill", "short")
    long = spans.steps(run, "gen.prefill", "long")
    assert sum(int(s.facts["rows"]) for s in short) == 3
    assert sum(int(s.facts["rows"]) for s in long) == 2
    assert read("mix_rows_mean.short") == pytest.approx(3 / len(short))
    assert read("mix_rows_mean.long") == pytest.approx(2 / len(long))
    for name in ("mix_step_ms.prefill_short", "mix_step_ms.prefill_long",
                 "mix_step_ms.decode_short", "mix_step_ms.decode_long"):
        assert read(name) > 0, name
    # every item's wait carries its bucket: at least the batcher's 30 ms
    waits, _ = spans._waits(run)
    assert sorted(w[2] for w in waits) == [64, 64, 64, 128, 128]
    assert read("mix_queue_wait_ms.short") >= 25.0
    assert read("mix_queue_wait_ms.long") >= 25.0
    # two full layers over 128 + 6 + 1 -> 192 columns, three rings of 8
    assert read("mix_window_cache_share") == pytest.approx(
        100.0 * 3 * 8 / (3 * 8 + 2 * 192))
    # what needs the device's ops reads nothing on a CPU's trace
    for name in ("mix_attn_full_device_ms_per_route",
                 "mix_attn_window_device_ms_per_route",
                 "mix_moe_device_ms_per_route", "mix_flash_roofline.full",
                 "mix_flash_roofline.window", "mix_moe_gmm_roofline.prefill",
                 "mix_moe_gmm_roofline.decode"):
        assert read(name) is None, name


def test_the_readers_find_nothing_on_a_program_without_the_facts():
    """The parent's traces carry no ``bucket`` on waits or markers: the
    recorded greedy trace of before this cell reads None, and raises
    nothing."""
    path = os.path.join(HERE, "data", "generation_greedy.xplane.pb")
    run = {"trace": {"path": path, "completed": [1], "peaks": None},
           "config": {"engine": {"seq_len_buckets": [512, 8192]},
                      "model": {}}}
    for name in ("mix_queue_wait_ms.short", "mix_queue_wait_ms.long",
                 "mix_window_cache_share", "mix_flash_roofline.full",
                 "mix_rows_mean.long", "mix_step_ms.decode_long"):
        assert cells.load_module("layer_metrics", name).read(run) is None, \
            name
