"""The family seam, proven on what is coming: a SECOND model family whose
every file lies under ``data/second_family/`` is dropped into a copy of
``chipbench/`` as new files, with one configuration, one workload and one
per-layer entry appended to the copy's ``BENCHMARK.json`` — what a
``model_config`` PR may do, and nothing else — and a cell of it runs.

The family drives the program's ``generative`` task kind: a toy dense Qwen3
through ``bootstrap``'s ``kind: generative``, the jailbreak family answered
by ``engine.guard_classify``, wrapped call ``generate``.  The toy is never a
cell, and what it prints carries no device metric's name (CPU).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
SECOND = os.path.join(HERE, "data", "second_family")

# run in the copy: a sound run, then the same run with the program's answer
# altered where it is produced (the generator's second decoded token)
DRIVER = r"""
import json, sys
import chipbench
from chipbench import cells, run
assert chipbench.__file__.startswith(sys.argv[1]), chipbench.__file__
bench = cells.load_benchmark()
sound = run.run_cell(bench, "toy_guard_chat", 2**31 + 27, 2.0, False,
                     require_chip=False)
from semantic_router_tpu.models import generate
inner = generate.GreedyGenerator.generate
def altered(self, *args, **kwargs):
    out = inner(self, *args, **kwargs)
    for r in out:
        if len(r.token_ids) > 1:
            r.token_ids[1] = 2 + (r.token_ids[1] - 1) % 500
    return out
generate.GreedyGenerator.generate = altered
broken = run.run_cell(bench, "toy_guard_chat", 2**31 + 27, 2.0, False,
                      require_chip=False)
traced = run.per_layer(bench, cells.find_cell(bench, "toy_guard_chat"),
                       {"completed": [1, 2], "spans": [
                           ("engine.generate", "", 0.0, 1.0)] * 3})
print("RESULTS " + json.dumps([sound, broken, traced]))
"""


def _hashes(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def second_family_run(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("checkout"))
    copy = os.path.join(tmp, "chipbench")
    shutil.copytree(cells.HERE, copy, ignore=shutil.ignore_patterns(
        "__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp)
    before = _hashes(copy)
    # new files only
    added = []
    for rel in _hashes(SECOND):
        if rel == "entries.json":
            continue
        dst = os.path.join(copy, rel)
        assert not os.path.exists(dst), f"{rel} is already in chipbench/"
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copy(os.path.join(SECOND, rel), dst)
        added.append(rel)
    # appended entries only
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(SECOND, "entries.json")) as f:
        entries = json.load(f)
    was = json.loads(json.dumps(bench))
    for key, new in entries.items():
        bench[key] = bench[key] + new
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", DRIVER, tmp], cwd=tmp, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    (line,) = [l for l in p.stdout.splitlines() if l.startswith("RESULTS ")]
    sound, broken, traced = json.loads(line[len("RESULTS "):])
    return {"before": before, "after": _hashes(copy), "added": added,
            "bench_was": was, "bench": bench, "stdout": p.stdout,
            "sound": sound, "broken": broken, "traced": traced}


def test_the_second_family_is_new_files_and_appended_entries_only(
        second_family_run):
    r = second_family_run
    # every file that was in chipbench/ before is there, unchanged
    assert {k: r["after"][k] for k in r["before"]} == r["before"]
    assert sorted(set(r["after"]) - set(r["before"])) == sorted(r["added"])
    assert {"families/toy_guard.py", "reference/toy_qwen3.py",
            "configs/toy-guard/model.json", "configs/toy-guard/limits.json",
            "configs/toy-guard/router_config.yaml",
            "workloads/toy_guard_chat.json",
            "layer_metrics/generate_calls_per_route.py"} == set(r["added"])
    for key, value in r["bench_was"].items():
        if isinstance(value, list) and key not in ("command", "paths"):
            assert r["bench"][key][:len(value)] == value
        else:
            assert r["bench"][key] == value


def test_the_second_familys_cell_runs_and_is_correct(second_family_run):
    r = second_family_run
    sound = r["sound"]
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["attempted"] > 4
    assert sound["metrics"] == {} and sound["device"]["platform"] == "cpu"
    shown = sound["compared"]["guard_greedy_token_mismatch_share"]
    assert shown == {"value": 0.0, "limit": 0.0}
    assert sound["compared"]["compiles_in_window"]["value"] == 0
    out = r["stdout"]
    assert "warmup jailbreak prompt_tokens=64" in out
    assert "compare guard_greedy_token_mismatch_share: 0.0 limit 0.0 ok" in out
    # some hundreds of greedy steps were compared, not a handful
    steps = [float(l.split("'guard_steps_compared': ")[1].split("}")[0]
                   .split(",")[0])
             for l in out.splitlines() if "'guard_steps_compared'" in l]
    assert steps and steps[0] >= 60
    assert r["traced"] == {"generate_calls_per_route": {
        "value": 1.5, "unit": "calls/route"}}


def test_the_second_familys_altered_answer_is_not_correct(second_family_run):
    broken = second_family_run["broken"]
    assert broken["correct"] is False and broken["failed"] == 0
    shown = broken["compared"]["guard_greedy_token_mismatch_share"]
    assert shown["value"] > shown["limit"] == 0.0
