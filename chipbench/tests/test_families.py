"""The family seam's own rules, fast and on the CPU (no engine is built):
every configuration names a family that keeps the contract, every number a
family can ask for has a limit, a configuration's own limits loosen none
that is there, and the generic files name no model family.

ISSUE 27 asked for this file as ``tests/test_chipbench_families.py``; a
benchmark PR may add files under the benchmark's own directories only, so it
lies here (PERF.md section 7)."""

from __future__ import annotations

import json
import os
import re

import pytest

from chipbench import cells, correctness, families

BENCH = cells.load_benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
GENERIC = ("run.py", "cells.py", "system.py", "correctness.py")
# what only a family may say: an architecture, a published key of one, a
# task kind's number
FORBIDDEN = ("modernbert", "global_attn_every_n_layers", "local_attention",
             "classifier_pooling", "rope_scaling", "intermediate_size", "pii",
             "embedding_one_minus_cos", "seq_logit", "checkpoints.")


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_names_a_family_that_keeps_the_contract(name):
    config = cells.load_config(BENCH, name)
    path = os.path.join(cells.HERE, "families", config["family"] + ".py")
    assert os.path.exists(path)
    family = cells.load_family(config)
    missing = [n for n in families.CONTRACT if not hasattr(family, n)]
    assert not missing, f"families/{config['family']}.py lacks {missing}"
    assert set(config["model"]) == set(family.MODEL_KEYS)
    for call, pair in family.ENGINE_CALLS.items():
        arguments, answers = pair
        assert callable(arguments) and callable(answers), call
    for method in ("from_checkpoints", "outputs", "answers"):
        assert callable(getattr(family.Reference, method))


@pytest.mark.parametrize("name", CONFIGS)
def test_every_number_a_family_can_ask_for_has_a_limit(name):
    config = cells.load_config(BENCH, name)
    family = cells.load_family(config)
    limits = correctness.load_limits(config)
    expected = family.expected_numbers(config)
    assert expected
    # ... whatever tasks a configuration of this family serves: ask with
    # each task alone, too
    for task, spec in config["tasks"].items():
        expected = expected + family.expected_numbers(
            dict(config, tasks={task: spec}))
    for number in expected:
        assert float(limits[number]["limit"]) >= 0.0, number
    ok, lines = correctness.judge(expected, {}, limits)
    assert not ok and all("not read" in l for l in lines)


def test_a_configurations_own_limits_may_not_restate_a_shared_one(tmp_path):
    config = {"dir": str(tmp_path)}
    shared = correctness.load_limits(config)  # none of its own: the shared
    taken = sorted(shared)[0]
    with open(tmp_path / "limits.json", "w") as f:
        json.dump({"limits": {"a_new_number": {"limit": 0.5}}}, f)
    merged = correctness.load_limits(config)
    assert merged["a_new_number"]["limit"] == 0.5
    assert {k: merged[k] for k in shared} == shared
    with open(tmp_path / "limits.json", "w") as f:
        json.dump({"limits": {taken: {"limit": 1e9}}}, f)
    with pytest.raises(SystemExit, match=taken):
        correctness.load_limits(config)


@pytest.mark.parametrize("name", CONFIGS)
def test_shared_and_local_limits_do_not_collide(name):
    correctness.load_limits(cells.load_config(BENCH, name))


def test_a_configuration_without_a_family_is_an_error(tmp_path):
    path = tmp_path / "model.json"
    with open(path, "w") as f:
        json.dump({"vocab_size": 8}, f)
    bench = {"configs": [{"name": "x", "file": os.path.relpath(
        str(path), cells.ROOT)}]}
    with pytest.raises(SystemExit, match="family"):
        cells.load_config(bench, "x")
    with open(path, "w") as f:
        json.dump({"family": "no_such_family"}, f)
    with pytest.raises(SystemExit, match="no_such_family"):
        cells.load_config(bench, "x")


@pytest.mark.parametrize("word", FORBIDDEN)
@pytest.mark.parametrize("file", GENERIC)
def test_generic_files_name_no_model_family(file, word):
    with open(os.path.join(cells.HERE, file)) as f:
        text = f.read()
    hits = [l for l in text.splitlines()
            if re.search(re.escape(word), l, re.IGNORECASE)]
    assert not hits, f"chipbench/{file} names {word!r}: {hits[:3]}"


def test_the_old_modules_are_gone_and_nothing_imports_them():
    assert not os.path.exists(os.path.join(cells.HERE, "checkpoints.py"))
    offenders = []
    for d, dirs, files in os.walk(cells.HERE):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", "data")]
        for f in files:
            if f.endswith(".py") and f != "test_families.py":
                with open(os.path.join(d, f)) as fh:
                    if re.search(r"chipbench\.checkpoints|import checkpoints"
                                 r"|correctness\.(Reference|compare|finish)",
                                 fh.read()):
                        offenders.append(f)
    assert not offenders


def test_the_warm_up_runs_on_a_thread_of_its_own_and_its_error_is_raised():
    import threading
    import traceback

    from chipbench import system

    seen = {}

    def fn(a, b):
        seen["thread"] = threading.current_thread().name
        seen["depth"] = len(traceback.extract_stack())
        seen["args"] = (a, b)

    here = len(traceback.extract_stack())
    system._on_a_thread_of_its_own(fn, 1, 2)
    assert seen["thread"].startswith("chipbench-warm")
    assert seen["args"] == (1, 2)
    # the thread's stack does not stand on the caller's
    assert seen["depth"] < here

    def boom():
        raise RuntimeError("a warm-up program failed")

    with pytest.raises(RuntimeError, match="warm-up program"):
        system._on_a_thread_of_its_own(boom)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("chipbench-warm")]
