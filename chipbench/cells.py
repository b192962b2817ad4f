"""Finding a cell's files by the names in BENCHMARK.json."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# everything a run writes besides the compile cache; git-ignored, fixed
WORK_DIR = os.path.join(ROOT, ".chipbench_work")


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                     f"({[c['name'] for c in bench['workloads']]})")


def load_config(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    """The configuration's JSON file plus ``model`` (its published
    numbers, the keys its family names) and ``dir`` (where its
    router_config.yaml lies)."""
    (entry,) = [c for c in bench["configs"] if c["name"] == name]
    path = os.path.join(ROOT, entry["file"])
    cfg = _load_json(path)
    if not cfg.get("family"):
        raise SystemExit(f"chipbench: {entry['file']} names no \"family\" "
                         f"(a file of chipbench/families/): no default")
    cfg["model"] = {k: cfg[k] for k in load_family(cfg).MODEL_KEYS}
    cfg["dir"] = os.path.dirname(path)
    cfg["name"] = name
    return cfg


def load_family(config: Dict[str, Any]):
    """The one module that knows the configuration's model family:
    ``families/<family>.py`` (``families/__init__.py`` has the contract)."""
    return load_module("families", config["family"])


def load_workload(traffic: str) -> Dict[str, Any]:
    """The traffic mix's data file: ``workloads/<traffic>.json``."""
    return _load_json(os.path.join(HERE, "workloads", f"{traffic}.json"))


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` by name (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"chipbench: no {kind} file for {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_')}", path)
    if spec.name in sys.modules:
        return sys.modules[spec.name]
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def load_peaks(device_kind: str) -> Dict[str, Any]:
    table = _load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"chipbench: device kind {device_kind!r} is not in "
                         f"peaks.json ({sorted(table)}): no default")
    return table[device_kind]
