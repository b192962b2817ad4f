"""The two controls of the linear-attention guard over ONE set of served
trajectories: the reference with float8 (e4m3) WEIGHTS in the program's
place (what ``control_float8_weights`` reads, and what has to come out NOT
correct), and the reference with every linear layer's matrix state rounded
to BFLOAT16 at every chunk boundary in the program's place — the
configuration states a float32 state, and this says what the lower one
would read and whether a limit tells it apart.

    python3 -m chipbench.tests.control_state_precision \\
        --workload guard_longdoc_linear --seed 101 --requests 2

One process, one seed (the system's weights leave the chip with it); it
prints the family's numbers for the program and for each control and judges
none: the readings go into the configuration's ``limits.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

from chipbench import cells, correctness

PRECISIONS = ("float8_e4m3_weights", "bfloat16_state")


def sound_and_controls(bench: Dict[str, Any], cell_name: str, seed: int,
                       n_requests: int) -> Dict[str, Dict[str, float]]:
    """As ``control_float8_weights.sound_and_control``: the system built
    and warmed at ONE row, ``n_requests`` routes one at a time; returns the
    family's numbers under ``sound`` and under each of ``PRECISIONS``."""
    from chipbench import system as system_mod

    cell = cells.find_cell(bench, cell_name)
    config = cells.load_config(bench, cell["config"])
    wl = cells.load_workload(cell["traffic"])
    family = cells.load_family(config)
    work = os.path.join(cells.WORK_DIR, f"control-{cell_name}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        dirs = family.write_checkpoints(os.path.join(work, "ckpt"), config,
                                        seed)
        path = system_mod.write_router_config(config, dirs, work)
        sut = system_mod.build(config, path, dict(wl["shapes"], rows=[1]))
        try:
            traffic = cells.load_module("traffic", wl["generator"]).generate(
                wl, seed, 1.0, config["model"]["vocab_size"])
            requests = traffic.requests[:n_requests]
            for req in requests:
                ok, detail = sut.route(req)
                assert ok, detail
            answers = dict(sut.spans.answers)
        finally:
            sut.close()
        del sut
        ref = family.Reference.from_checkpoints(config, dirs)
        parts: Dict[str, Dict[str, Any]] = {
            k: {} for k in ("sound",) + PRECISIONS}
        for req in requests:
            got = answers[req.text]
            raw = ref.outputs(req, wl["shapes"], got)
            correctness.merge(parts["sound"],
                              family.compare(config, req, got, raw))
            for precision in PRECISIONS:
                low = ref.answers(req, wl["shapes"], got, precision)
                correctness.merge(parts[precision],
                                  family.compare(config, req, low, raw))
        return {k: family.finish(v) for k, v in parts.items()}
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=2)
    args = ap.parse_args(argv)
    out = sound_and_controls(cells.load_benchmark(), args.workload, args.seed,
                             args.requests)
    for side, numbers in out.items():
        print(f"{side} {args.workload} seed {args.seed}: "
              f"{json.dumps(numbers)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
