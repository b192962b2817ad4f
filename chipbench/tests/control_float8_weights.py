"""The control of `correct` for the block-diffusion guard: a served
trajectory's inputs with the reference in float8 (e4m3) WEIGHTS in the
program's place — the nearest format below the bfloat16 the configuration
states.  It has to come out NOT correct, by at least one of the
configuration's own limits.

On the chip, at the cell's own size (the readings
``configs/sdar30b-a3b-guard/limits.json`` is set from), the trajectories are
the program's own: this script builds the system as ``run.py`` does, sends a
few requests, and compares twice — the program against the reference, and
the float8 reference against the reference:

    python3 -m chipbench.tests.control_float8_weights \\
        --workload guard_chat_blockdiff --seeds 101,102 --requests 3

Exit code 1 if, on any seed, the program is not within the limits or the
control is.  ``test_blockdiff_guard.py`` runs the same function on the CPU
on the toy.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from chipbench import cells, correctness

PRECISION = "float8_e4m3_weights"


def sound_and_control(bench: Dict[str, Any], cell_name: str, seed: int,
                      n_requests: int) -> Dict[str, Dict[str, float]]:
    """One seed: weights and requests from the seed, the system built and
    warmed at ONE row, ``n_requests`` routes one at a time; returns the
    family's numbers for the program (``sound``) and for the float8
    reference in its place (``control``)."""
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
        sound: Dict[str, Any] = {}
        control: Dict[str, Any] = {}
        for req in requests:
            got = answers[req.text]
            raw = ref.outputs(req, wl["shapes"], got)
            correctness.merge(sound, family.compare(config, req, got, raw))
            low = ref.answers(req, wl["shapes"], got, PRECISION)
            correctness.merge(control,
                              family.compare(config, req, low, raw))
        return {"sound": family.finish(sound),
                "control": family.finish(control)}
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) > 1:
        # a process per seed: a system's 8.7 GB leave the chip with it (this
        # one has not touched JAX, so each child gets the chip)
        return max(subprocess.run(
            [sys.executable, "-m", "chipbench.tests.control_float8_weights",
             "--workload", args.workload, "--seeds", str(seed),
             "--requests", str(args.requests)]).returncode
            for seed in seeds)
    bench = cells.load_benchmark()
    config = cells.load_config(
        bench, cells.find_cell(bench, args.workload)["config"])
    limits = correctness.load_limits(config)
    expected = cells.load_family(config).expected_numbers(config)
    failed = 0
    for seed in seeds:
        t = time.perf_counter()
        both = sound_and_control(bench, args.workload, seed, args.requests)
        for side, numbers in both.items():
            ok, _ = correctness.judge(expected, numbers, limits)
            # the program has to be within the limits, the control outside
            failed |= ok != (side == "sound")
            print(f"{side} {args.workload} seed {seed}: "
                  f"{json.dumps(numbers)} -> "
                  f"{'within the limits' if ok else 'not correct'}",
                  flush=True)
        print(f"seed {seed}: {time.perf_counter() - t:.1f} s", flush=True)
    return int(failed)


if __name__ == "__main__":
    sys.exit(main())
