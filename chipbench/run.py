"""One command, one cell, one run:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that makes its weights and traffic from ``--seed``, builds the
system the way its entry points do, warms the cell's shape set (set-up),
measures for ``--seconds``, checks what the window produced against the
plain reference, and prints ONE JSON object as the last line of stdout.
Without the cell's chips, or without the program, it exits non-zero and
prints no result.  ``BENCH_RUN`` is not read.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

if __package__ in (None, ""):  # python3 chipbench/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    __package__ = "chipbench"

from . import cells, correctness, loadgen  # noqa: E402
from . import system as system_mod  # noqa: E402
from .compile_watch import CompileWatch  # noqa: E402

EXIT_NO_CHIP = 2
EXIT_NO_PROGRAM = 3
GRACE_S = 30.0  # open loop: how long after the window a reply may come


class NoChip(Exception):
    pass


def device_block(chips: int, require_chip: bool) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    platform = devices[0].platform if devices else "none"
    if require_chip:
        if platform != "tpu":
            raise NoChip(f"JAX found platform {platform!r}, not a TPU")
        if len(devices) != chips:
            raise NoChip(f"the cell needs {chips} chip(s), JAX reports "
                         f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        peak = max(peak, int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)))
    return peak


def configure_cache() -> str:
    """The program's own cache placement (``JAX_COMPILATION_CACHE_DIR``,
    else the fixed ``<checkout>/.jax_cache``), with the thresholds lowered
    so that EVERY program is written: a second run compiles nothing."""
    from semantic_router_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # ... and kept: a size cap from the environment evicts the first
    # programs of a cell while its last ones are written, and the next run
    # then misses every one of them in turn (PERF.md, PR 23)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


def _cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


class Tracer:
    """Profiles ``[start_after, start_after + seconds]`` of the window from
    a helper thread."""

    def __init__(self, log_dir: str, t0: float, start_after: float,
                 seconds: float) -> None:
        self.log_dir, self.t0 = log_dir, t0
        self.start_after, self.seconds = start_after, seconds
        self.window: Optional[tuple] = None
        self.error: Optional[str] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chipbench-tracer")

    def start(self) -> "Tracer":
        self._thread.start()
        return self

    def _run(self) -> None:
        import jax.profiler

        try:
            time.sleep(max(0.0, self.t0 + self.start_after
                           - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            a = time.perf_counter() - self.t0
            time.sleep(self.seconds)
            b = time.perf_counter() - self.t0
            jax.profiler.stop_trace()
            self.window = (a, b)
        except Exception as exc:  # reported; the run goes on without a trace
            self.error = f"{type(exc).__name__}: {exc}"

    def join(self) -> None:
        self._thread.join()


def run_cell(bench: Dict[str, Any], cell_name: str, seed: int,
             seconds: float, trace: bool, require_chip: bool = True,
             workload: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The whole run; returns the result object.  ``require_chip=False``
    and ``workload`` exist for the harness's own tests on the CPU, whose
    result never carries a device metric's name."""
    cell = cells.find_cell(bench, cell_name)
    config = cells.load_config(bench, cell["config"])
    wl = workload or cells.load_workload(cell["traffic"])
    cache_dir = configure_cache()
    device = device_block(int(cell["chips"]), require_chip)
    # an unknown device kind is an error, not a default
    peaks = cells.load_peaks(device["kind"]) if require_chip else None
    watch = CompileWatch().install(time.perf_counter)
    print(f"device: {device}; compile cache {cache_dir} "
          f"({_cache_entries(cache_dir)} entries at start)", flush=True)

    work = os.path.join(cells.WORK_DIR, cell_name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        return _run_in(work, bench, cell, config, wl, seed, seconds, trace,
                       device, peaks, watch, cache_dir)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run_in(work, bench, cell, config, wl, seed, seconds, trace, device,
            peaks, watch, cache_dir) -> Dict[str, Any]:
    generator = cells.load_module("traffic", wl["generator"])
    family = cells.load_family(config)
    t = time.perf_counter()
    ckpt_dirs = family.write_checkpoints(
        os.path.join(work, "ckpt"), config, seed)
    print(f"setup checkpoints from seed {seed}: "
          f"{time.perf_counter() - t:.2f} s", flush=True)
    config_path = system_mod.write_router_config(config, ckpt_dirs, work)
    sut = system_mod.build(config, config_path, wl["shapes"])
    try:
        traffic = generator.generate(wl, seed, seconds,
                                     config["model"]["vocab_size"])
        # the route path itself, at the window's concurrency: lazily built
        # candidate embeddings, the tokenizer, every host-side cache
        t = time.perf_counter()
        warm = loadgen.run_closed(sut.route, traffic.warmup, traffic.clients,
                                  seconds=3600.0)
        bad = [r for r in warm if not r.ok]
        print(f"setup warm-up routes: {len(warm)} in "
              f"{time.perf_counter() - t:.2f} s, {len(bad)} not ok"
              + (f" (first: {bad[0].detail})" if bad else ""), flush=True)
        n_comp, comp_s = watch.total()
        print(f"setup compiles: {n_comp} programs compiled or loaded in "
              f"{comp_s:.1f} s; persistent cache {watch.cache_hits} hits, "
              f"{watch.cache_misses} misses, {_cache_entries(cache_dir)} "
              f"files now", flush=True)
        sut.spans.rows.clear()
        sut.spans.answers.clear()
        sut.spans.annotate = trace
        gc.collect()
        gc.freeze()  # set-up's objects leave the collector's scans

        steps_before = sut.step_counters()
        wait_before = sut.queue_wait_totals()
        t0 = time.perf_counter()
        setup_s = t0 - PROCESS_START
        tracer = None
        if trace:
            tracer = Tracer(os.path.join(work, "trace"), t0,
                            float(wl.get("trace_after_s", 2.0)),
                            min(float(wl.get("trace_seconds", 10.0)),
                                max(seconds - 3.0, 1.0))).start()
        unfinished = 0
        if traffic.loop == "closed":
            records = loadgen.run_closed(sut.route, traffic.requests,
                                         traffic.clients, seconds, t0=t0)
        else:
            records, unfinished = loadgen.run_open(
                sut.route, traffic.requests, traffic.clients, seconds,
                GRACE_S, t0=t0)
        window_end = time.perf_counter()
        if tracer is not None:
            tracer.join()
        steps_after = sut.step_counters()
        wait_after = sut.queue_wait_totals()
        peak = memory_peak_bytes()
        in_window_compiles = watch.since(t0)
        after_build = watch.since(sut.engine_built)
        spans = list(sut.spans.rows)
        answers = dict(sut.spans.answers)
    finally:
        sut.close()
    del sut
    gc.unfreeze()
    gc.collect()

    # -- the window's requests ----------------------------------------------
    failed = [r for r in records if not r.ok]
    n_attempted = len(records) + unfinished
    if traffic.loop == "closed":
        # a call in flight when the window closed was waited for: it counts
        # for the rate by the share of its time inside the window, and for
        # no latency
        completed = [r for r in records if r.ok and r.end <= seconds]
    else:
        completed = [r for r in records if r.ok]
    n_failed = len(failed) + unfinished
    for r in failed[:5]:
        print(f"failed request {r.index} ({r.n_tokens} tokens): {r.detail}",
              flush=True)
    print(f"window: {seconds:.1f} s asked, {window_end - t0:.2f} s until "
          f"the last reply; attempted {n_attempted}, completed "
          f"{len(completed)}, failed {n_failed}", flush=True)
    print(f"compiles inside the window: {len(in_window_compiles)} "
          f"({sum(c.seconds for c in in_window_compiles):.1f} s)",
          flush=True)
    # which program, and when: a run refused for a compile inside the
    # window then names the program that set-up did not warm
    for c in after_build:
        print(f"program after build_engine: {c.name} at "
              f"{c.when - PROCESS_START:.1f} s of the process "
              f"({c.when - t0:+.1f} s of the window), {c.seconds:.1f} s",
              flush=True)

    # -- correct ------------------------------------------------------------
    by_index = {r.index: r for r in traffic.requests}
    sample = correctness.sample_requests(
        [by_index[r.index] for r in completed], seed,
        int(wl.get("correctness_sample", 3)))
    t = time.perf_counter()
    parts: Dict[str, Any] = {}
    ref = family.Reference.from_checkpoints(config, ckpt_dirs)
    served_tokens = 0
    for req in sample:
        got = answers.get(req.text, {})
        raw = ref.outputs(req, wl["shapes"], got)
        correctness.merge(parts, family.compare(config, req, got, raw))
        served_tokens += req.n_tokens
    numbers = family.finish(parts)
    expected = family.expected_numbers(config)
    limits = correctness.load_limits(config)
    ok, lines = correctness.judge(expected, numbers, limits)
    print(f"reference: {len(sample)} requests of "
          f"{[r.n_tokens for r in sample]} tokens ({served_tokens} in all) "
          f"in {time.perf_counter() - t:.2f} s; numbers {numbers}",
          flush=True)
    for line in lines:
        print(line, flush=True)
    correct = bool(ok and sample and not in_window_compiles
                   and not failed)
    if failed:
        print(f"NOT CORRECT: {len(failed)} route(s) answered fail-open or "
              f"not routed", flush=True)
    if in_window_compiles:
        print("NOT CORRECT: a program compiled inside the window",
              flush=True)
    # every number compared beside its limit, last in the result's line
    compared = {name: {"value": numbers.get(name),
                       "limit": float(limits[name]["limit"])}
                for name in expected}
    compared["compiles_in_window"] = {
        "value": len(in_window_compiles), "limit": 0,
        "programs": [f"{c.name} at {c.when - t0:+.1f} s"
                     for c in in_window_compiles]}
    compared["failed_routes"] = {"value": len(failed), "limit": 0}

    # -- metrics ------------------------------------------------------------
    run: Dict[str, Any] = {
        "config": config, "seconds": seconds, "setup_s": setup_s,
        "records": records, "completed": completed, "spans": spans,
        "steps": (steps_before, steps_after),
        "queue_wait": (wait_before, wait_after),
        "memory_peak_bytes": peak, "requests": by_index, "trace": None}
    result_device = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        from . import reduce_trace

        if tracer.error or tracer.window is None:
            raise RuntimeError(f"the profiler gave no trace: {tracer.error}")
        reduced = reduce_trace.reduce(reduce_trace.find_xplane(
            os.path.join(work, "trace")))
        a, b = tracer.window
        run["trace"] = dict(
            reduced, window=(a, b),
            completed=[r for r in completed if a <= r.end <= b],
            peaks=peaks)
        result_device.update(busy_s=reduced["busy_s"], window_s=b - a)
        breakdown = {"device_ops": reduce_trace.top_ops(reduced),
                     "idle_gaps": reduced["idle_gaps"]}
        print(f"trace: {reduced['work_events']} device events on "
              f"{reduced['devices']}, busy {reduced['busy_s']:.3f} s of "
              f"{b - a:.3f} s, {len(run['trace']['completed'])} routes "
              f"completed inside, {reduced['host_spans']} host spans",
              flush=True)
    metrics = end_to_end(bench, cell, run) if not trace else \
        per_layer(bench, cell, run)
    result: Dict[str, Any] = {
        "correct": correct, "attempted": n_attempted, "failed": n_failed,
        "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if device["platform"] != "tpu":
        # a CPU rehearsal: its numbers are not the device's, and never go
        # under a metric's name
        result["cpu_rehearsal_values"] = result.pop("metrics")
        result["metrics"] = {}
    result["compared"] = compared
    return result


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench, cell, run) -> Dict[str, Any]:
    values = {"setup_s": run["setup_s"],
              "routes_per_s": routes_in_window(
                  run["records"], run["seconds"]) / run["seconds"]}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if _applies(m, cell["name"]) and values.get(m["name"])}


def routes_in_window(records, seconds: float) -> float:
    """Routes done in [0, seconds]: 1 for a route answered inside, and for
    one sent inside but answered after the window closed the share of its
    time that lay inside.  With a handful of long routes in flight, whole
    completions alone would jump by a batch at a time (8 callers ride one
    8-row step) — all the work and all the time of the window, counted
    without the jump."""
    done = 0.0
    for r in records:
        if r.ok and r.end > r.start:
            done += max(0.0, min(r.end, seconds) - r.start) / (r.end - r.start)
    return done


def per_layer(bench, cell, run) -> Dict[str, Any]:
    out = {}
    for m in bench["per_layer"]:
        if not _applies(m, cell["name"]):
            continue
        value = cells.load_module("layer_metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import semantic_router_tpu  # noqa: F401
    except ImportError as exc:
        print(f"chipbench: the program is not in this directory: {exc}",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    bench = cells.load_benchmark()
    try:
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as exc:
        print(f"chipbench: {exc}: the benchmark measures the chip and has "
              f"no other mode", file=sys.stderr)
        return EXIT_NO_CHIP
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compare {name}: {c['value']!r} limit {c['limit']!r}"
              + (f" {c['programs']}" if c.get("programs") else ""),
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
