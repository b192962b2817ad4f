"""Program-level performance observatory (ISSUE 18, `make profile-smoke`).

Covers docs/OBSERVABILITY.md "Program catalog & roofline" end to end:

- the peak-table tier selection (datasheet TPU tiers; CPU forces the
  flagged placeholder) and the roofline join math;
- the catalog unit contract: deferred lower-thunk capture, cost +
  memory analysis rows, newest-shape-wins, bounded size, fail-open
  error rows, retirement dropping both rows and gauge label sets;
- the ACCEPTANCE rig: every live program variant the engine serves on
  the forced 8-device CPU mesh — fused, packed, quantized,
  epilogue/bgmv-kerneled, mesh-sharded — yields a cost-model row joined
  with measured warm EWMAs in `/debug/programs`' report;
- satellite 2: quant/kernel/mesh/packing hot flips retire dead program
  keys from runtimestats AND programstats — 10 consecutive flips leave
  both registries (and the gauge cardinality) bounded;
- satellite 3: the `llm_device_memory_bytes` spelling table, one test
  per backend spelling plus the absent-on-CPU case;
- satellite 4: the `/debug/runtime` report schema across the knob
  matrix (packing x quant x kernels x mesh x cascade);
- the perf-regression gate: clean on the pinned baseline, flags the
  planted 2x fixture;
- SLO-burn-triggered capture: one bounded trace + catalog snapshot per
  firing alert, cooldown-gated, cross-linked from the flight recorder.
"""

from __future__ import annotations

import importlib.util
import json
import os
from itertools import product

import jax
import jax.numpy as jnp
import pytest

from semantic_router_tpu.engine.testing import make_shared_trunk_engine
from semantic_router_tpu.observability.flightrec import FlightRecorder
from semantic_router_tpu.observability.metrics import MetricsRegistry
from semantic_router_tpu.observability.programstats import (
    _CPU_TIER,
    ProgramCatalog,
    SLOCaptureController,
    peak_for,
)
from semantic_router_tpu.observability.runtimestats import (
    DEVICE_MEMORY_STATS,
    RuntimeStats,
)
from semantic_router_tpu.runtime.events import (
    SLO_ALERT_FIRING,
    SLO_CAPTURE,
    EventBus,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _matmul_lower(n: int = 16):
    """A real lower thunk over abstract shapes — the same contract the
    engine capture sites build (no device arrays pinned)."""
    f = jax.jit(lambda x: x @ x)
    ab = jax.ShapeDtypeStruct((n, n), jnp.float32)
    return lambda: f.lower(ab)


class FakeRuntimeStats:
    """Just the join surface ProgramCatalog.catalog reads."""

    def __init__(self, rows):
        self._rows = rows

    def programs(self):
        return list(self._rows)


# ---------------------------------------------------------------------------
# peak table


class TestPeakTable:
    def test_tpu_tiers_match_by_substring(self):
        assert peak_for("TPU v5e", "tpu")["tier"] == "tpu-v5e"
        assert peak_for("TPU v5 lite", "tpu")["tier"] == "tpu-v5e"
        assert peak_for("TPU v5p", "tpu")["tier"] == "tpu-v5p"
        assert peak_for("TPU v6e (Trillium)", "tpu")["tier"] == "tpu-v6e"
        assert peak_for("TPU v4", "tpu")["tier"] == "tpu-v4"

    def test_cpu_platform_always_placeholder(self):
        # a host CPU whose kind string happens to contain a TPU needle
        # must still get the placeholder tier — platform wins
        tier = peak_for("Genuine v5e-lookalike CPU", "cpu")
        assert tier["tier"] == "cpu-placeholder"
        assert tier["placeholder"] is True
        assert "placeholder" in tier["peak_note"]

    def test_unknown_accelerator_has_no_peaks(self):
        # an accelerator missing from the table is "unknown", never the
        # CPU guess: zero peaks mean the catalog emits no fractions
        tier = peak_for("H100 SXM", "gpu")
        assert tier["tier"] == "unknown"
        assert "placeholder" not in tier
        assert tier["flops_per_s"] == 0 and tier["hbm_bytes_per_s"] == 0
        assert peak_for("TPU v9 future", "tpu")["tier"] == "unknown"

    def test_datasheet_notes_carry_provenance(self):
        for kind in ("v4", "v5e", "v5p", "v6e"):
            note = peak_for(kind, "tpu")["peak_note"]
            assert "datasheet" in note


# ---------------------------------------------------------------------------
# catalog unit contract


class TestProgramCatalog:
    def test_capture_records_cost_and_memory(self):
        cat = ProgramCatalog(MetricsRegistry())
        cat.note_compile("g", 32, "fused:seq", (4, 32), _matmul_lower(),
                         measured_variant="fused")
        assert cat.capture_pending() == 1
        (row,) = cat.rows()
        assert row.flops > 0
        assert row.bytes_accessed > 0
        assert row.hbm_peak_bytes > 0
        assert row.error == ""
        assert row.shape == (4, 32)

    def test_roofline_join_math(self):
        cat = ProgramCatalog(MetricsRegistry())
        cat.note_compile("g", 32, "fused:seq", (4, 32), _matmul_lower(),
                         measured_variant="fused")
        ewma = 0.001
        fake = FakeRuntimeStats([{
            "group": "g", "bucket": 32, "variant": "fused",
            "executes": 5, "execute_ewma_s": ewma,
            "token_fill_ratio": 0.5,
        }])
        snap = cat.catalog(runtime_stats=fake)
        (row,) = snap["programs"]
        assert row["executes"] == 5
        achieved = row["flops"] / ewma
        assert row["achieved_flops_per_s"] == pytest.approx(achieved)
        assert row["useful_flops_per_s"] == pytest.approx(achieved * 0.5)
        assert row["achieved_bytes_per_s"] == pytest.approx(
            row["bytes_accessed"] / ewma)
        intensity = row["flops"] / row["bytes_accessed"]
        assert row["arithmetic_intensity"] == pytest.approx(intensity)
        peak_f = _CPU_TIER["flops_per_s"]
        peak_b = _CPU_TIER["hbm_bytes_per_s"]
        attainable = min(peak_f, intensity * peak_b)
        assert row["roofline_fraction"] == pytest.approx(
            achieved / attainable)
        assert row["bound"] == (
            "compute" if intensity * peak_b >= peak_f else "memory")
        # on this rig the device block must self-describe as placeholder
        assert snap["device"]["platform"] == "cpu"
        assert snap["device"]["placeholder"] is True

    def test_gauges_published_and_retired(self):
        reg = MetricsRegistry()
        cat = ProgramCatalog(reg)
        cat.note_compile("g", 32, "fused:seq", (4, 32), _matmul_lower(),
                         measured_variant="fused")
        cat.catalog(runtime_stats=FakeRuntimeStats([{
            "group": "g", "bucket": 32, "variant": "fused",
            "executes": 2, "execute_ewma_s": 0.001,
            "token_fill_ratio": 1.0}]))
        assert len(cat.flops_gauge._values) == 1
        assert len(cat.roofline_gauge._values) == 1
        assert cat.retire(group="g") == 1
        assert cat.rows() == []
        # the gauge label sets die with the program — cardinality must
        # track the live catalog, not its history
        assert len(cat.flops_gauge._values) == 0
        assert len(cat.roofline_gauge._values) == 0

    def test_recompile_supersedes_stale_row(self):
        cat = ProgramCatalog(MetricsRegistry())
        cat.note_compile("g", 32, "fused:seq", (4, 32), _matmul_lower(8))
        cat.capture_pending()
        old = cat.rows()[0].flops
        cat.note_compile("g", 32, "fused:seq", (8, 32), _matmul_lower(64))
        cat.capture_pending()
        (row,) = cat.rows()  # still one row for the key — newest wins
        assert row.shape == (8, 32)
        assert row.flops > old

    def test_bounded_catalog_drops_new_notes(self):
        cat = ProgramCatalog(MetricsRegistry(), max_programs=2)
        for i in range(4):
            cat.note_compile("g", i, "v", (1,), _matmul_lower())
        assert cat.capture_pending() == 2

    def test_capture_failure_is_fail_open(self):
        cat = ProgramCatalog(MetricsRegistry())

        def boom():
            raise RuntimeError("donated buffer quirk")

        cat.note_compile("g", 32, "fused:seq", (4, 32), boom)
        assert cat.capture_pending() == 1
        snap = cat.catalog()
        (row,) = snap["programs"]
        assert "donated buffer quirk" in row["error"]
        assert snap["capture_errors"] == 1

    def test_disabled_catalog_notes_nothing(self):
        cat = ProgramCatalog(MetricsRegistry())
        cat.enabled = False
        cat.note_compile("g", 32, "v", (1,), _matmul_lower())
        assert cat.capture_pending() == 0
        assert cat.catalog()["programs"] == []


# ---------------------------------------------------------------------------
# the acceptance rig: every live variant cost-accounted, per phase


def _variant_rows(snap, **want):
    rows = []
    for r in snap["programs"]:
        if all(str(r.get(k, "")).startswith(v) if k == "variant"
               else str(r.get(k, "")) == v for k, v in want.items()):
            rows.append(r)
    return rows


class TestEngineCaptureAcceptance:
    """Walk the knob ladder on one shared-trunk engine; after each flip
    the catalog must hold cost-model rows for the programs NOW serving
    (earlier phases' rows retire with their programs — that is the
    satellite-2 contract, asserted separately below)."""

    def test_every_live_variant_has_cost_and_measured_rows(self):
        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        cat = ProgramCatalog(reg)
        eng = make_shared_trunk_engine(lora_tasks=("fact_check",),
                                       runtime_stats=rs,
                                       program_stats=cat)
        texts = [f"acceptance probe {i} about maritime law phrasing"
                 for i in range(6)]

        def drive(task="intent"):
            # twice: first step is the cold compile, second the warm
            # execute that feeds the EWMA join
            eng.classify_batch(task, texts)
            eng.classify_batch(task, texts)

        def joined(rows):
            return [r for r in rows if r.get("executes", 0) >= 1
                    and "achieved_flops_per_s" in r]

        try:
            # -- fused (packing off) ----------------------------------
            eng.configure_packing({"enabled": False})
            drive()
            snap = cat.report(runtime_stats=rs)
            fused = _variant_rows(snap, variant="fused", mesh="off")
            assert fused, snap["programs"]
            assert all(r["flops"] > 0 and not r.get("error")
                       for r in fused)
            assert joined(fused), fused

            # -- packed ------------------------------------------------
            eng.configure_packing({"enabled": True})
            drive()
            snap = cat.report(runtime_stats=rs)
            packed = _variant_rows(snap, variant="packed")
            assert packed and all(r["flops"] > 0 and not r.get("error")
                                  for r in packed)
            assert joined(packed), packed

            # -- quantized ---------------------------------------------
            eng.configure_quant({"mode": "int8"})
            drive()
            snap = cat.report(runtime_stats=rs)
            quant = [r for r in snap["programs"] if r["quant"] == "int8"]
            assert quant and all(r["flops"] > 0 and not r.get("error")
                                 for r in quant)
            assert joined(quant), quant
            eng.configure_quant({"mode": "off"})

            # -- epilogue + bgmv kernels -------------------------------
            eng.configure_kernels({"epilogue": {"enabled": True},
                                   "bgmv": {"enabled": True,
                                            "min_tasks": 1}})
            drive()
            snap = cat.report(runtime_stats=rs)
            kern = [r for r in snap["programs"]
                    if r["kernels"] != "off"]
            assert kern, snap["programs"]
            assert any("epilogue" in r["kernels"] for r in kern)
            assert all(r["flops"] > 0 and not r.get("error")
                       for r in kern)
            eng.configure_kernels({})

            # -- mesh-sharded (forced 8-device CPU mesh) ---------------
            eng.configure_mesh({"enabled": True, "dp": 4, "tp": 2})
            drive()
            snap = cat.report(runtime_stats=rs)
            mesh = [r for r in snap["programs"]
                    if r["mesh"] not in ("", "off")]
            assert mesh, snap["programs"]
            assert any(r["mesh"] == "4x2x1" for r in mesh)
            assert all(r["flops"] > 0 and not r.get("error")
                       for r in mesh)
            assert joined(mesh), mesh

            # report shape: device tier + catalog accounting
            assert snap["device"]["device_count"] == 8
            assert snap["catalog_size"] == len(snap["programs"])
            assert snap["capture_errors"] == 0
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# satellite 2: hot flips retire dead program keys (10-flip regression)


class TestRetirementOnHotFlips:
    def test_ten_consecutive_flips_stay_bounded(self):
        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        cat = ProgramCatalog(reg)
        eng = make_shared_trunk_engine(runtime_stats=rs, program_stats=cat)
        texts = [f"flip probe {i} with filler words" for i in range(5)]
        sizes, gauge_sizes, rs_sizes = [], [], []
        try:
            for i in range(10):
                quant = "int8" if i % 2 == 0 else "off"
                eng.configure_quant({"mode": quant})
                eng.classify_batch("intent", texts)
                snap = cat.report(runtime_stats=rs)
                # every surviving row serves the CURRENT quant mode —
                # the flip retired the previous program set's keys
                assert all(r["quant"] == quant
                           for r in snap["programs"]), (i, snap)
                sizes.append(snap["catalog_size"])
                gauge_sizes.append(len(cat.flops_gauge._values))
                rs_sizes.append(len(rs.programs()))
            # bounded: flip #10 holds exactly what flip #2 held (the
            # steady state), not 5x it
            assert sizes[-1] == sizes[1], sizes
            assert gauge_sizes[-1] == gauge_sizes[1], gauge_sizes
            assert rs_sizes[-1] <= rs_sizes[1], rs_sizes
        finally:
            eng.shutdown()

    def test_packing_disable_retires_packed_keys_everywhere(self):
        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        cat = ProgramCatalog(reg)
        eng = make_shared_trunk_engine(runtime_stats=rs, program_stats=cat)
        texts = [f"packing probe {i} extra words" for i in range(5)]
        try:
            eng.classify_batch("intent", texts)  # packed (default on)
            cat.report(runtime_stats=rs)
            assert any(r["variant"].startswith("packed")
                       for r in cat.report(runtime_stats=rs)["programs"])
            eng.configure_packing({"enabled": False})
            snap = cat.report(runtime_stats=rs)
            assert not any(r["variant"].startswith("packed")
                           for r in snap["programs"])
            assert not any(p["variant"].startswith("packed")
                           for p in rs.programs())
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# satellite 3: device-memory gauge spelling table


class FakeDevice:
    def __init__(self, stats, id=0, platform="tpu"):
        self.id = id
        self.platform = platform
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


class TestDeviceMemorySpellings:
    @pytest.mark.parametrize("spelling,stat,value", [
        ("bytes_in_use", "bytes_in_use", 111),
        ("bytes_limit", "bytes_limit", 222),
        ("bytes_reservable_limit", "bytes_limit", 333),
        ("pool_bytes", "bytes_limit", 444),
        ("peak_bytes_in_use", "peak_bytes_in_use", 555),
        ("peak_pool_bytes", "peak_bytes_in_use", 666),
    ])
    def test_each_backend_spelling_resolves(self, spelling, stat, value):
        rs = RuntimeStats(MetricsRegistry())
        row = rs.device_memory_row(FakeDevice({spelling: value}))
        assert row[stat] == value
        assert value in [v for v in rs.device_memory._values.values()]

    def test_first_spelling_wins(self):
        rs = RuntimeStats(MetricsRegistry())
        row = rs.device_memory_row(FakeDevice(
            {"bytes_limit": 1, "pool_bytes": 2}))
        assert row["bytes_limit"] == 1

    def test_absent_on_cpu_publishes_nothing(self):
        # jax CPU devices return None from memory_stats(): the row is
        # identity-only and the gauge must NOT publish zeros
        rs = RuntimeStats(MetricsRegistry())
        row = rs.device_memory_row(FakeDevice(None, platform="cpu"))
        assert set(row) == {"device", "platform"}
        assert len(rs.device_memory._values) == 0

    def test_memory_stats_raising_is_fail_open(self):
        rs = RuntimeStats(MetricsRegistry())
        row = rs.device_memory_row(
            FakeDevice(RuntimeError("pjrt"), id=3))
        assert row == {"device": "3", "platform": "tpu"}

    def test_table_covers_the_three_stats(self):
        assert [s for s, _ in DEVICE_MEMORY_STATS] == [
            "bytes_in_use", "bytes_limit", "peak_bytes_in_use"]

    def test_live_cpu_devices_yield_identity_rows(self):
        rs = RuntimeStats(MetricsRegistry())
        for d in jax.local_devices():
            row = rs.device_memory_row(d)
            assert row["platform"] == "cpu"
            assert set(row) == {"device", "platform"}


# ---------------------------------------------------------------------------
# satellite 4: /debug/runtime schema across the knob matrix


class FakeRegistry:
    def __init__(self, **slots):
        self._slots = slots

    def get(self, name):
        return self._slots.get(name)


class FakeCascade:
    def report(self):
        return {"enabled": True, "waves": 3}


class TestRuntimeDebugReportMatrix:
    def test_no_runtimestats_is_none(self):
        from semantic_router_tpu.router.server import runtime_debug_report

        assert runtime_debug_report(FakeRegistry(), None) is None

    def test_no_engine_still_reports_stats(self):
        from semantic_router_tpu.router.server import runtime_debug_report

        rep = runtime_debug_report(
            FakeRegistry(runtimestats=RuntimeStats(MetricsRegistry())),
            None)
        assert rep is not None and "programs" in rep
        for block in ("packing", "kernels", "mesh", "cascade"):
            assert block not in rep

    def test_knob_matrix_block_presence_and_truth(self):
        from semantic_router_tpu.router.server import runtime_debug_report

        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        eng = make_shared_trunk_engine(runtime_stats=rs,
                                       program_stats=ProgramCatalog(reg))
        casc = FakeCascade()
        try:
            for pk, quant, kern, mesh, with_casc in product(
                    (True, False), ("int8", "off"), (True, False),
                    (True, False), (True, False)):
                eng.configure_packing({"enabled": pk})
                eng.configure_quant({"mode": quant})
                eng.configure_kernels(
                    {"epilogue": {"enabled": kern}})
                eng.configure_mesh({"enabled": mesh, "dp": 4, "tp": 2}
                                   if mesh else {"enabled": False})
                slots = {"runtimestats": rs}
                if with_casc:
                    slots["cascade"] = casc
                rep = runtime_debug_report(FakeRegistry(**slots), eng)
                combo = (pk, quant, kern, mesh, with_casc)
                # enabled blocks present with their truth; the cascade
                # block absent exactly when no evaluator is registered
                assert rep["packing"]["knobs"]["enabled"] is pk, combo
                assert rep["kernels"]["quant"]["mode"] == quant, combo
                assert rep["kernels"]["kernels"]["epilogue"][
                    "enabled"] is kern, combo
                assert rep["mesh"]["enabled"] is mesh, combo
                if with_casc:
                    assert rep["cascade"] == casc.report(), combo
                else:
                    assert "cascade" not in rep, combo
                assert "programs" in rep  # the runtimestats body rides
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# perf-regression gate


def _load_programgate():
    spec = importlib.util.spec_from_file_location(
        "programgate", os.path.join(REPO, "perf", "programgate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestPerfGate:
    BASELINE = os.path.join(REPO, "perf", "program_baseline.json")
    REGRESSED = os.path.join(REPO, "tests", "fixtures", "perf",
                             "program_baseline_regressed.json")

    def test_baseline_files_exist_and_parse(self):
        with open(self.BASELINE) as f:
            base = json.load(f)
        with open(self.REGRESSED) as f:
            reg = json.load(f)
        assert set(base) == set(reg)
        gate = _load_programgate()
        for key, row in base.items():
            for field in gate.GATE_FIELDS:
                assert row[field] > 0
                # the planted fixture is the baseline halved — current
                # costs read as a 2x regression against it
                assert reg[key][field] == pytest.approx(row[field] / 2)

    def test_clean_against_itself(self):
        gate = _load_programgate()
        with open(self.BASELINE) as f:
            base = json.load(f)
        verdict = gate.compare(base, base)
        assert verdict["ok"] and not verdict["regressions"]
        assert verdict["matched"] == len(base)

    def test_flags_planted_2x_fixture(self):
        gate = _load_programgate()
        with open(self.BASELINE) as f:
            current = json.load(f)
        with open(self.REGRESSED) as f:
            regressed = json.load(f)
        verdict = gate.compare(current, regressed)
        assert not verdict["ok"]
        # every field of every program doubled: all must flag
        assert len(verdict["regressions"]) == \
            len(current) * len(gate.GATE_FIELDS)

    def test_zero_overlap_fails(self):
        gate = _load_programgate()
        verdict = gate.compare({"a|1|v|off|off|off": {"flops": 1}},
                               {"b|1|v|off|off|off": {"flops": 1}})
        assert verdict["matched"] == 0 and not verdict["ok"]

    def test_program_set_drift_warns_but_passes(self):
        gate = _load_programgate()
        with open(self.BASELINE) as f:
            base = json.load(f)
        extra = dict(base)
        extra["gone|1|v|off|off|off"] = {"flops": 1, "bytes_accessed": 1,
                                         "hbm_peak_bytes": 1}
        verdict = gate.compare(base, extra)
        assert verdict["ok"]
        assert verdict["only_baseline"] == ["gone|1|v|off|off|off"]


# ---------------------------------------------------------------------------
# SLO-burn-triggered capture


class FakeProfiler:
    def __init__(self):
        self.starts = 0
        self.stops = 0

    def start(self, log_dir=""):
        self.starts += 1
        return {"started": True, "dir": f"/tmp/fake-trace-{self.starts}"}

    def stop(self, force=False):
        self.stops += 1
        return {"stopped": True}


class TestSLOCapture:
    def _catalog(self):
        cat = ProgramCatalog(MetricsRegistry())
        cat.note_compile("g", 32, "fused:seq", (4, 32), _matmul_lower(),
                         measured_variant="fused")
        return cat

    def test_firing_alert_captures_once_with_cooldown(self):
        bus = EventBus()
        prof = FakeProfiler()
        fr = FlightRecorder()
        cat = self._catalog()
        ctl = SLOCaptureController(catalog=cat, profiler=prof,
                                   flightrec=fr, events=bus,
                                   trace_s=0.05, cooldown_s=60.0)
        ctl.attach(bus)
        try:
            bus.emit(SLO_ALERT_FIRING, objective="routing_latency",
                     severity="page")
            caps = ctl.report()
            assert len(caps) == 1
            cap = caps[0]
            assert cap["objective"] == "routing_latency"
            assert cap["reason"] == "slo_alert"
            assert cap["catalog_size"] == 1
            assert cap["programs"][0]["flops"] > 0
            assert cap["trace_dir"] == "/tmp/fake-trace-1"
            assert prof.starts == 1
            # the bounded trace stops itself
            ctl.join(timeout=5.0)
            assert prof.stops == 1
            # a flapping alert inside the cooldown captures nothing new
            bus.emit(SLO_ALERT_FIRING, objective="routing_latency")
            assert len(ctl.report()) == 1
            assert prof.starts == 1
            # the capture announces itself on the bus
            stages = [e.stage for e in bus.recent(limit=10)]
            assert SLO_CAPTURE in stages
            (ev,) = [e for e in bus.recent(limit=10)
                     if e.stage == SLO_CAPTURE]
            assert ev.detail["id"] == cap["id"]
            assert ev.detail["trace_dir"] == cap["trace_dir"]
        finally:
            ctl.detach()
            ctl.join(timeout=5.0)

    def test_flightrec_dump_cross_links_captures(self):
        fr = FlightRecorder()
        cat = self._catalog()
        ctl = SLOCaptureController(catalog=cat, profiler=None,
                                   flightrec=fr, trace_s=0.0)
        ctl.trigger(objective="queue_wait", reason="slo_alert")
        dump = fr.dump()
        assert "slo_captures" in dump
        (link,) = dump["slo_captures"]
        assert link["objective"] == "queue_wait"
        assert link["id"] == "slocap-1"
        assert link["catalog_size"] == 1

    def test_busy_profiler_is_respected_not_clobbered(self):
        class BusyProfiler:
            def start(self, log_dir=""):
                return {"error": "profiler already running",
                        "dir": "/tmp/other", "status": 409}

            def stop(self, force=False):  # pragma: no cover
                raise AssertionError("must not stop a trace we "
                                     "didn't start")

        ctl = SLOCaptureController(catalog=self._catalog(),
                                   profiler=BusyProfiler(),
                                   trace_s=0.05)
        cap = ctl.trigger(objective="x")
        assert "trace_dir" not in cap
        assert "already running" in cap["trace_skipped"]
        ctl.join(timeout=1.0)

    def test_ring_is_bounded(self):
        ctl = SLOCaptureController(catalog=None, cooldown_s=0.0,
                                   trace_s=0.0, max_captures=3)
        for i in range(5):
            ctl.trigger(objective=f"o{i}")
        links = ctl.links()
        assert len(links) == 3
        assert links[-1]["objective"] == "o4"

    def test_catalog_report_carries_capture_ring(self):
        cat = self._catalog()
        ctl = SLOCaptureController(catalog=cat, trace_s=0.0)
        cat.slo_capture = ctl
        ctl.trigger(objective="lat")
        snap = cat.report()
        assert snap["slo_captures"][0]["objective"] == "lat"


# ---------------------------------------------------------------------------
# API surface coherence for the new endpoint


class TestDebugProgramsSurface:
    def test_in_catalog_and_openapi(self):
        from semantic_router_tpu.router import openapi
        from semantic_router_tpu.router.server import API_CATALOG

        eps = {(e["method"], e["path"])
               for e in API_CATALOG["endpoints"]}
        assert ("GET", "/debug/programs") in eps
        assert ("GET", "/debug/programs") in openapi._META
        spec = openapi.build_spec(API_CATALOG)
        assert "/debug/programs" in spec["paths"]
        assert openapi.validate_spec(spec) == []

    def test_programs_dashboard_renders(self, tmp_path):
        from semantic_router_tpu.observability import grafana

        dash = grafana.programs()
        assert dash["uid"] == "srt-programs"
        exprs = json.dumps(dash)
        for series in ("llm_program_flops", "llm_program_bytes",
                       "llm_program_hbm_peak_bytes",
                       "llm_program_roofline_fraction"):
            assert series in exprs
        written = grafana.render_all(str(tmp_path))
        assert any(p.endswith("programs.json") for p in written)
