"""Runtime telemetry (observability/runtimestats.py): the always-on
device-step sampler, per-jit-program accounting, and process gauges —
ISSUE 3's continuous profiling layer."""

import gc
import threading
import time

import pytest

from semantic_router_tpu.observability.metrics import (
    MetricSeries,
    MetricsRegistry,
)
from semantic_router_tpu.observability.runtimestats import RuntimeStats


class TestProgramRegistry:
    def test_cold_vs_warm_accounting(self):
        rs = RuntimeStats(MetricsRegistry())
        rs.record_step("trunk:g0", 128, "fused", 4, 8, 2.0, compiled=True)
        rs.record_step("trunk:g0", 128, "fused", 6, 8, 0.010)
        rs.record_step("trunk:g0", 128, "fused", 8, 8, 0.020)
        (p,) = rs.programs()
        assert p["compiles"] == 1
        assert p["compile_s_total"] == pytest.approx(2.0)
        # cold step excluded from the warm execute stats
        assert p["executes"] == 2
        assert p["execute_s_total"] == pytest.approx(0.030)
        assert 0.010 < p["execute_ewma_s"] < 0.020
        assert p["last_execute_s"] == pytest.approx(0.020)

    def test_padding_waste_accounting(self):
        rs = RuntimeStats(MetricsRegistry())
        rs.record_step("task:pii", 32, "split", 3, 4, 0.001)
        (p,) = rs.programs()
        assert p["rows_real"] == 3 and p["rows_padded"] == 4
        assert p["padding_waste_ratio"] == pytest.approx(0.25)
        # and the rows counter splits real vs padding
        rows = rs.step_rows.values()
        by_kind = {dict(k).get("kind"): v for k, v in rows.items()}
        assert by_kind == {"real": 3.0, "padding": 1.0}

    def test_programs_keyed_by_group_bucket_variant(self):
        rs = RuntimeStats(MetricsRegistry())
        rs.record_step("trunk:g0", 128, "fused", 1, 1, 0.01)
        rs.record_step("trunk:g0", 512, "fused", 1, 1, 0.01)
        rs.record_step("task:pii", 128, "split", 1, 1, 0.01)
        assert len(rs.programs()) == 3

    def test_disabled_short_circuits(self):
        rs = RuntimeStats(MetricsRegistry())
        rs.enabled = False
        rs.record_step("g", 32, "split", 1, 1, 0.01)
        assert rs.programs() == []

    def test_bounded_pending_never_blocks(self):
        rs = RuntimeStats(MetricsRegistry(), max_pending=16)
        for i in range(100):
            rs.record_step("g", 32, "split", 1, 1, 0.01)
        assert rs.flush() <= 16
        assert rs._dropped > 0

    def test_series_exposed_in_registry(self):
        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        rs.record_step("g", 32, "split", 1, 2, 0.01)
        rs.record_step("g", 32, "split", 1, 2, 5.0, compiled=True)
        rs.flush()
        text = reg.expose()
        assert "llm_runtime_step_seconds_bucket" in text
        assert "llm_runtime_program_compiles_total" in text
        assert "llm_runtime_step_rows_total" in text

    def test_a_prefills_attention_tiles_are_counted(self):
        """``attn_tiles = (visited, grid)`` of a prefill whose flash calls
        get its rows' lengths: two counters beside the forwards'; a
        program that hands none (a decode loop, a chat guard's prefill)
        adds to neither."""
        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        rs.record_generation("t", "gen.prefill", attn_tiles=(21, 36))
        rs.record_generation("t", "gen.prefill", attn_tiles=(0, 36))
        rs.record_generation("t", "gen.decode", forwards=31)
        rs.record_generation("u", "gen.prefill")
        assert rs.gen_attn_tiles_visited.get(task="t") == 21
        assert rs.gen_attn_tiles_grid.get(task="t") == 72
        assert rs.gen_attn_tiles_grid.get(task="u") == 0
        assert rs.gen_forwards.get(task="t", flavour="gen.prefill") == 2
        text = reg.expose()
        assert 'llm_runtime_gen_attn_tiles_visited_total{task="t"} 21' \
            in text
        assert 'llm_runtime_gen_attn_tiles_grid_total{task="t"} 72' in text
        disabled = RuntimeStats(MetricsRegistry())
        disabled.enabled = False
        disabled.record_generation("t", "gen.prefill", attn_tiles=(1, 2))
        assert disabled.gen_attn_tiles_grid.get(task="t") == 0


class TestProcessGauges:
    def test_rss_and_threads(self):
        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        sample = rs.sample_process()
        assert sample["rss_bytes"] > 0
        assert sample["threads"] >= 1
        assert "llm_process_rss_bytes" in reg.expose()

    def test_provider_scrape_and_replacement(self):
        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        rs.register_provider("b1", lambda: {"pending_items": 7})
        sample = rs.sample_process()
        assert sample["queues"]["b1"]["pending_items"] == 7.0
        # re-registration replaces (rebuilt engine), never duplicates
        rs.register_provider("b1", lambda: {"pending_items": 1})
        assert rs.sample_process()["queues"]["b1"]["pending_items"] == 1.0
        rs.unregister_provider("b1")
        assert rs.sample_process()["queues"] == {}

    def test_sibling_shutdown_keeps_live_provider(self):
        """Engine A shutting down must not rip out engine B's provider
        registered under the same batcher name (identity-guarded
        unregister)."""
        rs = RuntimeStats(MetricsRegistry())

        def fn_a():
            return {"x": 1}

        def fn_b():
            return {"x": 2}

        rs.register_provider("b", fn_a)
        rs.register_provider("b", fn_b)   # engine B replaced A's slot
        rs.unregister_provider("b", fn_a)  # A's shutdown: no-op now
        assert rs.sample_process()["queues"]["b"]["x"] == 2.0
        rs.unregister_provider("b", fn_b)  # B's own shutdown removes it
        assert rs.sample_process()["queues"] == {}

    def test_broken_provider_never_kills_sampling(self):
        rs = RuntimeStats(MetricsRegistry())

        def boom():
            raise RuntimeError("batcher stopped")

        rs.register_provider("dead", boom)
        rs.register_provider("live", lambda: {"x": 1})
        sample = rs.sample_process()
        assert "dead" not in sample["queues"]
        assert sample["queues"]["live"]["x"] == 1.0

    def test_gc_pause_capture(self):
        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        rs._install_gc_callback()
        try:
            gc.collect()
        finally:
            rs._remove_gc_callback()
        # the callback only accumulates (it must stay nearly free);
        # sample_process publishes the counts
        rs.sample_process()
        assert rs.gc_collections.total() >= 1
        assert "llm_gc_pause_seconds" in reg.expose()

    def test_sampler_thread_lifecycle(self):
        rs = RuntimeStats(MetricsRegistry())
        rs.record_step("g", 32, "split", 1, 1, 0.01)
        rs.start(0.05)
        try:
            deadline = time.time() + 2.0
            while time.time() < deadline and not rs.programs():
                time.sleep(0.02)
            assert rs.programs()
            assert rs.report(sample=False)["sampler_running"]
        finally:
            rs.stop()
        assert not rs.report(sample=False)["sampler_running"]
        # idempotent restart retunes the interval
        rs.start(0.2)
        rs.start(0.3)
        assert rs.interval_s == pytest.approx(0.3)
        rs.stop()


class TestEngineIntegration:
    @pytest.fixture(scope="class")
    def engine_stats(self):
        from semantic_router_tpu.engine.testing import (
            make_shared_trunk_engine,
        )

        reg = MetricsRegistry()
        rs = RuntimeStats(reg)
        eng = make_shared_trunk_engine(metrics=MetricSeries(reg),
                                       runtime_stats=rs)
        yield eng, rs
        eng.shutdown()

    def test_fused_step_sampled(self, engine_stats):
        eng, rs = engine_stats
        eng.classify_multi(["intent", "fact_check"],
                           ["runtime stats request one"])
        progs = {(p["group"], p["variant"]) for p in rs.programs()}
        assert any(g.startswith("trunk:") and v == "fused"
                   for g, v in progs)
        # the first step of a fresh shape is the compile
        p = next(p for p in rs.programs()
                 if p["group"].startswith("trunk:"))
        assert p["compiles"] >= 1

    def test_warm_steps_become_executes(self, engine_stats):
        eng, rs = engine_stats
        for i in range(3):
            eng.classify("intent", f"warm request number {i}")
        p = next(p for p in rs.programs()
                 if p["group"].startswith("trunk:"))
        assert p["executes"] >= 1
        assert p["execute_ewma_s"] > 0

    def test_queue_provider_registered(self, engine_stats):
        eng, rs = engine_stats
        sample = rs.sample_process()
        stats = sample["queues"][eng.batcher.name]
        assert {"pending_items", "pool_saturation"} <= set(stats)

    def test_report_shape(self, engine_stats):
        _, rs = engine_stats
        rep = rs.report()
        assert rep["enabled"] is True
        assert isinstance(rep["programs"], list)
        assert "process" in rep and "queues" in rep["process"]

    def test_shutdown_unregisters_provider(self):
        from semantic_router_tpu.engine.testing import make_test_engine
        from semantic_router_tpu.observability.runtimestats import (
            default_runtime_stats,
        )

        eng = make_test_engine()
        name = eng.batcher.name
        assert name in default_runtime_stats._providers
        eng.shutdown()
        assert name not in default_runtime_stats._providers


class TestBatcherTelemetry:
    def test_queue_depths_shape(self):
        from semantic_router_tpu.engine.batcher import DynamicBatcher

        done = threading.Event()

        def runner(key, items):
            done.wait(2.0)
            return [None] * len(items)

        b = DynamicBatcher(runner, max_batch_size=4, max_wait_ms=1.0)
        try:
            futs = [b.submit("g", i) for i in range(2)]
            time.sleep(0.05)  # let the batch dispatch and block
            d = b.queue_depths()
            assert d["pool_busy"] >= 1
            assert 0.0 < d["pool_saturation"] <= 1.0
            done.set()
            for f in futs:
                f.result(timeout=5)
            assert b.queue_depths()["pending_items"] == 0
        finally:
            done.set()
            b.shutdown()
