"""chip_smoke.py's phases on CPU at toy width: checkpoint generation →
serve(block=False) → requests → the response / decision-record checks;
that a learned family carrying an error fails the phase; that the device
gate refuses a CPU platform; and that an engine whose warmup step raises
is reported, not swallowed."""

import dataclasses
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

# window/global period stay the model's defaults (128, every third layer);
# buckets are small but span dense (<=4096) and chunked attention on CPU
TOY = chip_smoke.Sizes(
    vocab_size=512, hidden_size=32, intermediate_size=48,
    num_hidden_layers=3, num_attention_heads=2,
    max_position_embeddings=1024, original_max_position_embeddings=256,
    buckets=(32, 128, 512), request_tokens=(20, 100, 400), burst=6,
    parity_tokens=(20, 300))


@pytest.fixture(scope="module")
def running(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("chip_smoke"))
    ckpts = chip_smoke.generate_checkpoints(root, TOY, seed=0)
    config_path = chip_smoke.write_config(root, ckpts, TOY)
    run = chip_smoke.start_router(config_path, warmup_timeout_s=600.0)
    yield SimpleNamespace(run=run, root=root, ckpts=ckpts)
    run.stop()


class TestPhasesOnCpu:
    def test_checkpoints_are_reused_when_complete(self, running, capsys):
        again = chip_smoke.generate_checkpoints(running.root, TOY, seed=0)
        assert again == running.ckpts
        assert "reusing" in capsys.readouterr().out
        for task in ("intent", "jailbreak", "pii", "embedding"):
            d = running.ckpts[task]
            assert sorted(os.listdir(d)) == ["config.json",
                                             "model.safetensors"]

    def test_every_bucket_of_every_target_warmed(self, running):
        report = running.run.engine.warmup_report()
        assert report and not any(r["error"] for r in report)
        warmed = {(r["target"], r["bucket"]) for r in report}
        # intent + jailbreak + pii fuse into ONE trunk group; the
        # embedding task warms on its own
        assert warmed == {(t, b) for t in ("task:embedding", "trunk:trunk0")
                          for b in TOY.buckets}
        assert running.run.engine.trunk_group_info() == {
            "trunk0": ["intent", "jailbreak", "pii"]}

    def test_weights_live_on_the_device_once(self, running):
        """build_engine hands over numpy arrays; left so, every step
        re-uploads the whole tree through the jit boundary."""
        import jax

        engine = running.run.engine
        for name in engine.tasks():
            leaves = jax.tree_util.tree_leaves(engine._tasks[name].params)
            assert leaves and all(isinstance(x, jax.Array) for x in leaves)
        (g,) = engine._groups_by_gid.values()
        assert g.fns["trunk_params"] is g.trunk_params
        # the three fused members hold ONE trunk between them
        assert {id(engine._tasks[n].params["params"]["model"])
                for n in g.members} == {id(g.trunk_params)}

    def test_requests_and_decision_records(self, running):
        engine = running.run.engine
        steps0 = chip_smoke.device_steps(engine)
        responses = chip_smoke.send_requests(running.run.server.url, TOY,
                                             seed=0)
        assert len(responses) == len(TOY.request_tokens) + TOY.burst + 1
        chip_smoke.check_responses(running.run.server.url, responses)
        chip_smoke.check_engine(engine, steps0, "cpu",
                                want_padded_batch=True)
        # every bucket the requests aimed at really ran
        ran = {s[1] for v in engine.shape_census().values() for s in v}
        assert set(TOY.buckets) <= ran

    def test_engine_matches_plain_jnp_reference(self, running):
        chip_smoke.check_parity(running.run.engine, TOY, seed=0)

    def test_kernel_phase_interpreted(self):
        chip_smoke.check_kernels(32, task_counts=(6,), interpret=True)


class TestMultichipPhaseOnCpuMesh:
    def test_mesh_engine_matches_single_device(self, tmp_path):
        """--multichip's phases on the first four of conftest's eight
        virtual CPU devices: placement on four devices, tp's all-reduce
        in the compiled text, same labels as a single-device engine, and
        the sharded ANN bank."""
        sizes = dataclasses.replace(
            TOY, buckets=(32, 128), request_tokens=(20, 100), burst=0)
        chip_smoke.run_multichip(sizes, 0, str(tmp_path), ann_rows=1024)


class TestChecksFail:
    RECORD = {"id": "r1", "signals": {
        fam: {"source": "engine", "error": "", "hits": []}
        for fam in chip_smoke.CORE_FAMILIES}}

    def test_clean_record_passes(self):
        chip_smoke.check_decision_records([self.RECORD], expected=1)

    def test_family_with_error_fails_the_phase(self):
        bad = {"id": "r2", "signals": {
            **self.RECORD["signals"],
            "jailbreak": {"source": "engine", "hits": [],
                          "error": "XlaRuntimeError: compile failed"}}}
        with pytest.raises(chip_smoke.SmokeFailure, match="jailbreak"):
            chip_smoke.check_decision_records([self.RECORD, bad],
                                              expected=2)

    def test_heuristic_source_fails_the_phase(self):
        bad = {"id": "r3", "signals": {
            **self.RECORD["signals"],
            "domain": {"source": "heuristic", "error": "", "hits": []}}}
        with pytest.raises(chip_smoke.SmokeFailure, match="domain"):
            chip_smoke.check_decision_records([bad], expected=1)

    def test_missing_family_or_record_fails_the_phase(self):
        missing = {"id": "r4", "signals": {
            k: v for k, v in self.RECORD["signals"].items() if k != "pii"}}
        with pytest.raises(chip_smoke.SmokeFailure, match="pii"):
            chip_smoke.check_decision_records([missing], expected=1)
        with pytest.raises(chip_smoke.SmokeFailure, match="records"):
            chip_smoke.check_decision_records([self.RECORD], expected=2)

    def test_device_gate_refuses_cpu(self):
        import jax

        with pytest.raises(chip_smoke.SmokeFailure, match="not a TPU"):
            chip_smoke.device_gate(jax.devices(), 1)

    def test_device_gate_wants_the_mode_s_chip_count(self):
        tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        assert chip_smoke.device_gate([tpu], 1) == {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        with pytest.raises(chip_smoke.SmokeFailure, match="4 chip"):
            chip_smoke.device_gate([tpu], 4)

    def test_main_on_cpu_exits_nonzero_and_prints_no_result(
            self, capsys, monkeypatch, tmp_path):
        # placed from outside, main() sets no cache directory in code —
        # and so leaves this test process's JAX configuration alone
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert chip_smoke.main([]) != 0
        out = capsys.readouterr()
        assert out.out == ""
        assert "not a TPU" in out.err


class TestCompileCachePlacement:
    def test_env_var_wins_and_nothing_is_set_in_code(self, monkeypatch,
                                                     tmp_path):
        import jax

        from semantic_router_tpu.runtime.compile_cache import (
            configure_compile_cache,
        )

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_uses_one_fixed_path_inside_the_checkout(self,
                                                           monkeypatch):
        import jax

        from semantic_router_tpu.runtime import compile_cache

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(
            repo, ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            assert compile_cache.configure_compile_cache() == \
                compile_cache.DEFAULT_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == \
                compile_cache.DEFAULT_CACHE_DIR
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


class TestWarmupFailureIsReported:
    def _engine(self):
        from semantic_router_tpu.engine.testing import (
            make_shared_trunk_engine,
        )

        return make_shared_trunk_engine()

    def test_failing_warmup_step_raises_with_the_program_named(self):
        from semantic_router_tpu.engine.classify import WarmupError

        engine = self._engine()
        try:
            (g,) = engine._groups_by_gid.values()

            def refuse(*_a, **_k):
                raise RuntimeError("Mosaic failed to compile TPU kernel")

            g.fns = {**g.fns, "seq": refuse}
            with pytest.raises(WarmupError) as err:
                engine.warmup()
            assert "trunk:" in str(err.value)
            assert "Mosaic failed to compile" in str(err.value)
            rows = engine.warmup_report()
            # every bucket was still attempted, and each says what failed
            assert [r["bucket"] for r in rows] == \
                list(engine.cfg.seq_len_buckets)
            assert all("Mosaic failed" in r["error"] for r in rows)
        finally:
            engine.shutdown()

    def test_clean_warmup_reports_seconds_per_bucket(self):
        engine = self._engine()
        try:
            engine.warmup()
            rows = engine.warmup_report()
            assert rows and all(r["error"] == "" and r["seconds"] > 0
                                for r in rows)
        finally:
            engine.shutdown()

    def test_batch_sizes_warm_the_padded_batches(self):
        """serve() warms batch 1; the smoke warms the burst's batches
        through the same method, padded the way the batcher pads."""
        engine = self._engine()
        try:
            engine.warmup(buckets=[32], batch_sizes=(2, 3))
            rows = engine.warmup_report()
            assert [(r["bucket"], r["rows"]) for r in rows] == \
                [(32, 2), (32, 3)]
            assert not any(r["error"] for r in rows)
        finally:
            engine.shutdown()

    @staticmethod
    def _recover_process_degradation():
        """ENGINE_FAILED on the process bus sends the process-wide
        degradation controller to its fail-static level; announce the
        engine back and tick it down so later tests in this worker route
        normally."""
        from semantic_router_tpu.resilience import (
            default_degradation_controller as controller,
        )
        from semantic_router_tpu.runtime.events import (
            ENGINE_READY,
            default_bus,
        )

        default_bus.emit(ENGINE_READY, tasks=[])
        for _ in range(1000):
            if controller.tick() == 0:
                break
        assert controller.level() == 0

    def test_serve_marks_startup_failed(self, tmp_path, monkeypatch):
        """serve() used to emit an event and advance to ready anyway."""
        from semantic_router_tpu.engine.classify import (
            InferenceEngine,
            WarmupError,
        )

        root = str(tmp_path)
        sizes = dataclasses.replace(TOY, buckets=(32,))
        ckpts = chip_smoke.generate_checkpoints(root, sizes, seed=1)
        config_path = chip_smoke.write_config(root, ckpts, sizes)

        def refuse(self, *a, **k):
            raise WarmupError("1 warmup program(s) failed: trunk:trunk0@32:"
                              " the compiler's message")

        monkeypatch.setattr(InferenceEngine, "warmup", refuse)
        try:
            with pytest.raises(chip_smoke.SmokeFailure) as err:
                chip_smoke.start_router(config_path, warmup_timeout_s=60.0)
        finally:
            self._recover_process_degradation()
        assert "warmup failed" in str(err.value)
        assert "the compiler's message" in str(err.value)
        # the readiness surface says failed, not ready
        assert "'failed': True" in str(err.value)
        assert "'ready': False" in str(err.value)
