"""Mesh-sharded serving of the packed classifier bank (docs/PARALLEL.md).

ISSUE 15 acceptance: with ``engine.mesh.enabled: true`` on the forced
8-device CPU mesh (conftest sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), fused and
packed batches execute with dp-sharded rows and task-sharded head
banks, logit parity ≤1e-4 against the single-device path across
fused / packed / LoRA'd / deduped / token batches (quantized batches
gate through the engine.quant parity policy — bf16-compute matmuls
partition with different rounding, docs/KERNELS.md), ``enabled: false``
(the default) stays byte-identical, and a hot mesh flip under
concurrent traffic never fails an in-flight batch.

Tier-1 via ``make mesh-smoke`` (VSR_ANALYZE=1: the lock-order witness,
thread-leak gate, and access witness all arm over the hot-flip path).
"""

import threading

import numpy as np
import pytest

import jax

from semantic_router_tpu.config.schema import (
    InferenceEngineConfig,
    RouterConfig,
)
from semantic_router_tpu.engine.mesh import (
    build_serving_mesh,
    mesh_signature,
    normalize_mesh,
    resolve_axes,
)
from semantic_router_tpu.engine.testing import make_shared_trunk_engine
from semantic_router_tpu.observability.metrics import (
    MetricSeries,
    MetricsRegistry,
)

SEQ_TASKS = [
    ("intent", ["business", "law", "health", "other"]),
    ("fact_check", ["no_fact_check", "fact_check"]),
    ("user_feedback", ["none", "positive", "negative"]),
]
TOK_TASKS = [("pii", ["O", "B-EMAIL_ADDRESS", "I-EMAIL_ADDRESS"])]

MIXED_TEXTS = [("word " * (3 + i % 11)).strip() for i in range(13)]


def make_engine(mesh=None, packing=True, quant=None, max_batch=8,
                metrics=None, token=True):
    """Shared-trunk engine (LoRA'd member + token member) — identical
    params per seed, so a mesh-on and a mesh-off engine are the same
    model placed differently."""
    return make_shared_trunk_engine(
        tasks=SEQ_TASKS,
        lora_tasks=["fact_check"],
        token_tasks=TOK_TASKS if token else None,
        engine_cfg=InferenceEngineConfig(
            max_batch_size=max_batch, max_wait_ms=1.0,
            seq_len_buckets=[32, 128],
            packing={"enabled": bool(packing)},
            mesh=dict(mesh or {}),
            quant=dict(quant or {})),
        metrics=metrics or MetricSeries(MetricsRegistry()))


def assert_parity(ref, got, atol=1e-4):
    for task in ref:
        for r, g in zip(ref[task], got[task]):
            assert g.label == r.label, (task, r.label, g.label)
            diff = max(abs(r.probs[k] - g.probs[k]) for k in r.probs)
            assert diff <= atol, (task, diff)


class TestMeshKnobs:
    def test_normalize_defaults_off(self):
        mk = normalize_mesh(None)
        assert mk == {"enabled": False, "dp": 0, "tp": 1}

    def test_normalize_clamps_malformed(self):
        mk = normalize_mesh({"enabled": 1, "dp": "nope", "tp": -3})
        assert mk["enabled"] is True
        assert mk["dp"] == 0 and mk["tp"] == 1

    def test_schema_delegates_to_normalizer(self):
        cfg = RouterConfig.from_dict(
            {"engine": {"mesh": {"enabled": True, "dp": 4, "tp": 2}}})
        assert cfg.engine.mesh_config() == \
            {"enabled": True, "dp": 4, "tp": 2}

    def test_resolve_axes_auto_dp(self):
        assert resolve_axes({"enabled": True, "dp": 0, "tp": 2}, 8) == \
            {"dp": 4, "tp": 2}
        assert resolve_axes({"enabled": False}, 8) is None

    def test_resolve_axes_refuses_oversubscription(self):
        with pytest.raises(ValueError):
            resolve_axes({"enabled": True, "dp": 0, "tp": 16}, 8)
        with pytest.raises(ValueError):
            resolve_axes({"enabled": True, "dp": 8, "tp": 2}, 8)

    def test_build_and_signature(self):
        assert len(jax.devices()) >= 8, "conftest forces 8 devices"
        mesh = build_serving_mesh({"enabled": True, "dp": 4, "tp": 2})
        assert mesh_signature(mesh) == (4, 2, 1)
        assert build_serving_mesh({"enabled": False}) is None
        assert mesh_signature(None) is None


class TestMeshParity:
    @pytest.mark.parametrize("mesh", [{"enabled": True},
                                      {"enabled": True, "dp": 4,
                                       "tp": 2}])
    def test_fused_multi_task_parity(self, mesh):
        plain = make_engine()
        sharded = make_engine(mesh=mesh)
        try:
            assert sharded._serving_mesh is not None
            tasks = [t for t, _ in SEQ_TASKS]
            ref = plain.classify_multi(tasks, MIXED_TEXTS)
            got = sharded.classify_multi(tasks, MIXED_TEXTS)
            assert_parity(ref, got)
        finally:
            plain.shutdown()
            sharded.shutdown()

    def test_packed_batches_execute_sharded(self):
        """Mixed-length batches pack under the mesh: dp-sharded rows,
        per-segment demux gathers, parity with the single-device
        packed path — and the packed/mesh counters prove the path."""
        m = MetricSeries(MetricsRegistry())
        plain = make_engine(max_batch=4)
        sharded = make_engine(mesh={"enabled": True, "dp": 4},
                              max_batch=4, metrics=m)
        try:
            tasks = [t for t, _ in SEQ_TASKS[:2]]
            ref = plain.classify_multi(tasks, MIXED_TEXTS)
            got = sharded.classify_multi(tasks, MIXED_TEXTS)
            assert_parity(ref, got)
            assert m.packed_steps.total() > 0, \
                "packed composition never engaged under the mesh"
            assert m.mesh_steps.total() > 0, \
                "llm_engine_mesh_steps_total never counted"
        finally:
            plain.shutdown()
            sharded.shutdown()

    def test_dedup_parity_under_mesh(self):
        """Duplicate prompts collapse to one trunk row and fan out at
        demux — identical under the mesh."""
        texts = ["hot prompt"] * 6 + MIXED_TEXTS[:4]
        m = MetricSeries(MetricsRegistry())
        plain = make_engine()
        sharded = make_engine(mesh={"enabled": True}, metrics=m)
        try:
            ref = plain.classify_batch("intent", texts)
            got = sharded.classify_batch("intent", texts)
            for r, g in zip(ref, got):
                assert g.label == r.label
                diff = max(abs(r.probs[k] - g.probs[k])
                           for k in r.probs)
                assert diff <= 1e-4
            assert m.fused_dedup_rows.total() > 0
        finally:
            plain.shutdown()
            sharded.shutdown()

    def test_token_batches_parity(self):
        plain = make_engine()
        sharded = make_engine(mesh={"enabled": True, "dp": 8})
        try:
            text = "email me at alice@example.com or bob@example.com"
            ref = plain.token_classify("pii", text)
            got = sharded.token_classify("pii", text)
            assert [e.type for e in ref.entities] == \
                [e.type for e in got.entities]
            assert [e.text for e in ref.entities] == \
                [e.text for e in got.entities]
        finally:
            plain.shutdown()
            sharded.shutdown()

    def test_quantized_batches_gate_through_parity_policy(self):
        """int8 under the mesh vs int8 single-device: bf16-compute
        matmuls partition with different reduction order, so this leg
        gates through the engine.quant parity policy (calibrated
        tolerance + top-class agreement with a margin floor,
        docs/KERNELS.md) instead of the raw 1e-4 bound the float legs
        hold bit-identically."""
        from semantic_router_tpu.engine.kernels import normalize_quant

        par = normalize_quant({"mode": "int8"})["parity"]
        plain = make_engine(quant={"mode": "int8"})
        sharded = make_engine(mesh={"enabled": True, "dp": 8},
                              quant={"mode": "int8"})
        try:
            ref = plain.classify_batch("intent", MIXED_TEXTS)
            got = sharded.classify_batch("intent", MIXED_TEXTS)
            agree = disagree = 0
            for r, g in zip(ref, got):
                probs_r = np.asarray([r.probs[k] for k in sorted(r.probs)])
                probs_g = np.asarray([g.probs[k] for k in sorted(g.probs)])
                assert float(np.max(np.abs(probs_r - probs_g))) <= \
                    par["max_logit_diff"]
                top2 = np.sort(probs_r)[-2:]
                margin = float(top2[1] - top2[0])
                if g.label == r.label or margin < par["margin_floor"]:
                    agree += 1
                else:
                    disagree += 1
            assert disagree == 0, (agree, disagree)
        finally:
            plain.shutdown()
            sharded.shutdown()

    def test_disabled_is_byte_identical(self):
        """engine.mesh {enabled: false} (and absent) serve the exact
        same bytes as the pre-mesh engine — np.array_equal, not
        allclose."""
        default = make_engine()
        off = make_engine(mesh={"enabled": False, "dp": 4})
        try:
            assert off._serving_mesh is None
            ref = default.classify_batch("intent", MIXED_TEXTS)
            got = off.classify_batch("intent", MIXED_TEXTS)
            for r, g in zip(ref, got):
                assert np.array_equal(
                    [r.probs[k] for k in sorted(r.probs)],
                    [g.probs[k] for k in sorted(g.probs)])
        finally:
            default.shutdown()
            off.shutdown()


class TestMeshHotFlip:
    def test_flip_under_concurrent_traffic(self):
        """The atomic program-set swap contract: flipping the mesh on,
        reshaping it, and flipping it off while requests are in flight
        never fails a batch, and results stay correct throughout."""
        eng = make_engine()
        ref_engine = make_engine()
        tasks = [t for t, _ in SEQ_TASKS]
        ref = ref_engine.classify_multi(tasks, MIXED_TEXTS)
        errors = []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    got = eng.classify_multi(tasks, MIXED_TEXTS[:6])
                    for task in got:
                        for r, g in zip(ref[task], got[task]):
                            if r.label != g.label:
                                errors.append((task, r.label, g.label))
                except Exception as exc:  # noqa: BLE001
                    errors.append(repr(exc))

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        try:
            for t in threads:
                t.start()
            for knobs in ({"enabled": True, "dp": 4, "tp": 2},
                          {"enabled": True, "dp": 8},
                          {"enabled": False},
                          {"enabled": True, "dp": 2}):
                eng.configure_mesh(knobs)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert errors == [], errors[:5]
            # landed on dp=2: serving still sharded and correct
            got = eng.classify_multi(tasks, MIXED_TEXTS)
            assert_parity(ref, got)
            rep = eng.mesh_report()
            assert rep["enabled"] and rep["axes"]["dp"] == 2
            assert rep["rebuilds"] >= 3
        finally:
            stop.set()
            eng.shutdown()
            ref_engine.shutdown()

    def test_program_snapshot_carries_demux(self):
        """The runner reads ONE dict: programs, serving params, mesh,
        AND the demux banks — a torn (demux, fns) pair under a mesh
        flip would mix committed arrays from different device sets."""
        eng = make_engine(mesh={"enabled": True, "dp": 4}, token=False)
        try:
            (g,) = eng._groups_by_gid.values()
            assert g.fns["demux"] is g.demux
            eng.configure_mesh({"enabled": False})
            assert g.fns["demux"] is g.demux
            eng.configure_mesh({"enabled": True, "dp": 8})
            assert g.fns["demux"] is g.demux
        finally:
            eng.shutdown()

    def test_noop_reapply_rebuilds_nothing(self):
        eng = make_engine(mesh={"enabled": True, "dp": 4})
        try:
            before = eng._mesh_rebuilds
            fns_before = {g.gid: g.fns for g in
                          eng._groups_by_gid.values()}
            eng.configure_mesh({"enabled": True, "dp": 4})
            assert eng._mesh_rebuilds == before
            for gid, g in eng._groups_by_gid.items():
                assert g.fns is fns_before[gid]
        finally:
            eng.shutdown()

    def test_legacy_mesh_shape_owns_placement(self):
        """With the registration-time engine.mesh_shape active the
        engine.mesh block is inert — one placement owner at a time."""
        eng = make_shared_trunk_engine(
            tasks=SEQ_TASKS[:1],
            engine_cfg=InferenceEngineConfig(
                max_batch_size=4, seq_len_buckets=[32],
                mesh_shape={"dp": 8},
                mesh={"enabled": True, "dp": 4}))
        try:
            assert eng.mesh is not None
            assert eng._serving_mesh is None
            rep = eng.mesh_report()
            assert rep["source"] == "mesh_shape"
        finally:
            eng.shutdown()


class TestMeshScheduling:
    def test_padded_batch_scales_and_aligns(self):
        eng = make_engine(mesh={"enabled": True, "dp": 4}, token=False)
        try:
            mesh = eng._serving_mesh
            # rows pad to a dp multiple, floor dp
            assert eng._padded_batch(1, mesh=mesh) == 4
            assert eng._padded_batch(5, mesh=mesh) == 8
            # cap scales by dp: 8 * 4 = 32 rows max
            assert eng._padded_batch(40, mesh=mesh) == 32
            # no mesh: legacy behavior
            assert eng._padded_batch(5) == 8
        finally:
            eng.shutdown()

    def test_scheduler_budgets_scale_by_dp(self):
        eng = make_engine(mesh={"enabled": True, "dp": 4}, token=False)
        try:
            b = eng.batcher
            assert b.dp_degree == 4
            assert b._row_budget() == 4 * eng.cfg.max_batch_size
            assert b._item_budget() == 4 * 2 * eng.cfg.max_batch_size
            eng.configure_mesh({"enabled": False})
            assert b.dp_degree == 1
        finally:
            eng.shutdown()

    def test_plan_take_row_trim_respects_alignment(self):
        from semantic_router_tpu.engine.packing import plan_take

        # 6 full rows under backlog: the pow2 trim would cut to 4;
        # with row_align=8 the trim is skipped (padding would re-grow
        # the shape to 8 rows anyway)
        lengths = [32] * 6
        take, _ = plan_take(lengths, 32, max_rows=8,
                            max_segments_per_row=4, max_items=6,
                            backlog_beyond=True, row_align=8)
        assert len(take) == 6
        take, _ = plan_take(lengths, 32, max_rows=8,
                            max_segments_per_row=4, max_items=6,
                            backlog_beyond=True, row_align=1)
        assert len(take) == 4
        # non-power-of-two dp: no count ≤ 6 both pow2 and 3-aligned
        # pads to itself, so the take stays whole (a trim to 4 would
        # pad back up to 6 with 2 all-padding rows)
        take, _ = plan_take(lengths, 32, max_rows=8,
                            max_segments_per_row=4, max_items=6,
                            backlog_beyond=True, row_align=3)
        assert len(take) == 6
        # 12 full rows, dp=8: 8 is pow2 AND 8-aligned — trim engages
        take, _ = plan_take([32] * 12, 32, max_rows=16,
                            max_segments_per_row=4, max_items=12,
                            backlog_beyond=True, row_align=8)
        assert len(take) == 8

    def test_census_parser_handles_mesh_suffix(self):
        from semantic_router_tpu.engine.classify import InferenceEngine

        rows = InferenceEngine._parse_census_keys([
            ("trunk:g0", "packed:seq:4:p8:m8x1x1", 8, 128),
            ("trunk:g0", "packed:tok:2:m4x2x1", 4, 32),
            ("trunk:g0", "packed:both:2", 2, 32),
            ("trunk:g0", "fused:seq", 2, 32),
        ])
        assert (128, 4, 8, "seq", 8) in rows
        assert (32, 2, 4, "tok", 0) in rows
        assert (32, 2, 2, "both", 0) in rows
        assert len(rows) == 3


class TestMeshWiring:
    def test_apply_mesh_knobs_boot_and_reload(self):
        from semantic_router_tpu.runtime.bootstrap import (
            apply_mesh_knobs,
        )

        eng = make_engine(token=False)
        try:
            on = RouterConfig.from_dict({"engine": {"mesh": {
                "enabled": True, "dp": 4}}})
            apply_mesh_knobs(on, eng)
            assert eng._serving_mesh is not None
            assert eng.batcher.dp_degree == 4
            # hot reload flips it back off — no restart needed
            off = RouterConfig.from_dict({"engine": {"mesh": {
                "enabled": False}}})
            apply_mesh_knobs(off, eng)
            assert eng._serving_mesh is None
            # malformed config must never raise out of bootstrap
            bad = RouterConfig.from_dict({"engine": {"mesh": {
                "enabled": True, "tp": 4096}}})
            apply_mesh_knobs(bad, eng)
        finally:
            eng.shutdown()

    def test_malformed_mesh_never_stops_boot(self):
        """A bad engine.mesh block at CONSTRUCTION fails open to
        single-device serving (warning event), matching the hot-reload
        contract — boot and reload must treat the same config the same
        way."""
        eng = make_engine(mesh={"enabled": True, "tp": 4096},
                          token=False)
        try:
            assert eng._serving_mesh is None
            res = eng.classify_batch("intent", MIXED_TEXTS[:3])
            assert len(res) == 3
        finally:
            eng.shutdown()

    def test_mesh_report_shape(self):
        eng = make_engine(mesh={"enabled": True, "dp": 4, "tp": 2},
                          token=False)
        try:
            rep = eng.mesh_report()
            assert rep["enabled"] is True
            assert rep["source"] == "engine.mesh"
            assert rep["axes"] == {"dp": 4, "tp": 2, "sp": 1}
            assert rep["mesh_devices"] == 8
            assert rep["visible_devices"] >= 8
            assert all(v["sharded"] for v in rep["groups"].values())
            import json

            json.dumps(rep)  # /debug/runtime serves this verbatim
        finally:
            eng.shutdown()

    def test_mesh_devices_gauge_set_on_flip(self):
        m = MetricSeries(MetricsRegistry())
        eng = make_engine(token=False, metrics=m)
        try:
            eng.configure_mesh({"enabled": True, "dp": 4, "tp": 2})
            assert m.mesh_devices.get(axis="dp") == 4.0
            assert m.mesh_devices.get(axis="tp") == 2.0
            eng.configure_mesh({"enabled": False})
            assert m.mesh_devices.get(axis="dp") == 0.0
        finally:
            eng.shutdown()

    def test_head_bank_actually_sharded_on_task_axis(self):
        """tp shards the stacked bank on the TASK axis when the member
        count divides evenly — the PR 1 head_bank_specs follow-on,
        measured on the CPU mesh (the four-chip path is
        ``chip_smoke.py --multichip``)."""
        eng = make_engine(mesh={"enabled": True, "dp": 4, "tp": 2},
                          token=False, max_batch=4)
        try:
            (g,) = eng._groups_by_gid.values()
            # 3 seq members does not divide tp=2 → replicated; widths
            # prove the bank stacked; the trunk kernels DO tp-shard
            import flax.traverse_util as tu

            flat = tu.flatten_dict(g.fns["trunk_params"], sep="/")
            qkv = [v for k, v in flat.items()
                   if "Wqkv" in k and k.endswith("kernel")]
            assert qkv and tuple(qkv[0].sharding.spec) == (None, "tp")
        finally:
            eng.shutdown()
