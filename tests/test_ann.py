"""On-device ANN plane coverage (ISSUE 20 tentpole) — the
``make ann-smoke`` tier-1 gate.

What this file proves, on the forced 8-device CPU mesh (conftest):

- device top-k parity against the numpy brute-force reference, and the
  host-tier scan against the same oracle;
- sharded (dp=4 x tp=2) top-k equal to single-device — the same slot
  indices, float scores within 1e-6 (the embedding axis stays unsharded,
  so every score's reduction is local to one device, but a sharded
  matmul may tile that reduction differently);
- quantized banks (int8/bf16) clear the calibrated recall@10 gate at
  >= 0.99, and a bank whose geometry quantizes badly falls back to f32
  and stamps it — never silently serves bad recall;
- promotion / eviction / tombstone-compaction tiering;
- hot capacity/quant flips under concurrent lookups lose zero lookups;
- the SharedSemanticCache handoff: exact sha256 hits bypass the bank,
  the in-proc mirror gates OFF while ANN owns similarity
  (similarity_owner()), and detach restores it;
- stateplane version-gated sync convergence + fail-open local-only;
- bootstrap's apply_ann_knobs boot/reload/detach cycle
  (ann.enabled: false constructs nothing);
- vectorstore backend="ann" ingest/search/delete + the no-plane
  fallback.

Every test closes its AnnPlane / searchers: the VSR_ANALYZE
thread-leak gate fails the session on a leaked "ann-maintain" or
"*-lookup" thread.
"""

import threading
import time
import types

import numpy as np
import pytest

from semantic_router_tpu.ann import (
    AnnIndex,
    AnnPlane,
    DeviceBank,
    HostTier,
    TierPolicy,
    TopKPrograms,
    cache_index_sync,
    measure_recall,
    normalize_ann,
    normalize_rows,
    tier_for,
)
from semantic_router_tpu.ann import bank as bank_mod
from semantic_router_tpu.observability.metrics import MetricsRegistry
from semantic_router_tpu.stateplane import (
    GuardedBackend,
    InMemoryStateBackend,
    SharedSemanticCache,
    StateBackendUnavailable,
    StatePlane,
)
from semantic_router_tpu.stateplane.harness import hash_embed

DIM = 32


def _knobs(**over):
    d = {"enabled": True}
    d.update(over)
    return normalize_ann(d)


def _corpus(n, dim=DIM, seed=7):
    rng = np.random.default_rng(seed)
    return normalize_rows(rng.standard_normal((n, dim)))


def _ref_topk(matrix, ids, query, k):
    """Numpy brute-force oracle: cosine top-k ids over ``matrix``."""
    q = normalize_rows(query)[0]
    scores = matrix @ q
    order = np.argsort(-scores)[:k]
    return [ids[i] for i in order], [float(scores[i]) for i in order]


class TestKnobs:
    def test_defaults_are_off_and_closed(self):
        k = normalize_ann(None)
        assert k["enabled"] is False
        assert k["quant"] == "f32"
        assert k["min_capacity"] == 1024
        assert k["max_capacity"] == 1 << 20
        assert k["recall_floor"] == 0.99
        assert k["top_k"] == 8
        assert k["batch"]["enabled"] is False
        assert k["mesh"]["enabled"] is False
        assert k["share"] == {"cache": True, "vectorstore": True}

    def test_pow2_ceil_and_clamps(self):
        k = normalize_ann({"min_capacity": 1000, "max_capacity": 3000,
                           "quant": "Int8", "recall_floor": 2.0,
                           "evict_watermark": 0.0})
        assert k["min_capacity"] == 1024
        assert k["max_capacity"] == 4096
        assert k["quant"] == "int8"
        assert k["recall_floor"] == 1.0
        assert k["evict_watermark"] == 0.1
        # garbage quant falls back to the f32 oracle mode
        assert normalize_ann({"quant": "fp4"})["quant"] == "f32"
        # max below min snaps up (a bank needs at least one tier)
        k = normalize_ann({"min_capacity": 2048, "max_capacity": 512})
        assert k["max_capacity"] == k["min_capacity"] == 2048

    def test_tier_ladder(self):
        assert tier_for(0, 16, 1024) == 16
        assert tier_for(1, 1024, 1 << 20) == 1024
        assert tier_for(1500, 1024, 1 << 20) == 2048
        assert tier_for(5000, 16, 1024) == 1024  # clamped at max
        assert tier_for(1 << 20, 1024, 1 << 20) == 1 << 20


class TestDeviceBank:
    def test_add_overwrite_delete_compact(self):
        bank = DeviceBank(min_capacity=16, max_capacity=64)
        vecs = _corpus(8)
        for i in range(8):
            assert bank.add(f"e{i}", vecs[i])
        assert len(bank) == 8
        bank.add("e3", vecs[0])  # overwrite, not duplicate
        assert len(bank) == 8
        assert bank.delete("e5")
        assert not bank.delete("e5")
        assert "e5" not in bank
        assert bank.tombstone_ratio() == pytest.approx(1 / 8)
        assert bank.compact() == 1
        assert bank.tombstone_ratio() == 0.0
        assert sorted(bank.entry_ids()) == sorted(
            f"e{i}" for i in range(8) if i != 5)

    def test_extend_bulk_capacity_capped(self):
        bank = DeviceBank(min_capacity=16, max_capacity=16)
        vecs = _corpus(20)
        fresh = bank.extend([f"x{i}" for i in range(20)], vecs)
        assert fresh == 16  # overflow stays with the caller (host tier)
        assert len(bank) == 16
        # resident ids overwrite without consuming capacity
        assert bank.extend(["x0", "x1"], vecs[:2]) == 0
        assert len(bank) == 16

    def test_dim_mismatch_raises(self):
        bank = DeviceBank(min_capacity=16)
        bank.add("a", np.ones(8, np.float32))
        with pytest.raises(ValueError):
            bank.add("b", np.ones(16, np.float32))

    def test_publish_survives_tombstone_overflow_at_max_tier(self):
        """Delete + add churn at the max tier: add() caps LIVE entries
        but tombstoned slots keep counting, so allocated slots can
        exceed every capacity tier — publish() must reclaim and serve
        all live entries, not crash on the padded broadcast."""
        bank = DeviceBank(min_capacity=16, max_capacity=32)
        vecs = _corpus(36, seed=73)
        ids = [f"o{i}" for i in range(36)]
        bank.extend(ids[:32], vecs[:32])
        bank.publish()
        for eid in ("o1", "o2", "o3", "o4"):
            bank.delete(eid)  # below the 0.25 compaction ratio
        for i in range(32, 36):
            assert bank.add(ids[i], vecs[i])  # 36 allocated > tier 32
        view = bank.publish()
        assert view is not None
        assert view.tier == 32
        assert view.n_valid == 32
        assert len(bank) == 32
        # the churned-in entries are findable on the fresh view
        programs = TopKPrograms()
        _scores, idx = programs.run(view, vecs[35:36], k=1)
        assert view.ids[idx[0][0]] == "o35"


class TestLookupParity:
    """Device program and host scan against the numpy oracle."""

    def test_device_topk_matches_reference(self):
        vecs = _corpus(100)
        ids = [f"d{i}" for i in range(100)]
        bank = DeviceBank(min_capacity=128, max_capacity=1024)
        bank.extend(ids, vecs)
        view = bank.publish()
        assert view.tier == 128 and view.mode == "f32"
        programs = TopKPrograms()
        queries = _corpus(5, seed=11)
        scores, idx = programs.run(view, queries, k=8)
        for qi in range(5):
            ref_ids, ref_scores = _ref_topk(vecs, ids, queries[qi], 8)
            got_ids = [view.ids[s] for s in idx[qi]]
            assert got_ids == ref_ids
            assert np.allclose(scores[qi], ref_scores, atol=1e-5)

    def test_host_scan_matches_reference(self):
        vecs = _corpus(50, seed=3)
        ids = [f"h{i}" for i in range(50)]
        host = HostTier()
        host.extend(ids, vecs)
        q = _corpus(1, seed=13)[0]
        got_ids, got_scores = host.scan(q, 8)
        ref_ids, ref_scores = _ref_topk(vecs, ids, q, 8)
        assert got_ids == ref_ids
        assert np.allclose(got_scores, ref_scores, atol=1e-6)

    def test_index_merges_device_and_host(self):
        idx = AnnIndex("merge", _knobs(min_capacity=16), TopKPrograms())
        try:
            vecs = _corpus(12, seed=5)
            # 8 promoted to the device bank, 4 left on host — and one id
            # resident on BOTH tiers must dedupe to its best score
            for i in range(8):
                idx.bank.add(f"m{i}", vecs[i])
            idx.bank.publish()
            for i in range(8, 12):
                idx.host.add(f"m{i}", vecs[i])
            idx.host.add("m0", vecs[0])
            ids, scores = idx.lookup(vecs[10], k=12)
            assert ids.count("m0") == 1
            assert ids[0] == "m10"  # the exact row wins
            assert scores[0] == pytest.approx(1.0, abs=1e-5)
            ref_ids, _ = _ref_topk(vecs, [f"m{i}" for i in range(12)],
                                   vecs[10], 12)
            assert set(ids) == set(ref_ids)
            # deleted ids filter out of the merge immediately
            idx.delete("m10")
            ids, _ = idx.lookup(vecs[10], k=12)
            assert "m10" not in ids
        finally:
            idx.close()

    def test_lookup_before_any_publish_serves_host(self):
        idx = AnnIndex("fresh", _knobs(), TopKPrograms())
        try:
            vecs = _corpus(3, seed=17)
            for i in range(3):
                idx.add(f"f{i}", vecs[i])  # host tier, no view yet
            ids, scores = idx.lookup(vecs[1], k=2)
            assert ids[0] == "f1"
            assert scores[0] == pytest.approx(1.0, abs=1e-5)
        finally:
            idx.close()


class TestShardedBitIdentical:
    """dp=4 x tp=2 over the forced 8-device CPU platform: row-sharding
    the bank must not change a single bit of the result."""

    def test_sharded_topk_bit_identical_to_single_device(self):
        from semantic_router_tpu.engine.mesh import (
            build_serving_mesh,
            normalize_mesh,
        )

        mesh = build_serving_mesh(
            normalize_mesh({"enabled": True, "dp": 4, "tp": 2}))
        assert mesh is not None, "conftest forces 8 CPU devices"
        vecs = _corpus(128, seed=23)
        ids = [f"s{i}" for i in range(128)]

        def build(m):
            bank = DeviceBank(min_capacity=128, max_capacity=1024,
                              mesh=m)
            bank.extend(ids, vecs)
            return bank.publish()

        v_single, v_sharded = build(None), build(mesh)
        assert v_sharded.mesh_sig == (4, 2, 1)
        assert v_sharded.tier % 8 == 0  # evenly divisible → sharded
        programs = TopKPrograms()
        queries = _corpus(8, seed=29)
        s1, i1 = programs.run(v_single, queries, k=8)
        s2, i2 = programs.run(v_sharded, queries, k=8)
        assert np.array_equal(i1, i2)
        # scores to a tolerance, not bit for bit: D stays unsharded so
        # each score's f32 reduction is local to one device, but a
        # sharded matmul may tile that reduction differently from the
        # single-device program (it does on the CPU backend: last-bit
        # differences).  1e-6 is ~8 ulp of a cosine score in [-1, 1].
        np.testing.assert_allclose(s1, s2, rtol=0, atol=1e-6)

    def test_uneven_tier_replicates_instead_of_erroring(self):
        from semantic_router_tpu.engine.mesh import (
            build_serving_mesh,
            normalize_mesh,
        )

        mesh = build_serving_mesh(
            normalize_mesh({"enabled": True, "dp": 4, "tp": 2}))
        placements = DeviceBank._placements(mesh, tier=20, dim=DIM)
        spec = placements["bank_t"].spec
        assert tuple(spec) == (None, None)  # replicated, not an error


class TestRecallGate:
    def test_quantized_recall_clears_floor(self):
        corpus = _corpus(128, seed=31)
        assert measure_recall(corpus, "int8") >= 0.99
        assert measure_recall(corpus, "bf16") >= 0.99
        assert measure_recall(corpus, "f32") == 1.0
        assert measure_recall(np.zeros((0, DIM), np.float32),
                              "int8") == 1.0

    def test_int8_view_publishes_with_stamped_recall(self):
        bank = DeviceBank(min_capacity=128, max_capacity=1024,
                          mode="int8")
        vecs = _corpus(128, seed=31)
        bank.extend([f"q{i}" for i in range(128)], vecs)
        view = bank.publish()
        assert view.mode == "int8"
        assert view.recall >= 0.99
        assert view.quant_fallback is False
        assert view.qbank is not None and view.bank_t is None
        rep = bank.report()
        assert rep["view_mode"] == "int8"
        assert rep["quant_fallback"] is False
        # the quantized device path still finds the right neighbors
        programs = TopKPrograms()
        rng = np.random.default_rng(37)
        probe = normalize_rows(vecs[5] + 0.05 * rng.standard_normal(DIM))
        scores, idx = programs.run(view, probe, k=8)
        assert view.ids[idx[0][0]] == "q5"

    def test_bad_geometry_falls_back_to_f32_and_stamps(self, monkeypatch):
        monkeypatch.setattr(bank_mod, "measure_recall",
                            lambda *a, **k: 0.5)
        bank = DeviceBank(min_capacity=16, mode="int8",
                          recall_floor=0.99)
        bank.extend([f"b{i}" for i in range(8)], _corpus(8))
        view = bank.publish()
        assert view.mode == "f32"  # gate refused the quantized view
        assert view.quant_fallback is True
        assert bank.report()["quant_fallback"] is True
        # the bank keeps ASKING for int8: a later republish under a
        # friendlier geometry may clear the gate
        assert bank.mode == "int8"


class TestTiering:
    def test_promotion_hottest_first_with_floor(self):
        bank = DeviceBank(min_capacity=16, max_capacity=64)
        host = HostTier()
        policy = TierPolicy(bank, host, promote_ewma=1.0,
                            promote_min_hits=0.5)
        vecs = _corpus(3, seed=41)
        for i, eid in enumerate(("cold", "warm", "hot")):
            host.add(eid, vecs[i])
        policy.mark_hits(["hot", "hot", "warm"])
        counts = policy.run_cycle()
        assert counts["promoted"] == 2
        assert "hot" in bank and "warm" in bank
        assert "cold" in host and "cold" not in bank
        assert counts["published"] == 1

    def test_promoted_entry_stays_findable_through_the_publish(self,
                                                             monkeypatch):
        """The cause of test_ingest_search_delete_through_ann's empty
        hits: a promoted entry left the host tier BEFORE the bank
        published, so a lookup during publish() found it nowhere."""
        bank = DeviceBank(min_capacity=16, max_capacity=64)
        host = HostTier()
        policy = TierPolicy(bank, host, promote_min_hits=0.0)
        host.add("hot", _corpus(1, seed=47)[0])
        policy.mark_hits(["hot"])
        seen = []
        publish = bank.publish

        def publishing():
            view = bank.view()
            seen.append("hot" in host
                        or (view is not None and "hot" in view.ids))
            return publish()

        monkeypatch.setattr(bank, "publish", publishing)
        assert policy.run_cycle()["promoted"] == 1
        assert seen == [True]
        assert "hot" in bank and "hot" not in host

    def test_eviction_past_watermark_at_max_tier(self):
        bank = DeviceBank(min_capacity=16, max_capacity=16)
        host = HostTier()
        policy = TierPolicy(bank, host, promote_min_hits=0.0,
                            evict_watermark=0.5)
        vecs = _corpus(12, seed=43)
        ids = [f"t{i}" for i in range(12)]
        host.extend(ids, vecs)
        policy.mark_hits(ids)
        counts = policy.run_cycle()
        assert counts["promoted"] == 12
        assert counts["evicted"] == 4  # back down to the 0.5*16 mark
        assert len(bank) == 8 and len(host) == 4
        # every entry is still findable somewhere
        assert sorted(bank.entry_ids() + host.ids()) == sorted(ids)

    def test_tombstones_trigger_compaction(self):
        bank = DeviceBank(min_capacity=16, max_capacity=64)
        host = HostTier()
        policy = TierPolicy(bank, host, tombstone_ratio=0.25)
        vecs = _corpus(8, seed=47)
        bank.extend([f"c{i}" for i in range(8)], vecs)
        bank.publish()
        for eid in ("c1", "c4", "c6"):
            bank.delete(eid)
        counts = policy.run_cycle()
        assert counts["compacted"] == 3
        assert counts["published"] == 1
        assert bank.view().n_valid == 5

    def test_run_cycle_forces_compaction_on_slot_overflow(self):
        """Allocated slots past the max tier compact even below the
        tombstone ratio, so the maintenance publish never has to
        reclaim inline."""
        bank = DeviceBank(min_capacity=16, max_capacity=32)
        host = HostTier()
        policy = TierPolicy(bank, host, tombstone_ratio=0.9,
                            evict_watermark=2.0)
        vecs = _corpus(34, seed=79)
        bank.extend([f"ov{i}" for i in range(32)], vecs[:32])
        bank.publish()
        bank.delete("ov0")
        bank.delete("ov1")  # 2/34 tombstones — far below the 0.9 ratio
        for i in range(32, 34):
            assert bank.add(f"ov{i}", vecs[i])
        assert bank.used_slots() == 34
        counts = policy.run_cycle()
        assert counts["compacted"] == 2
        assert counts["published"] == 1
        assert bank.used_slots() == 32
        assert bank.view().tier == 32
        assert bank.view().n_valid == 32

    def test_index_retires_deleted_markers_after_compaction(self):
        idx = AnnIndex("retire", _knobs(min_capacity=16,
                                        tombstone_ratio=0.01),
                       TopKPrograms())
        try:
            vecs = _corpus(4, seed=53)
            for i in range(4):
                idx.add(f"r{i}", vecs[i])
            idx.flush()  # promote + publish
            assert len(idx.bank) == 4
            idx.delete("r2")
            assert idx.report()["deleted_pending"] == 1
            idx.maintain()  # compaction rewrites, marker retires
            assert idx.report()["deleted_pending"] == 0
            ids, _ = idx.lookup(vecs[2], k=4)
            assert "r2" not in ids
        finally:
            idx.close()


class TestBatchingAndHotFlips:
    def test_batched_lookups_match_direct(self):
        vecs = _corpus(40, seed=59)
        ids = [f"q{i}" for i in range(40)]

        def build(batch_enabled):
            idx = AnnIndex(
                "bt" + ("1" if batch_enabled else "0"),
                _knobs(min_capacity=64,
                       batch={"enabled": batch_enabled, "max_batch": 8,
                              "max_wait_ms": 0.5}),
                TopKPrograms())
            idx.bank.extend(ids, vecs)
            idx.bank.publish()
            return idx

        direct, batched = build(False), build(True)
        try:
            queries = _corpus(6, seed=61)
            for q in queries:
                want = direct.lookup(q, k=8)
                got = batched.lookup(q, k=8)
                assert got[0] == want[0]
                assert np.allclose(got[1], want[1], atol=1e-5)
        finally:
            direct.close()
            batched.close()  # joins the "<name>-lookup" batcher thread

    def test_dead_batcher_degrades_to_cache_miss(self):
        """A stalled/dead dispatch worker must cost a missed device
        lookup, not an error up the cache-probe path — and the merged
        index lookup still answers from the host tier."""
        idx = AnnIndex(
            "dead", _knobs(min_capacity=16,
                           batch={"enabled": True, "max_batch": 8,
                                  "max_wait_ms": 0.5}),
            TopKPrograms())
        try:
            vecs = _corpus(4, seed=83)
            idx.bank.extend([f"db{i}" for i in range(3)], vecs[:3])
            idx.bank.publish()
            idx.host.add("db3", vecs[3])

            class _DeadFuture:
                def result(self, timeout=None):
                    raise TimeoutError("dispatch worker stalled")

            idx.searcher._batcher.submit = \
                lambda *a, **k: _DeadFuture()
            assert idx.searcher.search(vecs[0], 2) == ([], [])
            ids, scores = idx.lookup(vecs[3], k=2)
            assert ids[0] == "db3"  # host scan still serves
            assert scores[0] == pytest.approx(1.0, abs=1e-5)
        finally:
            idx.close()

    def test_hot_flips_lose_zero_lookups(self):
        """Capacity + quant flips republish the view atomically while
        concurrent lookups keep serving their snapshot — every lookup
        completes with results, none errors."""
        reg = MetricsRegistry()
        plane = AnnPlane(reg)
        plane.configure(_knobs(min_capacity=256, compact_interval_s=60))
        idx = plane.index("hot")
        vecs = _corpus(200, seed=67)
        for i in range(200):
            idx.add(f"hf{i}", vecs[i])
        idx.flush()
        assert len(idx.bank) == 200
        failures, served = [], []
        stop = threading.Event()

        def prober(seed):
            rng = np.random.default_rng(seed)
            while not stop.is_set():
                q = vecs[int(rng.integers(0, 200))]
                try:
                    ids, scores = idx.lookup(q, k=4)
                    assert ids and scores[0] > 0.98
                    served.append(1)
                except Exception as exc:  # pragma: no cover
                    failures.append(exc)
        threads = [threading.Thread(target=prober, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        try:
            flips = (
                {"quant": "int8", "min_capacity": 256},
                {"quant": "f32", "min_capacity": 512},
                {"quant": "bf16", "min_capacity": 256,
                 "mesh": {"enabled": True, "dp": 4, "tp": 2}},
                {"quant": "f32", "min_capacity": 256},
            )
            for flip in flips:
                plane.configure(_knobs(compact_interval_s=60, **flip))
                time.sleep(0.05)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert not failures
        assert len(served) > 20
        assert plane.report()["indexes"]["hot"]["entries"] == 200
        plane.close()


def _counting_embed():
    base = hash_embed(DIM)
    calls = {"n": 0}

    def embed(text):
        calls["n"] += 1
        return base(text)
    return embed, calls


class TestCacheHandoff:
    """SharedSemanticCache + ANN: one similarity owner at a time."""

    def _cache(self, ns):
        plane = StatePlane(GuardedBackend(InMemoryStateBackend()),
                           replica_id="ann-t", namespace=ns)
        embed, calls = _counting_embed()
        cache = SharedSemanticCache(plane, embed,
                                    similarity_threshold=0.6)
        return plane, cache, calls

    def test_exact_sha256_hit_bypasses_bank_and_embedder(self):
        plane, cache, calls = self._cache("annx")
        idx = AnnIndex("cache", _knobs(), TopKPrograms())
        try:
            cache.attach_ann(idx)
            cache.add("what is the capital of france", "paris",
                      model="m")
            n_after_add = calls["n"]  # add embeds exactly once
            hit = cache.find_similar("what is the capital of france")
            assert hit is not None and hit.response == "paris"
            assert calls["n"] == n_after_add  # no embedding forward
            assert cache.stats().exact_hits == 1
        finally:
            idx.close()
            plane.close()

    def test_mirror_gates_off_while_ann_owns_similarity(self):
        plane, cache, _ = self._cache("anng")
        idx = AnnIndex("cache", _knobs(), TopKPrograms())
        try:
            cache.add("how long is a marathon race", "42km")
            cache.add("what does this contract clause mean", "intent")
            assert cache.similarity_owner() == "mirror"
            assert cache._matrix is not None
            cache.attach_ann(idx)  # seeds the index, empties the mirror
            assert cache.similarity_owner() == "ann"
            assert cache._matrix is None
            assert len(idx) == 2
            cache.add("is this liability clause enforceable", "maybe")
            assert len(idx) == 3
            assert cache._matrix is None  # mirror stays gated
            assert cache.stats().entries == 3
            # similarity now routes through the index (near-duplicate
            # query, exact path misses on the sha256 key)
            hit = cache.find_similar(
                "what does this contract clause mean?")
            assert hit is not None and hit.response == "intent"
            cache.detach_ann()
            assert cache.similarity_owner() == "mirror"
            assert cache._matrix is not None  # resynced off the plane
            assert cache._matrix.shape[0] == 3
            hit = cache.find_similar(
                "what does this contract clause mean?")
            assert hit is not None and hit.response == "intent"
        finally:
            idx.close()
            plane.close()

    def test_expired_plane_row_retires_from_index(self):
        plane, cache, _ = self._cache("anne")
        idx = AnnIndex("cache", _knobs(), TopKPrograms())
        try:
            cache.attach_ann(idx)
            cache.add("a question that will expire", "stale")
            assert len(idx) == 1
            # the row vanishes server-side (TTL/flush by a sibling):
            # the store wins — the candidate retires from the index
            prefix = plane.key("cache", "entry", "")
            for k in plane.backend.scan(prefix):
                plane.backend.delete(k)
            assert cache.find_similar(
                "a question that will expire!") is None
            assert len(idx) == 0
        finally:
            idx.close()
            plane.close()

    def test_device_path_failure_degrades_like_plane_failure(self):
        """A JAX/device blow-up inside the ANN lookup (hot mesh/quant
        flip mid-step) must degrade to a miss, exactly like a plane
        failure — never propagate out of find_similar."""
        plane, cache, _ = self._cache("annd")
        idx = AnnIndex("cache", _knobs(), TopKPrograms())
        try:
            cache.attach_ann(idx)
            cache.add("a query the device path will drop", "served")
            errors_before = cache.stats().errors

            def boom(*_a, **_k):
                raise RuntimeError("XlaRuntimeError: device lost")

            idx.lookup = boom
            # near-duplicate query: exact sha256 path misses, the ANN
            # path raises, and the probe degrades to a miss
            hit = cache.find_similar(
                "a query the device path will drop!!")
            assert hit is None
            assert cache.stats().errors == errors_before + 1
            # exact hits never touch the bank and keep serving
            hit = cache.find_similar(
                "a query the device path will drop")
            assert hit is not None and hit.response == "served"
        finally:
            idx.close()
            plane.close()

    def test_invalidate_and_clear_reach_the_index(self):
        plane, cache, _ = self._cache("anni")
        idx = AnnIndex("cache", _knobs(), TopKPrograms())
        try:
            cache.attach_ann(idx)
            cache.add("query one about routing", "r1")
            cache.add("query two about caching", "r2")
            assert len(idx) == 2
            cache.invalidate("query one about routing")
            assert len(idx) == 1
            cache.clear()
            assert len(idx) == 0
        finally:
            idx.close()
            plane.close()


class TestStateplaneSync:
    def test_version_gated_convergence_and_deletion(self):
        be = InMemoryStateBackend()
        pa = StatePlane(GuardedBackend(be), replica_id="sy-a",
                        namespace="syn1")
        pb = StatePlane(GuardedBackend(be), replica_id="sy-b",
                        namespace="syn1")
        ca = SharedSemanticCache(pa, hash_embed(DIM))
        idx = AnnIndex("cache", _knobs(), TopKPrograms())
        try:
            sync = cache_index_sync(pb, idx, interval_s=0.05)
            for q, r in (("alpha question", "a"), ("bravo question", "b"),
                         ("charlie question", "c")):
                ca.add(q, r)
            assert sync.due()
            assert sync.sync_once() is True
            assert len(idx) == 3
            # no sibling writes since → the version gate short-circuits
            assert sync.sync_once() is False
            assert sync.report()["syncs"] == 1
            ca.invalidate("bravo question")
            assert sync.sync_once() is True
            assert len(idx) == 2
            assert sync.report()["local_only"] is False
        finally:
            idx.close()
            pa.close()
            pb.close()

    def test_rebind_unregisters_superseded_recovery_hook(self):
        """Hot-reload churn rebinding the cache sync between planes
        must not accumulate recovery callbacks (each one pins a
        superseded sync object alive and refires on every recovery)."""
        reg = MetricsRegistry()
        annplane = AnnPlane(reg)
        annplane.configure(_knobs(compact_interval_s=60))
        be_a = GuardedBackend(InMemoryStateBackend())
        be_b = GuardedBackend(InMemoryStateBackend())
        pa = StatePlane(be_a, replica_id="rb-a", namespace="rb1")
        pb = StatePlane(be_b, replica_id="rb-b", namespace="rb2")
        n_a0, n_b0 = len(be_a._recover_cbs), len(be_b._recover_cbs)
        try:
            idx = annplane.bind_cache_sync(pa)
            first = idx.sync
            assert len(be_a._recover_cbs) == n_a0 + 1
            for _ in range(5):
                annplane.bind_cache_sync(pb)
                annplane.bind_cache_sync(pa)
            assert idx.sync is not first
            # exactly ONE live hook on the bound plane, zero leftovers
            # on the other — not 11 accumulated callbacks
            assert len(be_a._recover_cbs) == n_a0 + 1
            assert len(be_b._recover_cbs) == n_b0
        finally:
            annplane.close()  # index close unhooks the last sync
            assert len(be_a._recover_cbs) == n_a0
            pa.close()
            pb.close()

    def test_plane_death_fails_open_to_local_only(self):
        class _DeadBackend:
            def on_recover(self, fn):
                self.cb = fn

            def get(self, key):
                raise StateBackendUnavailable("dead")

        be = _DeadBackend()
        plane = types.SimpleNamespace(
            backend=be, key=lambda *p: ":".join(("srt",) + p))
        idx = AnnIndex("cache", _knobs(), TopKPrograms())
        try:
            idx.add("survivor", np.ones(DIM, np.float32))
            sync = cache_index_sync(plane, idx)
            assert sync.sync_once() is False
            assert sync.local_only is True
            # the index keeps answering from what it already holds
            ids, _ = idx.lookup(np.ones(DIM, np.float32), k=1)
            assert ids == ["survivor"]
            # the recovery hook forces a FULL resync next cycle
            be.cb()
            assert sync.report()["seen_ver"] == -1
        finally:
            idx.close()


class TestApplyAnnKnobs:
    """bootstrap.apply_ann_knobs: boot, hot reload, detach."""

    def _stack(self, ns):
        from semantic_router_tpu.runtime.registry import RuntimeRegistry
        from semantic_router_tpu.vectorstore.store import (
            VectorStoreManager,
        )

        registry = RuntimeRegistry.isolated()
        plane = StatePlane(GuardedBackend(InMemoryStateBackend()),
                           replica_id="ak", namespace=ns)
        cache = SharedSemanticCache(plane, hash_embed(DIM))
        vsm = VectorStoreManager(hash_embed(DIM), backend="ann")
        router = types.SimpleNamespace(cache=cache, vectorstores=vsm,
                                       stateplane=plane)
        return registry, plane, cache, vsm, router

    def test_disabled_constructs_nothing(self):
        from semantic_router_tpu.config.schema import RouterConfig
        from semantic_router_tpu.runtime.bootstrap import apply_ann_knobs

        registry, plane, cache, vsm, router = self._stack("ak0")
        try:
            cache.add("a preexisting entry", "kept")
            before = cache._matrix.copy()
            apply_ann_knobs(RouterConfig.from_dict({}), registry, router)
            assert registry.get("ann") is None
            assert cache.similarity_owner() == "mirror"
            assert np.array_equal(cache._matrix, before)
            assert vsm.ann is None
        finally:
            plane.close()

    def test_boot_reload_detach_cycle(self):
        from semantic_router_tpu.config.schema import RouterConfig
        from semantic_router_tpu.runtime.bootstrap import apply_ann_knobs

        registry, plane, cache, vsm, router = self._stack("ak1")
        cfg_on = RouterConfig.from_dict(
            {"ann": {"enabled": True, "quant": "int8",
                     "sync_interval_s": 0.1, "compact_interval_s": 60}})
        try:
            apply_ann_knobs(cfg_on, registry, router)
            ann = registry.get("ann")
            assert isinstance(ann, AnnPlane)
            assert cache.similarity_owner() == "ann"
            assert vsm.ann is ann
            idx = ann.index("cache")
            assert idx.sync is not None  # bound to the router's plane
            assert idx.sync.plane is plane
            assert ann.knobs["quant"] == "int8"
            # hot reload: same plane object, retuned in place
            apply_ann_knobs(RouterConfig.from_dict(
                {"ann": {"enabled": True, "quant": "f32",
                         "compact_interval_s": 60}}), registry, router)
            assert registry.get("ann") is ann
            assert ann.knobs["quant"] == "f32"
            # share.cache off while enabled: similarity returns to the
            # mirror but the plane stays up for vectorstores
            apply_ann_knobs(RouterConfig.from_dict(
                {"ann": {"enabled": True, "compact_interval_s": 60,
                         "share": {"cache": False}}}), registry, router)
            assert cache.similarity_owner() == "mirror"
            assert vsm.ann is ann
            # flip off: plane closes (thread joined), slot empties,
            # every consumer restored
            apply_ann_knobs(RouterConfig.from_dict({}), registry, router)
            assert registry.get("ann") is None
            assert cache.similarity_owner() == "mirror"
            assert vsm.ann is None
        finally:
            ann = registry.get("ann")
            if ann is not None:  # pragma: no cover — assert failed above
                ann.close()
            plane.close()

    def test_malformed_config_never_raises(self):
        from semantic_router_tpu.config.schema import RouterConfig
        from semantic_router_tpu.runtime.bootstrap import apply_ann_knobs

        registry, plane, cache, vsm, router = self._stack("ak2")
        try:
            cfg = RouterConfig.from_dict({"ann": {"enabled": True}})
            router_broken = types.SimpleNamespace(
                cache=cache, vectorstores=vsm, stateplane=object())
            apply_ann_knobs(cfg, registry, router_broken)  # must not raise
        finally:
            ann = registry.get("ann")
            if ann is not None:
                ann.close()
            plane.close()


class TestVectorStoreBackend:
    def test_ingest_search_delete_through_ann(self):
        from semantic_router_tpu.vectorstore.store import (
            VectorStoreManager,
        )

        reg = MetricsRegistry()
        plane = AnnPlane(reg)
        plane.configure(_knobs(compact_interval_s=60))
        vsm = VectorStoreManager(hash_embed(DIM), backend="ann",
                                 ann=plane)
        try:
            store = vsm.create("kb")
            from semantic_router_tpu.vectorstore.ann_store import (
                AnnVectorStore,
            )

            assert isinstance(store, AnnVectorStore)
            doc = store.ingest(
                "routing", "Semantic routing sends each query to the "
                "cheapest capable model. Cache hits skip the backend "
                "entirely. Embeddings drive the similarity match.")
            assert len(plane.index("vs:kb")) > 0
            hits = store.search("semantic routing query model", top_k=3)
            assert hits
            assert "routing" in hits[0].chunk.text.lower()
            assert store.delete_document(doc.id)
            assert len(plane.index("vs:kb")) == 0
        finally:
            plane.close()

    def test_missing_plane_falls_back_to_inmemory(self):
        from semantic_router_tpu.vectorstore.ann_store import (
            AnnVectorStore,
        )
        from semantic_router_tpu.vectorstore.store import (
            VectorStoreManager,
        )

        vsm = VectorStoreManager(hash_embed(DIM), backend="ann")
        store = vsm.create("orphan")  # no ann handle: warn + fall back
        assert not isinstance(store, AnnVectorStore)
        store.ingest("d", "some text to index without a device bank")
        assert store.search("text index", top_k=1)


class TestMetricsSurface:
    def test_lookup_paths_and_gauges_land_in_the_registry(self):
        reg = MetricsRegistry()
        plane = AnnPlane(reg)
        plane.configure(_knobs(min_capacity=16, compact_interval_s=60))
        idx = plane.index("m")
        try:
            vecs = _corpus(4, seed=71)
            for i in range(4):
                idx.add(f"mm{i}", vecs[i])
            idx.lookup(vecs[0], k=2)  # host path (no view yet)
            idx.flush()               # promote + publish
            idx.lookup(vecs[0], k=2)  # device path
            paths = {k[1][1] for k in
                     reg.counter("llm_ann_lookups_total").values()}
            assert {"host", "device"} <= paths
            fill = reg.gauge("llm_ann_bank_fill").values()
            assert fill[(("index", "m"),)] == pytest.approx(4 / 16)
            assert reg.gauge("llm_ann_local_fallback").values()[()] == 0.0
        finally:
            plane.close()

    def test_maintenance_failure_is_counted_not_swallowed(self):
        """A crashing index stamps llm_ann_maintenance_failures_total
        and does not starve the other indexes' maintenance."""
        reg = MetricsRegistry()
        plane = AnnPlane(reg)
        # keep the maintenance thread out of this test: cycles run
        # ONLY through the explicit maintain_once call below, so the
        # failure counter assertions are deterministic
        plane._closed = True
        plane.configure(_knobs(min_capacity=16, compact_interval_s=60))
        good, bad = plane.index("good"), plane.index("bad")
        try:
            good.add("g0", _corpus(1, seed=89)[0])

            def _boom():
                raise RuntimeError("compaction blew up")

            bad.maintain = _boom
            out = plane.maintain_once()  # must not raise
            assert out["bad"] == {"failed": 1}
            assert out["good"]["published"] == 1  # not starved
            vals = reg.counter(
                "llm_ann_maintenance_failures_total").values()
            assert vals[(("index", "bad"),)] == 1.0
        finally:
            plane.close()
