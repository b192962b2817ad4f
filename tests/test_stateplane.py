"""Unit coverage for the shared state plane (ISSUE 6 tentpole).

Backends (memory / RESP-over-MiniRedis / SQLite) against one contract
suite, the guarded circuit breaker, the consistent-hash ring, plane
membership + fleet pressure, the plane-shared cache / vector store /
decision mirror, and the config seam (enabled=false builds nothing)."""

import threading
import time

import numpy as np
import pytest

from semantic_router_tpu.config.schema import RouterConfig
from semantic_router_tpu.state.resp import MiniRedis
from semantic_router_tpu.stateplane import (
    GuardedBackend,
    HashRing,
    InMemoryStateBackend,
    RespStateBackend,
    SharedSemanticCache,
    SharedVectorStore,
    SQLiteStateBackend,
    StateBackendUnavailable,
    StatePlane,
    StatePlaneDecisionStore,
    build_backend,
    build_state_plane,
)
from semantic_router_tpu.stateplane.harness import hash_embed


@pytest.fixture(scope="module")
def mini():
    srv = MiniRedis().start()
    yield srv
    srv.stop()


def _backends(mini, tmp_path):
    return [
        InMemoryStateBackend(),
        RespStateBackend(port=mini.port),
        SQLiteStateBackend(str(tmp_path / "plane.db")),
    ]


class TestBackendContract:
    """One behavior suite, every backend — the seam's whole point."""

    def test_kv_hash_scan_incr_ttl(self, mini, tmp_path):
        for be in _backends(mini, tmp_path):
            ns = f"t:{type(be).__name__}"
            assert be.ping()
            be.put(f"{ns}:k1", b"v1")
            assert be.get(f"{ns}:k1") == b"v1"
            assert be.get(f"{ns}:absent") is None
            be.put_hash(f"{ns}:h1", {"a": b"1", "b": b"2"})
            assert be.get_hash(f"{ns}:h1") == {"a": b"1", "b": b"2"}
            assert be.get_hash(f"{ns}:absent") == {}
            be.put(f"{ns}:k2", b"v2")
            keys = be.scan(f"{ns}:k")
            assert keys == [f"{ns}:k1", f"{ns}:k2"]
            assert be.incr(f"{ns}:ctr") == 1
            assert be.incr(f"{ns}:ctr", 5) == 6
            assert be.delete(f"{ns}:k1") == 1
            assert be.get(f"{ns}:k1") is None
            # TTL expiry
            be.put(f"{ns}:ttl", b"x", ttl_s=0.05)
            assert be.get(f"{ns}:ttl") == b"x"
            time.sleep(0.2)
            assert be.get(f"{ns}:ttl") is None
            assert f"{ns}:ttl" not in be.scan(f"{ns}:ttl")

    def test_sqlite_shared_file_cross_handle(self, tmp_path):
        """Two handles over one file see each other's writes — the
        N-local-replicas posture."""
        path = str(tmp_path / "shared.db")
        a, b = SQLiteStateBackend(path), SQLiteStateBackend(path)
        a.put("x:k", b"from-a")
        assert b.get("x:k") == b"from-a"
        assert b.incr("x:ctr") == 1
        assert a.incr("x:ctr") == 2
        a.close(), b.close()

    def test_sqlite_incr_atomic_across_connections(self, tmp_path):
        """Version counters must never lose a bump: two handles (the
        two-processes-one-file posture) hammer one counter and every
        increment must land — BEGIN IMMEDIATE makes the read-modify-
        write atomic beyond this process's threading.Lock."""
        path = str(tmp_path / "ctr.db")
        a, b = SQLiteStateBackend(path), SQLiteStateBackend(path)
        n = 50

        def spin(be):
            for _ in range(n):
                be.incr("x:ctr")

        threads = [threading.Thread(target=spin, args=(be,))
                   for be in (a, b)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert a.incr("x:ctr") == 2 * n + 1
        a.close(), b.close()

    def test_build_backend_factory(self, tmp_path):
        g = build_backend({"backend": "memory"})
        assert isinstance(g, GuardedBackend)
        g = build_backend({"backend": "sqlite", "backend_config":
                           {"path": str(tmp_path / "f.db")}})
        g.put("k", b"v")
        assert g.get("k") == b"v"
        with pytest.raises(ValueError):
            build_backend({"backend": "zookeeper"})
        with pytest.raises(ValueError):
            build_backend({"backend": "sqlite"})  # no path


class TestGuardedBackend:
    def test_breaker_opens_fast_fails_and_recovers(self):
        class Flaky:
            def __init__(self):
                self.down = False
                self.data = {}

            def ping(self):
                if self.down:
                    raise OSError("dead")
                return True

            def put(self, key, value, ttl_s=None):
                if self.down:
                    raise OSError("dead")
                self.data[key] = value

            def get(self, key):
                if self.down:
                    raise OSError("dead")
                return self.data.get(key)

            def close(self):
                pass

        inner = Flaky()
        g = GuardedBackend(inner, cooldown_s=0.1)
        g.put("k", b"v")
        assert g.available
        inner.down = True
        with pytest.raises(StateBackendUnavailable):
            g.get("k")
        assert not g.available
        # breaker open: fails WITHOUT touching the inner backend
        calls_before = g.roundtrips
        with pytest.raises(StateBackendUnavailable):
            g.get("k")
        assert g.roundtrips == calls_before
        # recovery: cooldown elapses, one probe passes, callbacks fire
        fired = []
        g.on_recover(lambda: fired.append(1))
        inner.down = False
        time.sleep(0.15)
        assert g.get("k") == b"v"
        assert g.available
        deadline = time.time() + 2.0  # callbacks fire off-thread
        while time.time() < deadline and not fired:
            time.sleep(0.01)
        assert fired == [1]

    def test_error_report_surface(self):
        g = build_backend({"backend": "memory"})
        g.put("k", b"v")
        rep = g.report()
        assert rep["available"] and rep["roundtrips"] >= 1
        assert rep["backend"] == "InMemoryStateBackend"


class TestHashRing:
    def test_deterministic_and_balanced(self):
        ring = HashRing(["r0", "r1", "r2"], vnodes=64)
        assert ring.node_for("some-key") == ring.node_for("some-key")
        dist = ring.distribution(3000)
        assert set(dist) == {"r0", "r1", "r2"}
        for frac in dist.values():
            assert 0.15 < frac < 0.55  # rough balance, not perfection

    def test_minimal_reassignment_on_member_loss(self):
        members = [f"r{i}" for i in range(4)]
        ring = HashRing(members, vnodes=64)
        keys = [f"key:{i}" for i in range(800)]
        before = {k: ring.node_for(k) for k in keys}
        ring.rebuild(members[:-1])  # r3 dies
        moved = sum(1 for k in keys
                    if before[k] != ring.node_for(k) and before[k] != "r3")
        # only r3's share may move; surviving assignments stay put
        assert moved == 0

    def test_two_rings_agree_across_processes(self):
        a = HashRing(["x", "y", "z"])
        b = HashRing(["z", "y", "x"])  # order-independent
        for i in range(100):
            assert a.node_for(f"k{i}") == b.node_for(f"k{i}")


class TestPlaneMembership:
    def test_heartbeat_membership_and_ttl_expiry(self, mini):
        be = lambda: GuardedBackend(RespStateBackend(port=mini.port),
                                    cooldown_s=0.2)
        a = StatePlane(be(), replica_id="hb-a", namespace="m1",
                       heartbeat_s=0.1)
        b = StatePlane(be(), replica_id="hb-b", namespace="m1",
                       heartbeat_s=0.1)
        a.heartbeat_once()
        b.heartbeat_once()
        assert b.members() == ["hb-a", "hb-b"]
        a.heartbeat_once()
        assert a.members() == ["hb-a", "hb-b"]
        assert a.owner_of("k-123") == b.owner_of("k-123")
        # b stops beating: one TTL later it leaves a's ring
        deadline = time.time() + 5
        while time.time() < deadline and "hb-b" in a.members():
            time.sleep(0.1)
            a.heartbeat_once()
        assert a.members() == ["hb-a"]
        a.close(), b.close()

    def test_explicit_ttl_floored_at_two_beats(self, mini):
        # a TTL at or under the heartbeat would expire every member
        # between beats and flap the ring — explicit values get floored
        be = GuardedBackend(RespStateBackend(port=mini.port))
        assert StatePlane(be, replica_id="t1", heartbeat_s=2.0,
                          ttl_s=1.0).ttl_s == 4.0
        assert StatePlane(be, replica_id="t2", heartbeat_s=2.0,
                          ttl_s=10.0).ttl_s == 10.0
        be.close()

    def test_fleet_pressure_aggregation(self, mini):
        be = lambda: GuardedBackend(RespStateBackend(port=mini.port))
        a = StatePlane(be(), replica_id="fp-a", namespace="m2")
        b = StatePlane(be(), replica_id="fp-b", namespace="m2")
        a.publish_pressure({"firing": {"lat": "slow"}, "pending_items": 10,
                            "pool_saturation": 0.3, "level": 1})
        b.publish_pressure({"firing": {"lat": "fast", "err": "slow"},
                            "pending_items": 80, "pool_saturation": 0.1,
                            "level": 2})
        fleet = a.fleet_pressure()
        assert fleet["replicas"] == 2
        assert fleet["pending_items"] == 80.0
        assert fleet["pool_saturation"] == 0.3
        assert fleet["firing"] == {"lat": "fast", "err": "slow"}
        assert fleet["levels"] == {"fp-a": 1, "fp-b": 2}
        assert fleet["max_level"] == 2
        a.close(), b.close()

    def test_report_shape(self, mini):
        p = StatePlane(GuardedBackend(RespStateBackend(port=mini.port)),
                       replica_id="rep-a", namespace="m3")
        p.heartbeat_once()
        rep = p.report()
        assert rep["replica_id"] == "rep-a"
        assert rep["members"] == ["rep-a"]
        assert rep["backend"]["available"]
        assert abs(sum(rep["ring"]["distribution"].values()) - 1.0) < 0.01
        p.close()


class TestSharedCache:
    def _pair(self, mini, ns):
        embed = hash_embed()
        mk = lambda rid: StatePlane(
            GuardedBackend(RespStateBackend(port=mini.port),
                           cooldown_s=0.1),
            replica_id=rid, namespace=ns)
        a, b = mk("ca"), mk("cb")
        return (a, b, SharedSemanticCache(a, embed),
                SharedSemanticCache(b, embed), embed)

    def test_cross_replica_exact_and_similar(self, mini):
        a, b, ca, cb, _ = self._pair(mini, "c1")
        ca.add("what is contract law", "a legal answer", model="m-l")
        hit = cb.find_similar("what is contract law")
        assert hit is not None and hit.response == "a legal answer"
        assert hit.model == "m-l"
        assert cb.stats().exact_hits == 1
        # near-identical text similarity-hits through the mirror
        hit = cb.find_similar("what is contract law?",
                              threshold=0.85)
        assert hit is not None
        # rewrite dedupes on the query hash, never duplicates
        ca.add("what is contract law", "updated answer")
        assert cb.find_similar("what is contract law").response \
            == "updated answer"
        assert len(a.backend.scan(a.key("cache", "entry", ""))) == 1
        a.close(), b.close()

    def test_invalidate_and_clear_propagate(self, mini):
        a, b, ca, cb, _ = self._pair(mini, "c2")
        ca.add("q one", "r1")
        ca.add("q two", "r2")
        assert cb.find_similar("q one") is not None
        ca.invalidate("q one")
        assert cb.find_similar("q one", threshold=0.99) is None
        ca.clear()
        assert cb.find_similar("q two", threshold=0.99) is None
        a.close(), b.close()

    def test_category_scoping(self, mini):
        a, b, ca, cb, _ = self._pair(mini, "c3")
        ca.add("query in math", "math resp", category="math")
        assert cb.find_similar("query in math",
                               category="law") is None
        assert cb.find_similar("query in math",
                               category="math") is not None
        a.close(), b.close()

    def test_interleaved_writers_mirror_converges(self, mini):
        """Regression: a replica's OWN write must not mask sibling
        writes that landed since its last resync — when the version
        counter jumps by more than one, the mirror stays marked stale
        so the next lookup resyncs and picks up the sibling's entries
        (previously B adopted the counter and never similarity-served
        A's entry)."""
        a, b, ca, cb, _ = self._pair(mini, "c4")
        assert cb.find_similar("warm up the mirror") is None  # ver 0
        ca.add("what is contract law", "resp-from-a")         # ver 1
        cb.add("a completely different cooking query", "resp-b")  # 2
        hit = cb.find_similar("what is contract law?",
                              threshold=0.85)
        assert hit is not None and hit.response == "resp-from-a"
        a.close(), b.close()


class TestSharedVectorStore:
    def test_cross_replica_rag_rows(self, mini):
        embed = hash_embed()
        mk = lambda rid: StatePlane(
            GuardedBackend(RespStateBackend(port=mini.port)),
            replica_id=rid, namespace="vs1")
        a, b = mk("va"), mk("vb")
        sa = SharedVectorStore(a, "kb", embed_fn=embed)
        sb = SharedVectorStore(b, "kb", embed_fn=embed)
        doc = sa.ingest("doc1", "Contract law governs agreements. "
                        "A breach of contract has remedies. "
                        "Damages compensate the innocent party.")
        assert doc.chunk_ids
        hits = sb.search("breach of contract remedies", top_k=2)
        assert hits and "breach" in hits[0].chunk.text.lower()
        # delete through the OTHER replica
        assert sb.delete_document(doc.id)
        assert sa.search("breach of contract remedies",
                         threshold=0.99) == []
        a.close(), b.close()

    def test_manager_cross_replica_attach(self, mini):
        from semantic_router_tpu.vectorstore import VectorStoreManager

        embed = hash_embed()
        mk = lambda rid: StatePlane(
            GuardedBackend(RespStateBackend(port=mini.port)),
            replica_id=rid, namespace="vs2")
        a, b = mk("ma"), mk("mb")
        mgr_a = VectorStoreManager(embed, backend="stateplane",
                                   stateplane=a)
        mgr_b = VectorStoreManager(embed, backend="stateplane",
                                   stateplane=b)
        store = mgr_a.create("docs")
        store.ingest("d", "Shared text about liability limits.")
        # b never created "docs" — it attaches by name via the plane
        got = mgr_b.get("docs")
        assert got is not None
        assert got.search("liability limits", top_k=1)
        assert mgr_b.get("never-created") is None
        a.close(), b.close()

    def test_mid_ingest_failure_strands_no_searchable_orphans(self, mini):
        """A backend death between the chunk writes and the doc row
        must not leave searchable orphan chunks (no doc row references
        them, so _resync skips them), and recovery reaps the stranded
        bytes before replaying under fresh ids."""
        embed = hash_embed()

        class DocPutFails:
            """Backend whose plain put() dies for doc keys — chunk
            put_hash calls land, the doc row never does."""

            def __init__(self, inner):
                self.inner = inner
                self.fail_doc_puts = False

            def put(self, key, value, ttl_s=None):
                if self.fail_doc_puts and ":doc:" in key:
                    raise OSError("died mid-ingest")
                return self.inner.put(key, value, ttl_s=ttl_s)

            def __getattr__(self, name):
                return getattr(self.inner, name)

        raw = DocPutFails(RespStateBackend(port=mini.port))
        mk = lambda rid, be: StatePlane(
            GuardedBackend(be, cooldown_s=0.05),
            replica_id=rid, namespace="vs4")
        a = mk("oa", raw)
        b = mk("ob", RespStateBackend(port=mini.port))
        sa = SharedVectorStore(a, "kb", embed_fn=embed)
        raw.fail_doc_puts = True
        sa.ingest("d1", "Contract law governs agreements "
                        "between parties.")
        chunk_prefix = b.key("vs", "kb", "chunk", "")
        stranded = b.backend.scan(chunk_prefix)
        assert stranded  # chunk rows landed before the doc put died
        # a replica syncing NOW must not mirror the orphans
        sc = SharedVectorStore(b, "kb", embed_fn=embed)
        assert sc.search("contract law agreements",
                         threshold=0.3) == []
        # recovery: probe re-attaches, reconcile reaps + replays
        raw.fail_doc_puts = False
        time.sleep(0.1)
        deadline = time.time() + 5.0
        while time.time() < deadline:
            try:
                sa.search("probe")  # drives the breaker's probe
                keys = set(b.backend.scan(chunk_prefix))
                if keys and not (keys & set(stranded)):
                    break
            except StateBackendUnavailable:
                pass
            time.sleep(0.05)
        keys = set(b.backend.scan(chunk_prefix))
        assert keys and not (keys & set(stranded))  # reaped + replayed
        # the replay runs on the breaker's recovery thread: its chunk
        # rows are on the plane before its doc row and version bump, so
        # wait for the replica's mirror to see the document
        while time.time() < deadline:
            hits = sc.search("contract law agreements", top_k=5)
            if hits:
                break
            time.sleep(0.05)
        assert sum("contract" in h.chunk.text.lower()
                   for h in hits) == 1  # replayed once, no duplicates
        a.close(), b.close()

    def test_interleaved_ingest_mirror_converges(self, mini):
        """Same regression as the cache: replica B's own ingest must
        not hide a sibling ingest that landed since B's last resync."""
        embed = hash_embed()
        mk = lambda rid: StatePlane(
            GuardedBackend(RespStateBackend(port=mini.port)),
            replica_id=rid, namespace="vs3")
        a, b = mk("ia"), mk("ib")
        sa = SharedVectorStore(a, "kb", embed_fn=embed)
        sb = SharedVectorStore(b, "kb", embed_fn=embed)  # syncs ver 0
        sa.ingest("d1", "Contract law governs agreements "
                        "between parties.")              # ver 1
        sb.ingest("d2", "Unrelated text about baking sourdough "
                        "bread at home.")                # B incr -> 2
        hits = sb.search("contract law agreements", top_k=3)
        assert any("contract" in h.chunk.text.lower() for h in hits)
        a.close(), b.close()


class TestDecisionMirror:
    def test_fleet_wide_durable_records(self, mini):
        mk = lambda rid: StatePlane(
            GuardedBackend(RespStateBackend(port=mini.port)),
            replica_id=rid, namespace="dm1")
        a, b = mk("da"), mk("db")
        sa = StatePlaneDecisionStore(a, max_records=100)
        sb = StatePlaneDecisionStore(b, max_records=100)
        sa.add({"record_id": "r1", "trace_id": "t1",
                "ts_unix": time.time(), "kind": "route",
                "model": "m1", "decision": {"name": "d1"}})
        # adds ride a background writer — poll until the flush lands
        deadline = time.time() + 5.0
        rec = sb.get("r1")
        while rec is None and time.time() < deadline:
            sa._drain()
            time.sleep(0.02)
            rec = sb.get("r1")
        assert rec is not None and rec["model"] == "m1"
        assert sb.get("t1")["record_id"] == "r1"  # trace-id lookup
        assert len(sb) == 1
        rows = sb.list(model="m1")
        assert rows and rows[0]["record_id"] == "r1"
        assert sb.list(model="other") == []
        sa.close(), sb.close()
        a.close(), b.close()

    def test_retention_trims_oldest(self, mini):
        plane = StatePlane(
            GuardedBackend(RespStateBackend(port=mini.port)),
            replica_id="dr", namespace="dm2")
        store = StatePlaneDecisionStore(plane, max_records=5)
        # stop the background writer so the explicit drain+trim below
        # cannot race it (half-drained queues make the count flap)
        store._stop.set()
        store._wake.set()
        store._writer.join(timeout=2.0)
        for i in range(12):
            store.add({"record_id": f"r{i:02d}", "trace_id": f"t{i}",
                       "ts_unix": 1000.0 + i, "kind": "route",
                       "model": "m"})
        store._drain()
        store._trim()
        assert len(store) <= 5
        # newest survive
        assert store.get("r11") is not None
        assert store.get("r00") is None
        store.close()
        plane.close()


class TestConfigSeam:
    def test_disabled_builds_nothing(self):
        cfg = RouterConfig()
        assert build_state_plane(cfg) is None

    def test_enabled_memory_plane(self):
        cfg = RouterConfig.from_dict({"stateplane": {
            "enabled": True, "backend": "memory",
            "replica_id": "cfg-r", "heartbeat_s": 0.5}})
        plane = build_state_plane(cfg)
        assert plane is not None and plane.replica_id == "cfg-r"
        plane.heartbeat_once()
        assert plane.members() == ["cfg-r"]
        plane.close()

    def test_normalization_survives_garbage(self):
        cfg = RouterConfig.from_dict({"stateplane": {
            "enabled": True, "heartbeat_s": "soon",
            "ring_vnodes": "many", "share": {"cache": False}}})
        sp = cfg.stateplane_config()
        assert sp["heartbeat_s"] == 2.0
        assert sp["ring_vnodes"] == 64
        assert sp["share"]["cache"] is False
        assert sp["share"]["fleet"] is True

    def test_router_default_has_no_plane_reads(self):
        """enabled=false leaves Router.stateplane None — the
        byte-identical single-process posture."""
        from semantic_router_tpu.router.pipeline import Router

        router = Router(RouterConfig(default_model="m"))
        assert router.stateplane is None
        res = router.route({"model": "auto", "messages": [
            {"role": "user", "content": "hello"}]})
        assert "x-vsr-affinity-replica" not in res.headers
        router.shutdown()
