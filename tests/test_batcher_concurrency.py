"""Concurrent batch dispatch (VERDICT r3 item 6): a cold XLA compile of
one (task, bucket) group must not park live traffic on warm groups.

The reference gives each engine a dedicated scheduler thread
(continuous_batch_scheduler.rs:124-250); DynamicBatcher gets the same
isolation from one picker + a dispatch pool with at-most-one in-flight
batch per group.
"""

import threading
import time
from concurrent.futures import wait

import pytest

from semantic_router_tpu.engine.batcher import DynamicBatcher
from semantic_router_tpu.engine.packing.scheduler import PackingBatcher


class _Recorder:
    """Runner that records per-group concurrency and can stall a group."""

    def __init__(self, stall_group=None, stall_s=0.0):
        self.stall_group = stall_group
        self.stall_s = stall_s
        self.stalled_once = False
        self.lock = threading.Lock()
        self.active = {}
        self.max_active = {}
        self.calls = []

    def __call__(self, key, batch):
        with self.lock:
            self.active[key] = self.active.get(key, 0) + 1
            self.max_active[key] = max(self.max_active.get(key, 0),
                                       self.active[key])
            self.calls.append((key, len(batch)))
            do_stall = (key == self.stall_group and not self.stalled_once)
            if do_stall:
                self.stalled_once = True
        if do_stall:
            time.sleep(self.stall_s)  # simulated first-shape compile
        try:
            return [p * 2 for p in (it.payload for it in batch)]
        finally:
            with self.lock:
                self.active[key] -= 1


class TestConcurrentDispatch:
    def test_cold_group_does_not_park_warm_group(self):
        rec = _Recorder(stall_group="cold", stall_s=2.0)
        b = DynamicBatcher(rec, max_batch_size=8, max_wait_ms=1.0,
                           dispatch_workers=4)
        try:
            cold = b.submit("cold", 1)
            time.sleep(0.05)  # let the cold batch enter its "compile"
            t0 = time.perf_counter()
            warm = [b.submit("warm", i) for i in range(16)]
            wait(warm, timeout=5.0)
            warm_done_s = time.perf_counter() - t0
            assert all(f.done() for f in warm), "warm futures parked"
            # warm traffic must complete while cold is still compiling
            assert warm_done_s < 1.0, (
                f"warm batches took {warm_done_s:.2f}s — serialized "
                "behind the cold compile")
            assert cold.result(timeout=5.0) == 2
        finally:
            b.shutdown()

    def test_one_inflight_batch_per_group(self):
        rec = _Recorder(stall_group="g0", stall_s=0.3)
        b = DynamicBatcher(rec, max_batch_size=2, max_wait_ms=0.5,
                           dispatch_workers=4)
        try:
            futs = [b.submit("g0", i) for i in range(10)]
            wait(futs, timeout=5.0)
            assert [f.result() for f in futs] == [i * 2 for i in range(10)]
            # ordering + dedup invariant: never two g0 batches at once
            assert rec.max_active.get("g0", 0) == 1
        finally:
            b.shutdown()

    def test_groups_overlap_on_the_pool(self):
        barrier = threading.Barrier(3, timeout=3.0)

        def runner(key, batch):
            barrier.wait()  # only passes if 3 groups run CONCURRENTLY
            return [it.payload for it in batch]

        b = DynamicBatcher(runner, max_batch_size=4, max_wait_ms=0.5,
                           dispatch_workers=4)
        try:
            futs = [b.submit(f"g{i}", i) for i in range(3)]
            done, not_done = wait(futs, timeout=4.0)
            assert not not_done, "groups did not dispatch concurrently"
            assert sorted(f.result() for f in futs) == [0, 1, 2]
        finally:
            b.shutdown()

    def test_queued_items_drain_after_inflight_completes(self):
        rec = _Recorder(stall_group="g", stall_s=0.2)
        b = DynamicBatcher(rec, max_batch_size=4, max_wait_ms=0.5,
                           dispatch_workers=2)
        try:
            first = b.submit("g", 0)
            time.sleep(0.05)
            # these arrive while g is in flight; they must dispatch
            # after it completes, not be dropped or deadlocked
            later = [b.submit("g", i) for i in range(1, 5)]
            wait([first, *later], timeout=5.0)
            assert first.result() == 0
            assert [f.result() for f in later] == [2, 4, 6, 8]
        finally:
            b.shutdown()

    def test_stats_track_inflight(self):
        rec = _Recorder()
        b = DynamicBatcher(rec, max_batch_size=4, dispatch_workers=4)
        try:
            futs = [b.submit(f"g{i % 3}", i) for i in range(12)]
            wait(futs, timeout=5.0)
            s = b.stats()
            assert s["items"] == 12
            assert s["max_inflight"] >= 1
        finally:
            b.shutdown()


def _closed_loop(batcher, key, callers, rounds):
    """``callers`` threads, each submitting again as soon as it is
    answered: what a step releases comes back within a millisecond."""
    def caller(i):
        for _ in range(rounds):
            batcher.submit(key, i).result(timeout=10.0)

    threads = [threading.Thread(target=caller, args=(i,))
               for i in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)


def _packing_batcher(runner, **kw):
    """The engine's batcher; no group here is packable."""
    return PackingBatcher(runner, bucket_of=lambda key: None, **kw)


@pytest.mark.parametrize("cls", [DynamicBatcher, _packing_batcher],
                         ids=["DynamicBatcher", "PackingBatcher"])
class TestPatientGroups:
    """A group whose step lasts far longer than max_wait (a whole
    generation; the engine marks its generative groups so): the callers a
    step released ride the NEXT step together.  Without it the first to
    come back fires alone (the low-QPS fast path) and the rest queue
    behind it for a second step: steps of 1 and n-1 rows in turn."""

    def test_released_callers_ride_the_next_step_together(self, cls):
        rec = _Recorder(stall_group=None)
        slow = lambda key, batch: (time.sleep(0.15), rec(key, batch))[1]
        b = cls(slow, max_batch_size=8, max_wait_ms=100.0,
                patient=lambda key: key == "gen")
        try:
            _closed_loop(b, "gen", callers=4, rounds=3)
        finally:
            b.shutdown()
        assert rec.calls == [("gen", 4)] * 3

    def test_a_patient_group_waits_max_wait_from_its_last_release(self, cls):
        rec = _Recorder()
        b = cls(rec, max_batch_size=8, max_wait_ms=150.0,
                patient=lambda key: key == "gen")
        try:
            b.submit("gen", 0).result(timeout=5.0)  # releases at about now
            t0 = time.perf_counter()
            first = b.submit("gen", 1)
            time.sleep(0.05)
            second = b.submit("gen", 2)
            assert (first.result(timeout=5.0), second.result(timeout=5.0)) \
                == (2, 4)
            waited = time.perf_counter() - t0
        finally:
            b.shutdown()
        # one step for both, max_wait after the release and not after the
        # second item's enqueue
        assert rec.calls[-1] == ("gen", 2) and 0.05 < waited < 2.0

    def test_an_ordinary_group_still_takes_the_fast_path(self, cls):
        rec = _Recorder()
        b = cls(rec, max_batch_size=8, max_wait_ms=2000.0,
                patient=lambda key: key == "gen")
        try:
            t0 = time.perf_counter()
            assert b.submit("cls", 3).result(timeout=5.0) == 6
            fast = time.perf_counter() - t0
            t0 = time.perf_counter()
            lone = b.submit("gen", 4)
            time.sleep(0.3)
            assert not lone.done()  # a lone patient item waits for company
            for i in range(7):
                b.submit("gen", i)  # a full group fires at once
            assert lone.result(timeout=1.0) == 8
        finally:
            b.shutdown()
        assert fast < 0.5, f"a lone ordinary item waited {fast:.2f} s"
        assert rec.calls == [("cls", 1), ("gen", 8)]
