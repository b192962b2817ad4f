"""Dynamic batching shim — the host-side front end of the TPU engine.

Capability parity with the reference's continuous batch scheduler (N6,
candle-binding/src/model_architectures/embedding/continuous_batch_scheduler.rs:
124-250: queue → batch builder bounded by max_batch_size / max_wait_ms →
single forward → result distribution), re-designed for XLA's compilation
model:

- requests are grouped by (group_key, seq-len bucket); sequences pad to the
  bucket edge and batches pad to the next power-of-two ≤ max_batch_size, so
  the jit cache sees a small closed set of shapes (SURVEY.md hard-part 1:
  bucketed padding + compile-cache discipline).
- adaptive wait: the scheduler sleeps at most ``max_wait_ms`` past the
  oldest queued item, but fires immediately when a full batch is ready or
  the queue is drained at low QPS (no added queueing latency when idle —
  hard-part 2).
- fail-open: a forward error resolves every future in the batch with the
  exception rather than wedging callers.
- concurrent dispatch (VERDICT r3 item 6): ready batches are handed to a
  small worker pool — at most ONE in-flight batch per group (preserves
  per-group ordering and avoids duplicate compiles of one shape), but
  different (task, bucket) groups dispatch concurrently, so a cold
  XLA compile of one bucket (seconds) cannot park live traffic on warm
  buckets.  The reference runs a dedicated scheduler thread per engine
  (continuous_batch_scheduler.rs:124-250); here one picker + N dispatch
  workers gives the same isolation on a shared chip, where XLA already
  serializes on-device execution.

The runner receives (group_key, list[BatchItem]) and returns one result per
item; it owns padding/stacking since shapes are model-specific.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence


def _capture_trace():
    """Snapshot the submitting thread's active trace context
    (observability.batchtrace) so the batch runner — which executes on a
    dispatch thread where thread-local tracer context is lost — can emit
    batch.wait/batch.ride spans back into each request's trace.  One
    thread-local read when no trace is open."""
    try:
        from ..observability.batchtrace import capture

        return capture()
    except Exception:
        return None


@dataclass
class BatchItem:
    payload: Any  # model-specific (e.g. Encoding)
    future: Future = field(default_factory=Future)
    enqueue_t: float = field(default_factory=time.perf_counter)
    # the originating request's (tracer, trace_id, span_id, sampled),
    # captured at enqueue — None on untraced requests
    trace: Any = field(default_factory=_capture_trace)
    # packed steps this item was passed over by the packing scheduler's
    # lookahead (engine.packing.scheduler): bounded by the scheduler's
    # starvation_steps knob — the continuous-admission fairness bound
    deferred: int = 0


BatchRunner = Callable[[Hashable, List[BatchItem]], Sequence[Any]]


def pow2_batch(n: int, max_batch: int) -> int:
    """Smallest power of two ≥ n, capped at max_batch.

    A non-power-of-two ``max_batch`` is allowed and adds exactly ONE
    extra compiled shape: batch dims come from {1, 2, 4, …} ∪
    {max_batch}, so the per-bucket shape count stays ⌈log2(max_batch)⌉+1
    (shape_census() is the regression surface)."""
    b = 1
    while b < n:
        b <<= 1
    return min(b, max_batch)


def pick_bucket(seq_len: int, buckets: Sequence[int]) -> int:
    """Smallest bucket that fits ``seq_len``.

    A seq_len past the largest bucket CLAMPS to buckets[-1] — the batch
    builders then clip the encoding at the bucket edge, tag the item's
    result ``truncated=True``, and count
    llm_batcher_bucket_overflow_total; the clamp is never silent (a task
    registered with max_seq_len > buckets[-1] is the case that hits
    this)."""
    for b in buckets:
        if seq_len <= b:
            return b
    return buckets[-1]


class _DispatchPool:
    """N DAEMON worker threads over a queue — deliberately not
    ThreadPoolExecutor, whose non-daemon workers are joined at
    interpreter exit: a forward call wedged in PJRT would then block
    process exit forever.  Daemon workers
    let a clean self-exit proceed; shutdown() CANCELS still-queued
    batches instead of running them against torn-down model state."""

    def __init__(self, workers: int, name: str) -> None:
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._stopped = False
        # serialises submit's check+put against shutdown's flag+drain:
        # without it an item enqueued between the drain and the last
        # worker's exit would neither run nor cancel (futures hang)
        self._guard = threading.Lock()
        # workers currently inside a batch (saturation gauge); guarded
        # by its own lock — `self._busy += 1` is LOAD/ADD/STORE, not
        # atomic, and lost updates would drift the gauge permanently
        self._busy = 0
        self._busy_lock = threading.Lock()
        self._threads = []
        for i in range(max(1, workers)):
            t = threading.Thread(target=self._work, daemon=True,
                                 name=f"{name}-{i}")
            t.start()
            self._threads.append(t)

    def stats(self) -> dict:
        """Saturation snapshot for the runtime-stats gauges: queued
        batches + busy/total workers (all-busy with a backlog = the
        dispatch pool is the bottleneck, not the device)."""
        return {"workers": len(self._threads), "queued": self._q.qsize(),
                "busy": self._busy}

    def submit(self, run: Callable, cancel: Callable, *args: Any) -> None:
        with self._guard:
            if self._stopped:
                raise RuntimeError("dispatch pool stopped")
            self._q.put((run, cancel, args))

    def _work(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            run, cancel, args = item
            with self._busy_lock:
                self._busy += 1
            try:
                (cancel if self._stopped else run)(*args)
            finally:
                with self._busy_lock:
                    self._busy -= 1

    def shutdown(self) -> None:
        with self._guard:
            self._stopped = True
            for _ in self._threads:
                self._q.put(None)
        # drain-and-cancel whatever is still queued; a worker that grabs
        # an item after the flag also cancels, so nothing runs late.  The
        # drain races the parked workers for the None sentinels above —
        # count any it steals and re-put them, or an idle worker could
        # block in q.get() forever.
        stolen = 0
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is None:
                stolen += 1
            else:
                _, cancel, args = item
                cancel(*args)
        for _ in range(stolen):
            self._q.put(None)


class DynamicBatcher:
    """Coalesces concurrent requests into padded batches per group."""

    def __init__(self, runner: BatchRunner, max_batch_size: int = 32,
                 max_wait_ms: float = 2.0, name: str = "batcher",
                 dispatch_workers: int = 4, metrics=None,
                 patient: Optional[Callable[[Hashable], bool]] = None
                 ) -> None:
        self.runner = runner
        # groups whose step lasts far longer than max_wait (a whole
        # generation): such a group takes no low-QPS fast path and counts
        # its wait from its last step's end, so that the callers a step
        # released ride the NEXT step together instead of one firing alone
        # and the rest queueing behind it for a second step
        self._patient = patient or (lambda key: False)
        # facts of a group's ``engine.queue_wait`` events beyond its name
        # (the engine says a generative group's ``bucket``)
        self.wait_facts: Callable[[Hashable], Dict[str, int]] = \
            lambda key: {}
        self._released: Dict[Hashable, float] = {}
        self.name = name
        self.max_batch_size = max(1, max_batch_size)
        self.max_wait_s = max_wait_ms / 1000.0
        self._queues: Dict[Hashable, List[BatchItem]] = {}
        # in-flight STEP COUNT per group (plain DynamicBatcher caps at
        # 1 — ordering + compile dedup; the packing scheduler raises the
        # cap so host-side composition of step k+1 overlaps step k's
        # device execution: continuous admission)
        self._inflight: Dict[Hashable, int] = {}
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._stop = False
        self._stats = {"batches": 0, "items": 0, "max_batch": 0,
                       "max_inflight": 0}
        # instance-routable observability like the engine's: None = the
        # process default series (single-engine posture)
        self._metrics = metrics
        self._pool = _DispatchPool(dispatch_workers,
                                   name=f"{name}-dispatch")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=name)
        self._thread.start()

    # -- public ------------------------------------------------------------

    def submit(self, group_key: Hashable, payload: Any) -> Future:
        item = BatchItem(payload)
        with self._wake:
            if self._stop:
                raise RuntimeError("batcher stopped")
            self._queues.setdefault(group_key, []).append(item)
            self._wake.notify()
        return item.future

    def submit_many(self, group_key: Hashable,
                    payloads: Sequence[Any]) -> List[Future]:
        items = [BatchItem(p) for p in payloads]
        with self._wake:
            if self._stop:
                raise RuntimeError("batcher stopped")
            self._queues.setdefault(group_key, []).extend(items)
            self._wake.notify()
        return [i.future for i in items]

    def _series(self):
        if self._metrics is not None:
            return self._metrics
        from ..observability import metrics as M

        return M.default_series

    def _observe_batch(self, key: Hashable,
                       batch: List[BatchItem]) -> None:
        """Queue-wait + occupancy series per dispatched batch: the fused
        path's coalescing win must be *visible* (p99 wait vs fill ratio),
        not inferred from end-to-end latency.  Each item's wait is also
        an ``engine.queue_wait`` profiler annotation carrying its
        request's trace id (observability.batchtrace.queue_wait), so a
        profile joins an item to its ``router.route``.  Runs on the
        single picker thread, so it fails open — an observability error
        (e.g. a custom metrics object missing these series) must never
        kill the loop that all serving depends on."""
        try:
            from ..observability.batchtrace import queue_wait

            s = self._series()
            now = time.perf_counter()
            group = ":".join(map(str, key)) if isinstance(key, tuple) \
                else str(key)
            facts = self.wait_facts(key)
            for item in batch:
                # exemplar: the waiting request's trace id, so a slow
                # queue-wait bucket links straight to the trace that
                # landed there (no-op unless exemplars are enabled)
                tid = item.trace.trace_id if item.trace is not None else None
                s.batcher_queue_wait.observe(now - item.enqueue_t,
                                             exemplar=tid,
                                             batcher=self.name)
                queue_wait(tid or "", group, now - item.enqueue_t, **facts)
            s.batcher_fill_ratio.observe(len(batch) / self.max_batch_size,
                                         batcher=self.name)
        except Exception:
            pass

    def queue_depths(self) -> dict:
        """Live congestion snapshot for the runtime-stats sampler
        (llm_dispatcher_queue_depth): queued items/groups, in-flight
        groups, and the dispatch pool's saturation."""
        with self._lock:
            out = {
                "pending_items": sum(len(v) for v in
                                     self._queues.values()),
                "pending_groups": sum(1 for v in self._queues.values()
                                      if v),
                "inflight_groups": sum(1 for v in self._inflight.values()
                                       if v > 0),
            }
        pool = self._pool.stats()
        out["pool_queued"] = pool["queued"]
        out["pool_busy"] = pool["busy"]
        out["pool_saturation"] = (pool["busy"] / pool["workers"]
                                  if pool["workers"] else 0.0)
        return out

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._stats)
        out["fill_ratio_mean"] = (out["items"] / out["batches"]
                                  / self.max_batch_size
                                  if out["batches"] else 0.0)
        try:
            s = self._series()
            wait = s.batcher_queue_wait
            fill = s.batcher_fill_ratio
            out["queue_wait_p50_s"] = wait.percentile(50, batcher=self.name)
            out["queue_wait_p99_s"] = wait.percentile(99, batcher=self.name)
            out["fill_ratio_p50"] = fill.percentile(50, batcher=self.name)
        except Exception:
            pass  # base counters still report
        return out

    def shutdown(self, timeout: float = 5.0) -> None:
        with self._wake:
            self._stop = True
            self._wake.notify_all()
        self._thread.join(timeout=timeout)
        self._pool.shutdown()
        # resolve anything left
        with self._lock:
            for items in self._queues.values():
                for it in items:
                    if not it.future.done():
                        it.future.set_exception(RuntimeError("batcher stopped"))
            self._queues.clear()

    # -- scheduler loop ----------------------------------------------------

    # composition hooks — the packing scheduler (engine.packing.scheduler
    # .PackingBatcher) overrides these; the defaults reproduce the
    # original fixed-batch behavior exactly.

    def _inflight_cap(self, key: Hashable) -> int:
        """Max concurrent in-flight steps for a group.  1 (the default)
        keeps per-group ordering and compile dedup; the packing
        scheduler raises it for continuous admission."""
        return 1

    def _group_full(self, key: Hashable, items: List[BatchItem]) -> bool:
        """True when the group should fire without waiting."""
        return len(items) >= self.max_batch_size

    def _ready_immediately(self, key: Hashable,
                           items: List[BatchItem]) -> bool:
        """Extra readiness (continuous admission): fire before max_wait
        because something else provides the accumulation window."""
        return False

    def _take_batch(self, key: Hashable, items: List[BatchItem]
                    ) -> tuple:
        """Split a group's queue into (batch to dispatch, remainder)."""
        return items[:self.max_batch_size], items[self.max_batch_size:]

    def _ready_group(self) -> Optional[Hashable]:
        """A group is ready when full, or its oldest item aged past
        max_wait, or (low-QPS fast path) nothing else is pending.
        Groups at their in-flight cap are NOT ready — the cap (1 by
        default) keeps ordering and compile-dedup."""
        now = time.perf_counter()
        oldest_key, oldest_age = None, -1.0
        total = 0
        for key, items in self._queues.items():
            if not items or self._inflight.get(key, 0) \
                    >= self._inflight_cap(key):
                continue
            total += len(items)
            if self._group_full(key, items) \
                    or self._ready_immediately(key, items):
                return key
            age = now - self._waiting_since(key, items)
            if age > oldest_age:
                oldest_key, oldest_age = key, age
        if oldest_key is None:
            return None
        if oldest_age >= self.max_wait_s:
            return oldest_key
        # single pending group and small queue: fire immediately — waiting
        # cannot grow the batch if no concurrent traffic exists
        if total == len(self._queues.get(oldest_key, ())) and total <= 1 \
                and not self._patient(oldest_key):
            return oldest_key
        return None

    def _waiting_since(self, key: Hashable, items: List[BatchItem]) -> float:
        """When the group's max_wait began: its oldest item's enqueue, or
        for a patient group its last step's end if that came later."""
        return max(items[0].enqueue_t, self._released.get(key, 0.0))

    def _next_deadline(self) -> Optional[float]:
        deadline = None
        for key, items in self._queues.items():
            if items and self._inflight.get(key, 0) \
                    < self._inflight_cap(key):
                d = self._waiting_since(key, items) + self.max_wait_s
                deadline = d if deadline is None else min(deadline, d)
        return deadline

    def _loop(self) -> None:
        while True:
            with self._wake:
                while not self._stop:
                    key = self._ready_group()
                    if key is not None:
                        break
                    deadline = self._next_deadline()
                    timeout = None if deadline is None else \
                        max(0.0, deadline - time.perf_counter())
                    self._wake.wait(timeout=timeout)
                if self._stop:
                    return
                items = self._queues[key]
                batch, rest = self._take_batch(key, items)
                if not batch:  # defensive: a planner must never wedge
                    batch, rest = items[:1], items[1:]
                self._queues[key] = rest
                self._inflight[key] = self._inflight.get(key, 0) + 1
                self._stats["batches"] += 1
                self._stats["items"] += len(batch)
                self._stats["max_batch"] = max(self._stats["max_batch"],
                                               len(batch))
                self._stats["max_inflight"] = max(
                    self._stats["max_inflight"],
                    sum(1 for v in self._inflight.values() if v > 0))
            self._observe_batch(key, batch)
            try:
                self._pool.submit(self._dispatch, self._cancel_batch,
                                  key, batch)
            except RuntimeError:  # pool shut down underneath us
                self._cancel_batch(key, batch)

    def _release_inflight(self, key: Hashable) -> None:
        if self._patient(key):
            self._released[key] = time.perf_counter()
        n = self._inflight.get(key, 0)
        if n <= 1:
            self._inflight.pop(key, None)
        else:
            self._inflight[key] = n - 1

    def _dispatch(self, key: Hashable, batch: List[BatchItem]) -> None:
        try:
            self._run_batch(key, batch)
        finally:
            # group becomes dispatchable again; wake the picker in case
            # it queued more items for this group while we ran
            with self._wake:
                self._release_inflight(key)
                self._wake.notify()

    def _cancel_batch(self, key: Hashable, batch: List[BatchItem]) -> None:
        """Shutdown raced this batch out of the pool queue: fail its
        futures rather than running the model against torn-down state."""
        with self._wake:
            self._release_inflight(key)
        for item in batch:
            if not item.future.done():
                item.future.set_exception(RuntimeError("batcher stopped"))

    def _run_batch(self, key: Hashable, batch: List[BatchItem]) -> None:
        try:
            results = self.runner(key, batch)
            if len(results) != len(batch):
                raise RuntimeError(
                    f"runner returned {len(results)} results for "
                    f"{len(batch)} items")
            for item, res in zip(batch, results):
                item.future.set_result(res)
        except Exception as exc:  # fail open: propagate to callers
            for item in batch:
                if not item.future.done():
                    item.future.set_exception(exc)
