"""Drives ``send(request)`` open loop (a schedule, whatever the system
does) or closed loop (each client sends when its last call returns), from
one process with plain threads, and records every request.

All times are ``time.perf_counter()`` seconds relative to the window's
start.  Open loop: latency counts from the DUE time, so a stall is charged
to every request it delays, and lateness (actual minus scheduled send) is
kept so that a starved generator is not read as a fast system."""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Tuple


@dataclasses.dataclass
class Record:
    index: int
    n_tokens: int
    due: float          # when it should have been sent (closed: = start)
    start: float        # when it was sent
    end: float          # when the call returned
    ok: bool
    detail: str = ""    # why not ok

    @property
    def latency(self) -> float:
        return self.end - self.due


SendFn = Callable[[Any], Tuple[bool, str]]


def _call(send: SendFn, req, due: float, t0: float) -> Record:
    start = time.perf_counter() - t0
    try:
        ok, detail = send(req)
    except Exception as exc:  # the boundary: a raised route is a failure
        ok, detail = False, f"{type(exc).__name__}: {exc}"[:300]
    end = time.perf_counter() - t0
    return Record(req.index, req.n_tokens, start if due is None else due,
                  start, end, ok, detail)


def run_closed(send: SendFn, requests: List[Any], clients: int,
               seconds: float, t0: Optional[float] = None) -> List[Record]:
    """``clients`` callers take requests in order until the window ends;
    a call in flight at the end is waited for and recorded (its ``end``
    lies after ``seconds``: it is not a completion of the window)."""
    t0 = time.perf_counter() if t0 is None else t0
    records: List[Record] = []
    lock = threading.Lock()
    cursor = [0]

    def client() -> None:
        while True:
            with lock:
                i = cursor[0]
                if i >= len(requests) \
                        or time.perf_counter() - t0 >= seconds:
                    return
                cursor[0] += 1
            rec = _call(send, requests[i], None, t0)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, name=f"chipbench-client-{c}",
                                daemon=True) for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(records, key=lambda r: r.start)


def run_open(send: SendFn, requests: List[Any], senders: int,
             seconds: float, grace_s: float,
             t0: Optional[float] = None) -> Tuple[List[Record], int]:
    """Every request goes out at its due time from a pool of ``senders``
    threads.  Returns the records of the calls that returned within
    ``grace_s`` after the window, and the count of those that did not
    (they are failures; their threads are left to finish)."""
    t0 = time.perf_counter() if t0 is None else t0
    pool = ThreadPoolExecutor(senders, thread_name_prefix="chipbench-send")
    futures = []
    for req in sorted(requests, key=lambda r: r.due_s):
        wait = req.due_s - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        futures.append(pool.submit(_call, send, req, req.due_s, t0))
    deadline = t0 + seconds + grace_s
    records, unfinished = [], 0
    for f in futures:
        left = deadline - time.perf_counter()
        try:
            records.append(f.result(timeout=max(left, 0.0)))
        except TimeoutError:
            unfinished += 1
    pool.shutdown(wait=False, cancel_futures=True)
    return records, unfinished
