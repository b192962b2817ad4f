"""Per route completed in the traced window, the time it spent waiting in
the batcher: the union of its items' ``engine.queue_wait`` intervals, joined
to the route by ``trace_id`` (an item waits while the step before it runs; a
route's items wait one after another and side by side).  A route that began
before the session is seen from the session's start on: it counts by the
share of its seen time that it waited, times its length, and not at all if
less than half of it was seen.  Median, ms."""

from chipbench import stats
from chipbench.layer_metrics import _program_spans


def read(run):
    ps = _program_spans.load(run)
    if ps is None:
        return None
    by_route = {}
    for trace_id, start, end in ps.waits:
        by_route.setdefault(trace_id, []).append((start, end))
    waited = []
    for trace_id, (start, end) in ps.routes.items():
        seen_from = max(start, ps.window[0])
        if end <= start or end - seen_from < 0.5 * (end - start):
            continue
        share = stats.union_length(by_route.get(trace_id, ()),
                                   clip=(seen_from, end)) / (end - seen_from)
        waited.append(share * (end - start))
    return stats.percentile(waited, 50) * 1e3 if waited else None
