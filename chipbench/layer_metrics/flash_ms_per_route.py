"""Device time of the flash attention kernel's executions in the traced
window per route completed in it, ms."""

from chipbench import cells, reduce_trace


def read(run):
    tr = run["trace"]
    if not tr or not tr["completed"]:
        return None
    pattern = cells.load_module("opcount", "flash_attention").EVENT_PATTERN
    secs, calls = reduce_trace.seconds_matching(tr, pattern)
    return secs / len(tr["completed"]) * 1e3 if calls else None
