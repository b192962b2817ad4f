"""Mean time an item waited in the batcher's queue before its batch was
dispatched: the batcher's own ``queue_wait`` histogram (sum / count) over
the window, ms."""


def read(run):
    before, after = run["queue_wait"]
    if before is None or after is None or after[1] == before[1]:
        return None
    return (after[0] - before[0]) / (after[1] - before[1]) * 1e3
