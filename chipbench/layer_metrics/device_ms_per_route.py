"""Device-busy time (union of the device's op intervals in the traced
window) per route completed in it, ms."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["completed"]:
        return None
    return tr["busy_s"] / len(tr["completed"]) * 1e3
