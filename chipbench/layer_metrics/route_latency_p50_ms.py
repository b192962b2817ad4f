"""Median of completion minus send time of ``Router.route()`` over the
routes answered inside the window, ms.  In a closed loop at saturation it
is callers / rate (Little's law), so it sits beside ``routes_per_s`` and
carries no bound: what it adds is one caller's wait."""

from chipbench import stats


def read(run):
    lat = [r.latency * 1e3 for r in run["completed"]]
    return stats.percentile(lat, 50) if lat else None
