"""Self time of ``Router.route()``: the route's span minus the part of it
that engine calls for the same request cover — signal dispatch, decision
engine, selection, record keeping.  Median over the window, ms."""

from chipbench import stats


def read(run):
    routes, calls = {}, {}
    for name, key, s, e in run["spans"]:
        if name == "router.route":
            routes[key] = (s, e)
        else:
            calls.setdefault(key, []).append((s, e))
    texts = {run["requests"][r.index].text for r in run["completed"]}
    own = [(e - s) - stats.union_length(calls.get(k, ()), clip=(s, e))
           for k, (s, e) in routes.items() if k in texts]
    return stats.percentile(own, 50) * 1e3 if own else None
