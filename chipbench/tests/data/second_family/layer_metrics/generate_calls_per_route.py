"""Calls of the engine's ``generate`` per completed route, from the
benchmark's own spans around the wrapped call."""


def read(run):
    n = len(run["completed"])
    calls = sum(1 for name, _, _, _ in run["spans"]
                if name == "engine.generate")
    return calls / n if n and calls else None
