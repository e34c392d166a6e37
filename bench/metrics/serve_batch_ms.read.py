"""Store facade and routing host path (``core/store.py``, ``core/routing.py``):
mean wall time of one ``serve_batch`` call, from the harness's span around it."""


def read(ctx):
    calls = ctx["win"].serve_calls
    return sum(t1 - t0 for t0, t1, *_ in calls) / len(calls) * 1e3 if calls else None
