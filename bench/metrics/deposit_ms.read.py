"""Demand plane (``demand/od_layer.py``): per ``serve_batch`` call, the wall
time outside the store's own routing seconds (``last_serve_seconds``), which
is the demand deposit; mean per call."""


def read(ctx):
    calls = ctx["win"].serve_calls
    if not calls:
        return None
    return sum((t1 - t0) - serve_s for t0, t1, _, _, serve_s in calls) / len(calls) * 1e3
