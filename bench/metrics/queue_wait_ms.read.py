"""Admission layer (``serve/scheduler.py``): mean wait of an answered read
from its scheduled arrival to the controller's dispatch of its batch, over
the reads answered before the profiler started."""
from bench.harness import before_trace


def read(ctx):
    win = ctx["win"]
    ok = before_trace(win)
    return float(win.wait[ok].mean() * 1e3) if ok.any() else None
