"""Admission layer (``serve/scheduler.py``): the controller's own time per
step, from the program's ``serve.step`` span in the trace: the step minus
the spans of other layers inside it (the store's ``store.serve_batch`` and
``demand.deposit``); mean over the steps that start in the traced window."""
from bench import spans


def read(ctx):
    red = spans.of(ctx)
    ctl = red["controller_s"] if red else None
    return 1e3 * sum(ctl) / len(ctl) if ctl else None
