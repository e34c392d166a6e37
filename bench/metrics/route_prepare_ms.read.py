"""Routing host path (``core/routing.py``): mean duration of the program's
``routing.prepare`` span in the trace (request arrays, item bytes, replica
rows, bit pack and tile fill), over the spans that start in the traced
window."""
from bench import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "routing.prepare")
