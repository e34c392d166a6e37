"""Routing epilogue (``core/routing.py``): mean duration of the program's
``routing.epilogue`` span in the trace (the float64 fold of the picks into
``RouteResult``s), over the spans that start in the traced window."""
from bench import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "routing.epilogue")
