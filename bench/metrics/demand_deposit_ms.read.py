"""Demand plane (``demand/od_layer.py``): mean duration of the program's
``demand.deposit`` span in the trace (the heat deposit of a served batch),
over the spans that start in the traced window."""
from bench import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "demand.deposit")
