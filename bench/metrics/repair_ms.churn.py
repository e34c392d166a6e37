"""Update path: the layered-graph repair over the batch's alive edges,
from the program's ``store.repair_layers`` span in the trace; mean over
the spans that start in the traced window."""
from bench import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "store.repair_layers")
