"""Update path: the id-space growth of a batch (item rows, workload,
demand tables, the heat caches' views), from the program's ``store.remap``
span in the trace; mean over the spans that start in the traced window."""
from bench import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "store.remap")
