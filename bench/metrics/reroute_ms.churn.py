"""Update path: the route-index patch of the rows whose replica sets the
batch changed, from the program's ``store.reroute`` span in the trace;
mean over the spans that start in the traced window."""
from bench import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "store.reroute")
