"""Update path: the warm heat diffusion (ELL row patch, device sync,
frontier and global sweeps), from the program's ``store.warm_heat`` span
in the trace; mean over the spans that start in the traced window."""
from bench import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "store.warm_heat")
