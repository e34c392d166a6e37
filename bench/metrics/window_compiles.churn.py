"""Programs compiled inside the measured window (XLA backend compiles,
counted through ``jax.monitoring``): the update path's programs are
compiled ahead, so every batch in the window should compile none."""


def read(ctx):
    return float(ctx["win"].compiles)
