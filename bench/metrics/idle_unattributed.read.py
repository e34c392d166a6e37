"""Device: share of the device's idle time in the traced window during
which no program span was open on the serving thread (the harness's own
loop, or a stage the program's spans leave out)."""
from bench import spans


def read(ctx):
    red = spans.of(ctx)
    if not red or red["idle_s"] <= 0:
        return None
    return 100.0 * red["idle_unattributed_s"] / red["idle_s"]
