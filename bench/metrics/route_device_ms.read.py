"""Kernel dispatch and transfers (``kernels/ops.py``): mean host duration of
the program's ``routing.device`` span in the trace (upload, launch, wait for
and fetch of the expansion on the chip), over the spans that start in the
traced window."""
from bench import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "routing.device")
