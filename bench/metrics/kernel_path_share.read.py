"""Kernel dispatch (``kernels/ops.py``): share of ``serve_batch`` calls whose
routing expansion ran on the Pallas kernel, from the program's
``kernels.dispatch{op=route_expand,path=kernel}`` counter."""


def read(ctx):
    calls = ctx["win"].serve_calls
    if not calls:
        return None
    reg = ctx["registry"]
    n = reg.counter("kernels.dispatch", op="route_expand", path="kernel").value
    return 100.0 * n / len(calls)
