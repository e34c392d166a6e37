"""Client (open-loop arrivals): the 99th percentile of read latency, from a
read's scheduled arrival to the return of the controller step that served
it, over the reads answered before the profiler started.  Host stalls of
about a tenth of a second set it, so it stands here beside the end-to-end
median and not among the bounded metrics."""
import numpy as np

from bench.harness import before_trace


def read(ctx):
    win = ctx["win"]
    ok = before_trace(win)
    if not ok.any():
        return None
    return float(np.percentile((win.done[ok] - ctx["st"].requests.t[ok]) * 1e3, 99))
