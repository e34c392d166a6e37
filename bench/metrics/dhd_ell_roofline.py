"""Pallas DHD ELL sweeps of the warm heat solve: the least time the chip's
HBM bandwidth allows for the relaxation's bytes, over the device time of the
compiled relaxation (the XLA module whose name holds ``heat_relax``) that starts
inside each program span ``heat.sweeps`` in the traced window.

Bytes are the algorithm's, whatever implements it, counted unpadded: per
sweep, 12 B per alive directed edge (its column, its weight and the
neighbour's heat), each undirected edge taken in both directions, and 12 B
per vertex (heat in, heat out, source).  The span's tags give the vertices
and alive edges and the sweeps run; the program runs one more sweep, which
prices the residual.  Padded rows and slots therefore show as a lower share.
"""
from bench import peaks, tracefile

SPAN = "heat.sweeps"
MODULE = "heat_relax"


def sweep_bytes(nodes: int, edges: int) -> int:
    return 12 * 2 * edges + 12 * nodes


def _sweep_spans(trace_dir):
    """``(t0, t1, tags)`` of each ``heat.sweeps`` annotation in the trace."""
    path = tracefile.find_xplane(trace_dir) if trace_dir else None
    if path is None:
        return []
    from jax.profiler import ProfileData

    return _spans_of(ProfileData.from_file(path))


def _spans_of(pd):
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == SPAN]


def read(ctx):
    red = ctx["trace"]
    if not red or not red["devices"]:
        return None
    mods = red["module_events"][red["devices"][0]]
    bw = peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    moved = 0.0
    dev_s = 0.0
    for t0, t1, tags in _sweep_spans(ctx["win"].trace_dir):
        if not {"nodes", "edges", "iters"} <= set(tags):
            continue
        dt = sum(d for name, t, d in mods if t0 <= t < t1 and MODULE in name)
        if dt > 0:
            moved += (int(tags["iters"]) + 1) * sweep_bytes(int(tags["nodes"]), int(tags["edges"]))
            dev_s += dt
    return 100.0 * moved / bw / dev_s if dev_s > 0 else None
