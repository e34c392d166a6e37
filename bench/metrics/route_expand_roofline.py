"""Pallas ``route_expand``: the least time the chip's HBM bandwidth allows
for the routing expansion's bytes, over the device time of what the call
puts on the chip (every XLA module it runs: the kernel and the gathers and
pads that feed it), in the traced window.

Bytes are the algorithm's, whatever implements it: per unpadded item a 4 B
replica bitmask and 4 B size in and a 4 B pick out; per request its length
and origin in and its layers-used and missing-after-each-layer counts out.
Padding the tile therefore shows as a lower share.  The expansion does no
floating-point work worth counting, so HBM bandwidth bounds it.
"""
from bench import peaks, tracefile

ITEM_BYTES = 12


def request_bytes(n_layers: int) -> int:
    return 4 * (2 + 1 + n_layers + 1)


def read(ctx):
    red = ctx["trace"]
    if not red:
        return None
    bw = peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    per_req = request_bytes(ctx["st"].reg.n_layers)
    moved = 0.0
    dev_s = 0.0
    for span, dt in tracefile.device_time_in(red, "bench.serve_batch", "route_expand"):
        if dt > 0:
            moved += ITEM_BYTES * span["stats"]["items"] + per_req * span["stats"]["requests"]
            dev_s += dt
    return 100.0 * moved / bw / dev_s if dev_s > 0 else None
