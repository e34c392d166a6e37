"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy time, the traced window, device time per
operation, the XLA module runs, the benchmark's host spans, and the
device's idle gaps attributed to the host span they fall in.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:N`` plane), averaged over
the devices that ran anything.  The traced window runs from the first to the
last of the benchmark's own host spans (``bench.*`` annotations), which the
window's loop keeps open back to back.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
# innermost first: the span an idle gap is charged to
SPAN_ORDER = ("bench.serve_batch", "bench.wait", "bench.event", "bench.drain")


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _op_name(hlo: str) -> str:
    """``%route_expand.1 = (s32[...]) custom-call(...)`` -> ``%route_expand.1``."""
    return hlo.split(" = ", 1)[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce(trace_dir: str) -> Optional[Dict]:
    """The reduced trace, or None where no trace file exists."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(pd) -> Dict:
    host: List[Dict] = []
    devices: Dict[str, Dict] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(_op_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append({"name": e.name, "t0": e.start_ns * 1e-9,
                                     "t1": (e.start_ns + e.duration_ns) * 1e-9,
                                     "stats": dict(e.stats)})
    host.sort(key=lambda s: s["t0"])
    if host:
        w0, w1 = host[0]["t0"], max(s["t1"] for s in host)
    else:
        w0, w1 = 0.0, 0.0
    used = {k: v for k, v in devices.items() if v["ops"]}
    busy_by_dev = {}
    op_time: Dict[str, float] = defaultdict(float)
    for name, d in used.items():
        iv = _union([(max(t, w0), min(t + dt, w1)) for _, t, dt in d["ops"]
                     if t + dt > w0 and t < w1])
        busy_by_dev[name] = iv
        for op, t, dt in d["ops"]:
            if w0 <= t < w1:
                op_time[op] += dt
    busy_s = (sum(sum(b - a for a, b in iv) for iv in busy_by_dev.values()) / len(used)
              if used else 0.0)
    gaps: Dict[str, float] = defaultdict(float)
    if used:
        first = busy_by_dev[sorted(used)[0]]
        edges = [w0] + [x for iv in first for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_host_span_at(host, 0.5 * (a + b))] += b - a
    return {
        "window_s": w1 - w0,
        "busy_s": busy_s,
        "devices": sorted(used),
        "op_time": dict(op_time),
        "module_events": {k: v["modules"] for k, v in used.items()},
        "host": host,
        "idle_by_span": dict(gaps),
    }


def _host_span_at(host: List[Dict], t: float) -> str:
    covering = {s["name"] for s in host if s["t0"] <= t < s["t1"]}
    for name in SPAN_ORDER:
        if name in covering:
            return name
    return "outside bench spans"


def device_time_in(red: Dict, span_name: str, module_match=None) -> List[Tuple[Dict, float]]:
    """Per host span named ``span_name`` in the window: the device seconds of
    the XLA modules that started inside it (only modules whose name
    contains ``module_match``, when given), on the first device."""
    if not red["devices"]:
        return []
    mods = red["module_events"][red["devices"][0]]
    out = []
    for s in red["host"]:
        if s["name"] != span_name:
            continue
        dt = sum(d for name, t, d in mods if s["t0"] <= t < s["t1"]
                 and (module_match is None or module_match in name))
        out.append((s, dt))
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
