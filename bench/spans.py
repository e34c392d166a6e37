"""The program's own spans in a profiler trace, and the device's idle time
split among them.

The store mirrors its live ``repro.obs`` spans into a running profiler as
annotations of the same name: ``serve.*`` (the admission controller's
step), ``store.*``, ``routing.*`` and ``demand.*``.  They lie on the
serving thread's host line, on the device ops' clock.  This module reads
them from the same ``.xplane.pb`` as ``tracefile``, over the traced window
``tracefile.reduce`` found, and gives:

* per span name, over the spans that start inside the window: how many,
  their mean duration, and their mean self time (duration minus the part
  of it that child spans cover);
* per ``serve.step``, the controller's own time: the step minus the spans
  of other layers inside it (the store's ``serve_batch``, the deposit);
* the device's idle time in the window, each instant of it charged to the
  innermost program span open then, or to none.  A gap of 20 ms that
  crosses five stages is split among the five.

Spans of one thread nest, so the innermost span at an instant is the one
opened last of those still open.

    python3 -m bench.spans <trace_dir>

prints the idle split and the per-span times of a recorded trace.
"""
from __future__ import annotations

import math
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import tracefile

PREFIXES = ("serve.", "store.", "routing.", "demand.")
STEP = "serve.step"
CONTROLLER = "serve."  # the layer of the step's own children


def of(ctx: Dict) -> Optional[Dict]:
    """The reduction of a traced run's program spans, memoised in the
    readers' ``ctx``; None where the trace holds none (a program without
    the mirror)."""
    if "program_spans" not in ctx:
        win, red = ctx["win"], ctx["trace"]
        ctx["program_spans"] = reduce(win.trace_dir, red) if win.trace_dir and red else None
    return ctx["program_spans"]


def reduce(trace_dir: str, red: Dict) -> Optional[Dict]:
    path = tracefile.find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), red)


def _program_spans(pd) -> List[Tuple[float, float, str]]:
    """``(t0, t1, name)`` of the program spans on the host line that holds
    the most of them: the serving thread."""
    best: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                     for e in line.events if e.name.startswith(PREFIXES)]
            if len(found) > len(best):
                best = found
    return best


def _busy(pd, device: str, w0: float, w1: float) -> List[Tuple[float, float]]:
    for plane in pd.planes:
        if plane.name != device:
            continue
        for line in plane.lines:
            if line.name == tracefile.OPS_LINE:
                return tracefile._union([
                    (max(e.start_ns * 1e-9, w0), min((e.start_ns + e.duration_ns) * 1e-9, w1))
                    for e in line.events
                    if (e.start_ns + e.duration_ns) * 1e-9 > w0 and e.start_ns * 1e-9 < w1])
    return []


def _idle(busy: List[Tuple[float, float]], w0: float, w1: float) -> List[Tuple[float, float]]:
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _segments(spans: List[Tuple[float, float, str]]) -> Tuple[List[tuple], List[int]]:
    """The timeline cut into pieces ``(a, b, i)``, each with the index of
    the innermost span open over it (-1 for none), and each span's parent
    index (-1 for a root)."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][0], -spans[i][1]))
    parent = [-1] * len(spans)
    segs: List[tuple] = []
    stack: List[int] = []
    t = -math.inf
    for i in order + [None]:
        nxt = spans[i][0] if i is not None else math.inf
        while stack and spans[stack[-1]][1] <= nxt:
            j = stack.pop()
            if spans[j][1] > t:
                segs.append((t, spans[j][1], j))
                t = spans[j][1]
        if i is None:
            break
        if nxt > t and t > -math.inf:
            segs.append((t, nxt, stack[-1] if stack else -1))
        t = max(t, nxt)
        parent[i] = stack[-1] if stack else -1
        stack.append(i)
    return segs, parent


def reduce_profile(pd, red: Optional[Dict]) -> Optional[Dict]:
    """The reduction, over the window of ``red`` (``tracefile``'s); None
    where the trace has no window, no device or no program span."""
    if not red or not red["host"] or not red["devices"]:
        return None
    spans = _program_spans(pd)
    if not spans:
        return None
    w0, w1 = red["host"][0]["t0"], max(s["t1"] for s in red["host"])
    segs, parent = _segments(spans)
    self_s = [0.0] * len(spans)
    for a, b, i in segs:
        if i >= 0:
            self_s[i] += b - a
    # the controller's own time per step: its self time and that of its
    # serve.* descendants
    controller: Dict[int, float] = defaultdict(float)
    for i, (_, _, name) in enumerate(spans):
        if not name.startswith(CONTROLLER):
            continue
        j = i
        while j >= 0 and spans[j][2] != STEP:
            j = parent[j]
        if j >= 0:
            controller[j] += self_s[i]
    inside = [w0 <= t0 < w1 for t0, _, _ in spans]
    per_name: Dict[str, Dict[str, list]] = defaultdict(lambda: {"dur_s": [], "self_s": []})
    for i, (t0, t1, name) in enumerate(spans):
        if inside[i]:
            per_name[name]["dur_s"].append(t1 - t0)
            per_name[name]["self_s"].append(self_s[i])
    idle = _idle(_busy(pd, red["devices"][0], w0, w1), w0, w1)
    idle_by: Dict[str, float] = defaultdict(float)
    k = 0
    for a, b in idle:
        while k < len(segs) and segs[k][1] <= a:
            k += 1
        m = k
        while m < len(segs) and segs[m][0] < b:
            s0, s1, i = segs[m]
            if i >= 0:
                idle_by[spans[i][2]] += min(b, s1) - max(a, s0)
            m += 1
    idle_s = sum(b - a for a, b in idle)
    return {
        "window_s": w1 - w0,
        "idle_s": idle_s,
        "idle_by_span": dict(idle_by),
        "idle_unattributed_s": idle_s - sum(idle_by.values()),
        "spans": {name: dict(d) for name, d in per_name.items()},
        "controller_s": [controller[i] for i in sorted(controller) if inside[i]],
    }


def mean_ms(red: Optional[Dict], name: str, key: str = "dur_s") -> Optional[float]:
    """Mean per span of ``name`` in ms (``key``: ``dur_s`` or ``self_s``)."""
    vals = red["spans"].get(name, {}).get(key) if red else None
    return 1e3 * sum(vals) / len(vals) if vals else None


def table(red: Dict) -> str:
    idle = red["idle_s"]
    rows = [f"window {red['window_s']:.6f} s, device idle {idle:.6f} s",
            f"{'span':<20} {'n':>6} {'mean ms':>10} {'self ms':>10} {'idle s':>10} {'idle %':>8}"]
    names = sorted(red["spans"], key=lambda n: -red["idle_by_span"].get(n, 0.0))
    for name in names:
        d = red["spans"][name]
        got = red["idle_by_span"].get(name, 0.0)
        rows.append(f"{name:<20} {len(d['dur_s']):>6} {mean_ms(red, name):>10.4f} "
                    f"{mean_ms(red, name, 'self_s'):>10.4f} {got:>10.6f} "
                    f"{100 * got / idle if idle else 0.0:>8.3f}")
    un = red["idle_unattributed_s"]
    rows.append(f"{'(no program span)':<20} {'':>6} {'':>10} {'':>10} {un:>10.6f} "
                f"{100 * un / idle if idle else 0.0:>8.3f}")
    ctl = red["controller_s"]
    if ctl:
        rows.append(f"controller per step: {1e3 * sum(ctl) / len(ctl):.4f} ms "
                    f"over {len(ctl)} steps")
    return "\n".join(rows)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m bench.spans <trace_dir>", file=sys.stderr)
        return 2
    red = reduce(argv[0], tracefile.reduce(argv[0]))
    if red is None:
        print(f"no trace with program spans under {argv[0]}", file=sys.stderr)
        return 1
    print(table(red))
    return 0


if __name__ == "__main__":
    sys.exit(main())
