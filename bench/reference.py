"""Plain reference of what the store must compute, independent of the program.

It imports nothing of ``repro``.  From the program it takes the results
under test and, for each read, the replica rows of its items as the store
held them when it answered (placement is an optimisation over diffused heat
that the reference does not redo; the map is the data the reads are
answered from).  Every other input is the benchmark's own: the
configuration's regions, the generated graph (or the graph an event source
made of it by then) and the pattern pool.

* :func:`components` — Definitions 1-2 from scratch: each cross-region edge
  in the RTT bucket of its region pair, and the region components per layer.
* :func:`route` — the stepwise layered router of the GeoLayer paper (Sec.
  VI, Fig. 5), one request at a time: serve locally, then per layer greedily
  take the cluster DC that covers the most missing items (lowest DC id on
  ties), escalate when no cluster DC covers anything; fold each serving DC's
  bytes into Eq. 1 (``RTT + bytes / bandwidth``, local serving free).

``dtype`` selects the arithmetic of the Eq. 1 fold.  The reference runs it
in float64; the configuration states float32 (the store's scalar router sums
item bytes in float32), so the correctness control runs it in bfloat16.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np


class Regions(NamedTuple):
    rtt_s: np.ndarray  # [D, D]
    bw_Bps: np.ndarray  # [D, D], +inf on the diagonal
    thresholds_s: List[float]  # layer bucket edges t_1 .. t_{h-1}

    @property
    def n_dcs(self) -> int:
        return self.rtt_s.shape[0]

    @property
    def n_layers(self) -> int:
        return len(self.thresholds_s) + 1


def regions(cfg: Dict) -> Regions:
    r = cfg["regions"]
    rtt = np.asarray(r["rtt_ms"], np.float64) / 1e3
    bw = np.asarray(r["bw_mbps"], np.float64) * 1e6 / 8.0
    bw[bw == 0] = np.inf
    interval = float(r["layer_interval_s"])
    h = max(1, int(np.ceil(float(rtt.max()) / interval + 1e-9)))
    return Regions(rtt, bw, [interval * k for k in range(1, h)])


# ------------------------------------------------------------ layered graph
def edge_layers(src_dc: np.ndarray, dst_dc: np.ndarray, alive: np.ndarray,
                reg: Regions) -> np.ndarray:
    """Layer of each edge: 0 within a region, 1..h by the RTT bucket
    ``[t_{i-1}, t_i)`` of its region pair, -1 for a dead edge."""
    t = np.asarray([0.0] + list(reg.thresholds_s) + [np.inf])
    lay = np.searchsorted(t, reg.rtt_s[src_dc, dst_dc], side="right")
    lay = np.clip(lay, 1, reg.n_layers)
    lay[src_dc == dst_dc] = 0
    lay[~alive] = -1
    return lay


def _components(n: int, pairs: np.ndarray) -> np.ndarray:
    """Component label per node, numbered by each component's smallest node."""
    root = np.arange(n)
    for a, b in pairs.tolist():
        ra, rb = root[a], root[b]
        if ra != rb:
            root[root == max(ra, rb)] = min(ra, rb)
    return np.unique(root, return_inverse=True)[1]


def components(src_dc: np.ndarray, dst_dc: np.ndarray, reg: Regions) -> np.ndarray:
    """Region component of each DC at each layer, ``[h + 1, D]``, over the
    edges whose endpoints sit in regions ``src_dc``/``dst_dc``: layer 0 is
    each region alone, layer i merges the components of layer i - 1 that an
    edge of layer i joins."""
    D = reg.n_dcs
    pairs = np.unique(np.asarray(src_dc, np.int64) * D + np.asarray(dst_dc, np.int64))
    a, b = pairs // D, pairs % D
    lay = edge_layers(a, b, np.ones(len(pairs), bool), reg)
    comp = np.zeros((reg.n_layers + 1, D), np.int64)
    comp[0] = np.arange(D)
    for i in range(1, reg.n_layers + 1):
        m = lay == i
        prev = comp[i - 1]
        pp = np.stack([prev[a[m]], prev[b[m]]], 1) if m.any() else np.zeros((0, 2), int)
        comp[i] = _components(int(prev.max()) + 1, pp)[prev]
    return comp


# ----------------------------------------------------------------- routing
class Route(NamedTuple):
    served: np.ndarray  # [K] serving DC per item, -1 unresolved
    layers_used: int
    latency_s: float
    wan_bytes: float


def route(items_delta: np.ndarray, sizes: np.ndarray, origin: int, comp: np.ndarray,
          reg: Regions, dtype=np.float64) -> Route:
    """Stepwise routing of one request over its items' replica rows
    ``items_delta [K, D]`` (bool) with item bytes ``sizes [K]``."""
    delta = np.asarray(items_delta, bool)
    served = np.full(delta.shape[0], -1, np.int64)
    served[delta[:, origin]] = origin
    layers_used = 0
    for layer in range(1, comp.shape[0]):
        if (served >= 0).all():
            break
        cluster = np.where(comp[layer] == comp[layer, origin])[0]
        cluster = cluster[cluster != origin]
        if len(cluster) == 0:
            continue
        layers_used = layer
        while True:
            missing = np.where(served < 0)[0]
            if len(missing) == 0:
                break
            cover = delta[missing][:, cluster].sum(axis=0)
            best = int(np.argmax(cover))
            if cover[best] == 0:
                break
            hit = missing[delta[missing, cluster[best]]]
            served[hit] = cluster[best]
    dt = np.dtype(dtype).type
    sz = np.asarray(sizes).astype(dt)
    lat = dt(0.0)
    wan = dt(0.0)
    for dc in np.unique(served[served >= 0]).tolist():
        s_d = dt(0.0)
        for x in sz[served == dc]:
            s_d = dt(s_d + x)
        if dc != origin:
            lat = max(lat, dt(dt(reg.rtt_s[dc, origin]) + dt(s_d / dt(reg.bw_Bps[dc, origin]))))
            wan = dt(wan + s_d)
    return Route(served, layers_used, float(lat), float(wan))
