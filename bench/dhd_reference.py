"""Plain reference of the store's warm heat solve: directed heat diffusion
(GeoLayer paper Eqs. 7-8) relaxed by Jacobi sweeps over an undirected edge
list, in numpy, independent of the program.

One sweep over the alive edges, each taken in both directions: a vertex u
sends ``alpha * w * (H_u - H_v) / |N_u^out|`` to each strictly cooler
neighbour v, where ``|N_u^out|`` counts u's strictly cooler neighbours (at
least 1), and ``H <- (1 - gamma) * (H + inflow - outflow) + beta * Q``.
``alpha`` is the configured cap clamped into the contraction regime,
``0.5 * gamma / ((1 - gamma) * (max_e w_e + max_u sum_{e at u} w_e))``, so
the relaxation has one fixed point.

The relaxation stops as the store's stated setting says: after a sweep
that moves no vertex by ``tol`` or after ``max_sweeps``.  A batch first
relaxes its frontier alone (the touched vertices and their neighbours,
every other vertex held) when both the touched set and the frontier are at
most ``max_frontier_share`` of the vertices, for at most ``frontier_sweeps``
sweeps, then the whole graph.

The field is kept in float32 between sweeps, as the configuration states;
``dtype`` puts another precision in its place (the control).
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def _alpha(n: int, u: np.ndarray, w: np.ndarray, heat: Dict) -> float:
    wdeg = np.bincount(u, weights=w, minlength=n).max(initial=0.0)
    bound = float(w.max(initial=0.0)) + float(wdeg)
    if bound <= 0.0:
        return float(heat["alpha_cap"])
    g = float(heat["gamma"])
    return min(float(heat["alpha_cap"]), 0.5 * g / ((1.0 - g) * bound))


def sweep(h: np.ndarray, u: np.ndarray, v: np.ndarray, w: np.ndarray, q: np.ndarray,
          alpha: float, gamma: float, beta: float) -> np.ndarray:
    """One DHD update over directed halves ``u -> v`` with weights ``w``."""
    n = len(h)
    hu, hv = h[u].astype(np.float64), h[v].astype(np.float64)
    out = hu > hv
    n_out = np.maximum(np.bincount(u[out], minlength=n), 1)
    flow = alpha * w[out] * (hu[out] - hv[out]) / n_out[u[out]]
    delta = np.bincount(v[out], weights=flow, minlength=n) - np.bincount(
        u[out], weights=flow, minlength=n)
    return (1.0 - gamma) * (h.astype(np.float64) + delta) + beta * q


def relax(n: int, src: np.ndarray, dst: np.ndarray, w: np.ndarray, q: np.ndarray,
          h0: Optional[np.ndarray], touched: Optional[np.ndarray], heat: Dict,
          dtype=np.float32) -> Dict:
    """The field after one batch: from ``h0`` (the field before it, grown
    with zeros to ``n`` vertices; None: a cold start from ``q``) over the
    alive edges ``src``/``dst`` with weights ``w`` and sources ``q``.
    Returns the field and the sweeps run in each phase."""
    u = np.concatenate([src, dst]).astype(np.int64)
    v = np.concatenate([dst, src]).astype(np.int64)
    ww = np.concatenate([w, w]).astype(np.float64)
    q = np.asarray(q, np.float64)
    alpha = _alpha(n, u, ww, heat)
    gamma, beta, tol = float(heat["gamma"]), float(heat["beta"]), float(heat["tol"])

    def cast(x):
        return np.asarray(x).astype(dtype).astype(np.float64)

    h = cast(q if h0 is None else h0)
    share = float(heat["max_frontier_share"])
    local = 0
    if h0 is not None and touched is not None and 0 < len(touched) <= share * n:
        front = np.zeros(n, bool)
        front[touched] = True
        for _ in range(int(heat["halo_hops"])):
            front[v[front[u]]] = True
        if front.sum() <= share * n:
            for local in range(1, int(heat["frontier_sweeps"]) + 1):
                nh = np.where(front, cast(sweep(h, u, v, ww, q, alpha, gamma, beta)), h)
                moved = np.abs(nh - h).max(initial=0.0)
                h = nh
                if moved < tol:
                    break
    glob = 0
    for glob in range(1, int(heat["max_sweeps"]) + 1):
        nh = cast(sweep(h, u, v, ww, q, alpha, gamma, beta))
        moved = np.abs(nh - h).max(initial=0.0)
        h = nh
        if moved < tol:
            break
    return {"heat": h, "frontier_sweeps": local, "sweeps": glob, "alpha": alpha}


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Relative sup-norm gap: ``max |got - want| / max |want|``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    scale = float(np.abs(want).max(initial=0.0))
    return float(np.abs(got - want).max(initial=0.0) / scale) if scale > 0 else 0.0
