"""Yardstick generators: graphs, pattern pools, requests, arrivals.

Everything the benchmark feeds the store is made from ``--seed``, here or in
a graph generator file ``bench/graphs/<generator>.py``, so a later change to
the program's own generators cannot change the work a cell measures.
``generate_khop_patterns`` (and ``bench/graphs/community_graph.py``) are
copies of the generators in ``repro.core.patterns`` (and
``repro.data.synthetic``) as they stood when this benchmark was written;
``bench/tests/test_bench_gen.py`` holds each copy to its original bit for
bit at a small seed.  Graphs and patterns are returned as plain arrays and
tuples; ``bench.harness`` wraps them in the program's types.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from bench import files


class GraphArrays(NamedTuple):
    """A geo-partitioned graph. Item ids: vertex v -> v, edge e -> n_nodes + e."""

    n_nodes: int
    src: np.ndarray  # [m] int32
    dst: np.ndarray  # [m] int32
    node_size: np.ndarray  # [n] float32 bytes
    edge_size: np.ndarray  # [m] float32 bytes
    partition: np.ndarray  # [n] int32 home DC
    # tombstones of a graph that events have changed; None: every item alive
    node_alive: Optional[np.ndarray] = None  # [n] bool
    edge_alive: Optional[np.ndarray] = None  # [m] bool

    def alive_items(self) -> np.ndarray:
        """``[n + m]`` bool: which item ids are alive."""
        m = len(self.src)
        return np.concatenate([
            np.ones(self.n_nodes, bool) if self.node_alive is None else self.node_alive,
            np.ones(m, bool) if self.edge_alive is None else self.edge_alive,
        ])


class PatternArrays(NamedTuple):
    pid: int
    items: np.ndarray  # sorted unique item ids
    r_py: np.ndarray  # [D] read frequency per origin DC
    w_py: np.ndarray  # [D] write frequency per origin DC
    eta: float


def graph_arrays(n, src, dst, node_size, edge_size, partition) -> GraphArrays:
    """A generated graph in the benchmark's dtypes, every item alive."""
    return GraphArrays(
        int(n), np.asarray(src, np.int32), np.asarray(dst, np.int32),
        np.asarray(node_size, np.float32), np.asarray(edge_size, np.float32),
        np.asarray(partition, np.int32),
    )


def make_graph(spec: Dict, seed: int, n_dcs: int) -> GraphArrays:
    """The graph a configuration's ``graph`` entry describes, made by the
    generator file ``bench/graphs/<generator>.py``."""
    return files.load("graphs", spec["generator"]).make(spec, seed, n_dcs)


# ---------------------------------------------------------------- patterns
def _out_edge_csr(n_nodes: int, src: np.ndarray, dst: np.ndarray):
    """(indptr, neighbour, edge id) over out-edges, stably sorted by source."""
    src = np.asarray(src, dtype=np.int64)
    order = np.argsort(src, kind="stable")
    counts = np.bincount(src[order], minlength=n_nodes)
    indptr = np.zeros(n_nodes + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    nbr = np.asarray(dst, dtype=np.int64)[order].astype(np.int32)
    eid = np.arange(len(src), dtype=np.float32)[order]
    return indptr, nbr, eid


def generate_khop_patterns(
    g: GraphArrays, n_patterns: int, hops: int = 3, branch: int = 2, seed: int = 0,
    write_fraction: float = 0.3, freq_zipf_a: float = 1.4,
    eta_choices: Sequence[float] = (0.25, 0.5, 0.75, 1.0),
    n_dcs: Optional[int] = None, n_hot_sources: Optional[int] = None,
) -> List[PatternArrays]:
    """K-hop random-walk patterns with Zipf-skewed sources over a hot core."""
    rng = np.random.default_rng(seed)
    D = n_dcs if n_dcs is not None else int(g.partition.max()) + 1
    ranks = rng.permutation(g.n_nodes) + 1
    popularity = 1.0 / ranks.astype(np.float64) ** freq_zipf_a
    if n_hot_sources is not None and n_hot_sources < g.n_nodes:
        hot = np.argsort(ranks)[:n_hot_sources]
        mask = np.zeros(g.n_nodes)
        mask[hot] = 1.0
        popularity = popularity * mask
    popularity /= popularity.sum()
    indptr, nbr, eid_w = _out_edge_csr(g.n_nodes, g.src, g.dst)

    patterns: List[PatternArrays] = []
    for pid in range(n_patterns):
        v0 = int(rng.choice(g.n_nodes, p=popularity))
        verts = {v0}
        edges: set = set()
        frontier = [v0]
        for _ in range(hops):
            nxt: List[int] = []
            for u in frontier:
                lo, hi = int(indptr[u]), int(indptr[u + 1])
                deg = hi - lo
                if deg == 0:
                    continue
                k = min(branch, deg)
                sel = rng.choice(deg, size=k, replace=False)
                for s in sel:
                    v = int(nbr[lo + s])
                    e = int(eid_w[lo + s])
                    edges.add(e)
                    if v not in verts:
                        verts.add(v)
                        nxt.append(v)
            frontier = nxt
            if not frontier:
                break
        items = np.concatenate([
            np.fromiter(verts, dtype=np.int64, count=len(verts)),
            g.n_nodes + np.fromiter(edges, dtype=np.int64, count=len(edges)),
        ])
        origin = int(g.partition[v0])
        r_py = np.zeros(D)
        base = float(1 + rng.poisson(4) + 40 * popularity[v0] * g.n_nodes / 10)
        r_py[origin] = base
        if rng.random() < 0.35 and D > 1:
            other = int(rng.choice([d for d in range(D) if d != origin]))
            r_py[other] = max(1.0, base * rng.uniform(0.2, 0.8))
        w_py = np.zeros(D)
        if rng.random() < write_fraction:
            w_py[origin] = base * rng.uniform(0.05, 0.3)
        eta = float(rng.choice(np.asarray(eta_choices)))
        patterns.append(PatternArrays(pid, np.unique(items), r_py, w_py, eta))
    return patterns


def pattern_pool(g: GraphArrays, spec: Dict, seed: int, n_dcs: int) -> List[PatternArrays]:
    """The pool a configuration's ``pool`` entry describes: ``n_patterns``
    patterns, each width class of ``widths`` (share, hops, branch) holding
    its share of them in an order drawn from the seed.  Every width class
    draws its sources from one generator seed, so all classes share the
    same Zipf hot core."""
    rng = np.random.default_rng(seed)
    shares = np.asarray([w["share"] for w in spec["widths"]], np.float64)
    cls = rng.permutation(exact_counts(shares, spec["n_patterns"]))
    n_hot = max(24, g.n_nodes // spec["hot_core_divisor"])
    by_class = [
        generate_khop_patterns(
            g, int((cls == c).sum()), hops=w["hops"], branch=w["branch"], seed=seed + 1,
            freq_zipf_a=spec["zipf_a"], n_dcs=n_dcs, n_hot_sources=n_hot,
        )
        for c, w in enumerate(spec["widths"])
    ]
    taken = [0] * len(by_class)
    pool = []
    for pid, c in enumerate(cls.tolist()):
        p = by_class[c][taken[c]]
        taken[c] += 1
        pool.append(p._replace(pid=pid))
    return pool


# --------------------------------------------------------------- requests
class Requests(NamedTuple):
    """An open-loop request stream: arrival second, pattern index, origin DC."""

    t: np.ndarray  # [N] float64, sorted, in [0, seconds)
    pattern: np.ndarray  # [N] int64 index into the pool
    origin: np.ndarray  # [N] int64


def exact_counts(shares: np.ndarray, n: int) -> np.ndarray:
    """``n`` class labels, class ``c`` taking ``shares[c]`` of them (largest
    remainders round), sorted by class."""
    want = np.asarray(shares, np.float64) / np.sum(shares) * n
    counts = np.floor(want).astype(np.int64)
    counts[np.argsort(counts - want)[: n - counts.sum()]] += 1
    return np.repeat(np.arange(len(counts)), counts)


def poisson_arrivals(rate_per_s: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """``rate_per_s * seconds`` arrival instants over ``[0, seconds)``, the
    first at 0, whose gaps are the exponential distribution's quantiles at
    the midpoints of ``n`` equal slices, scaled to fill the window, in an
    order drawn from ``rng``: a Poisson process's gaps, with the same set of
    gaps for every seed, so that seeds change the order of the work and not
    its amount."""
    n = int(round(rate_per_s * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    t = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
    return np.minimum(t, np.nextafter(seconds, 0.0))


def request_stream(pool: Sequence[PatternArrays], rate_per_s: float, seconds: float,
                   home_share: float, n_dcs: int, seed: int,
                   opening_backlog: int = 0) -> Requests:
    """Poisson arrivals, after ``opening_backlog`` requests that are all due
    at the window's first instant.  Every pattern of the pool is read
    equally often (the remainder drawn without repeats), ``home_share`` of
    the requests from their pattern's home DC and the rest from the DCs in
    turn, each in an order drawn from the seed."""
    rng = np.random.default_rng(seed)
    t = np.concatenate([np.zeros(opening_backlog),
                        poisson_arrivals(rate_per_s, seconds, rng)])
    n = len(t)
    P = len(pool)
    pat = np.concatenate([np.tile(np.arange(P), n // P),
                          rng.choice(P, size=n % P, replace=False)])
    pat = rng.permutation(pat)
    home = np.asarray([int(np.argmax(p.r_py)) for p in pool], np.int64)
    local = rng.permutation(exact_counts([1.0 - home_share, home_share], n).astype(bool))
    away = rng.permutation(np.resize(np.arange(n_dcs), n))
    origin = np.where(local, home[pat], away)
    return Requests(t, pat.astype(np.int64), origin.astype(np.int64))
