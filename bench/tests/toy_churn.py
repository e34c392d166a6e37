"""A toy event source: topology churn applied through the store's
``apply_updates`` inside the window.  It serves the tests of the event-source
interface and a chip rehearsal of a churn cell; no cell names it.

In set-up, from the seed, it makes ``batches`` mutation batches with the
program's ``random_churn_batch`` over a shadow ``DeltaGraph`` of the
generated graph, each touching about ``rate`` of the alive edges, and keeps
the shadow's graph after each.  ``step`` applies the next batch once
``interval_s`` has passed since the window opened or the last batch ended
(0: closed loop, the next batch as soon as the last is done), and re-keys
the pattern pool through the batch's id growth (vertex arrivals shift every
edge item id), so that later reads name the same items.

Its check, ``topology_mismatches``: the store's graph at the close against
the shadow's after as many batches, counted over the graph's fields and the
store's tombstone ratio.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from bench.gen import GraphArrays

# the store applies each batch exactly: its graph at the close is the
# shadow's, field for field
TOPOLOGY_LIMIT = 0
FIELDS = ("src", "dst", "node_size", "edge_size", "partition")


def tiny(events: Dict) -> Dict:
    return dict(events, batches=3, rate=0.02)


def _tombstone_ratio(g: GraphArrays) -> float:
    alive = int(g.node_alive.sum()) + int(g.edge_alive.sum())
    total = g.n_nodes + len(g.src)
    return 1.0 - alive / max(total, 1)


class Source:
    def __init__(self, cfg: Dict, events: Dict, st) -> None:
        from repro.core.graph import Graph
        from repro.streaming import DeltaGraph, random_churn_batch

        g = st.graph
        dg = DeltaGraph(Graph(g.n_nodes, g.src.copy(), g.dst.copy(), g.node_size.copy(),
                              g.edge_size.copy(), g.partition.copy()))
        rng = np.random.default_rng(st.seed + 7)
        self.st = st
        self.batches, self.shifts = [], []  # shifts: (old n_nodes, new vertices)
        self.graphs = [g]
        for _ in range(int(events["batches"])):
            b = random_churn_batch(dg, float(events["rate"]), rng,
                                   vertex_fraction=float(events["vertex_fraction"]))
            res = dg.apply(b)
            self.batches.append(b)
            self.shifts.append((res.old_n_nodes, res.n_new_vertices))
            h = dg.g
            self.graphs.append(GraphArrays(h.n_nodes, h.src, h.dst, h.node_size, h.edge_size,
                                           h.partition, dg.node_alive, dg.edge_alive))
        self.interval_s = float(events["interval_s"])
        self.version = 0
        self.due = self.interval_s
        self.apply_s = []
        self.open_map = None

    def warm(self, store) -> None:
        """Builds the store's mutable overlay (an empty batch) before the
        window, and keeps the replica map at the window's open."""
        from repro.streaming import MutationBatch

        store.apply_updates(MutationBatch.empty())
        self.open_map = np.array(store.state.delta, bool)

    def step(self, store, now: float) -> None:
        if self.version == len(self.batches) or now < self.due:
            return
        t0 = time.perf_counter()
        store.apply_updates(self.batches[self.version])
        old_n, nv = self.shifts[self.version]
        self.st.pool = [p._replace(items=np.where(p.items < old_n, p.items, p.items + nv))
                        for p in self.st.pool]
        self.version += 1
        dt = time.perf_counter() - t0
        self.apply_s.append(dt)
        self.due = now + dt + self.interval_s

    def topology(self, version: int) -> GraphArrays:
        return self.graphs[version]

    def keep(self, store) -> Dict:
        g = store.g
        kept = {f: np.array(getattr(g, f)) for f in FIELDS}
        kept.update(n_nodes=int(g.n_nodes), tombstone_ratio=float(store.tombstone_ratio()))
        return kept

    def check(self, kept: Dict, st) -> Dict[str, Dict[str, float]]:
        want = self.topology(self.version)
        bad = sum(not (kept[f].shape == getattr(want, f).shape
                       and np.array_equal(kept[f], getattr(want, f))) for f in FIELDS)
        bad += kept["n_nodes"] != want.n_nodes
        if self.version:
            bad += kept["tombstone_ratio"] != _tombstone_ratio(want)
        return {"topology_mismatches": {"value": float(bad), "limit": TOPOLOGY_LIMIT}}

    def record(self) -> Dict:
        return {"applied": self.version, "apply_s": list(self.apply_s),
                "ops": [b.n_ops for b in self.batches[: self.version]]}
