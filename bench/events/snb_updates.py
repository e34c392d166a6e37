"""LDBC SNB Interactive's update stream, projected onto the person-knows
graph the store holds: IU1 (add person, with 1-3 knows edges), IU8 (add
friendship), DEL1 (remove person, with its knows edges) and DEL8 (remove
friendship), issued open-loop at fixed instants through the store's client
API ``GeoGraphStore.apply_updates``.

In set-up, from the seed, it makes every batch the window can take on a
numpy shadow of the topology of its own (the configuration's ``updates``
give the batch size and mix), and keeps the shadow after each batch.  Batch
``k`` (1, 2, ...) is due at ``k * interval_s`` after the window opens, and
none is due in the window's last ``interval_s``.  Vertex arrivals shift
every edge item id, so the pattern pool is re-keyed after each batch and
later reads name the same items.

``warm`` builds the store's mutable overlay with an empty batch and
compiles its update programs ahead (``prepare_updates``): no batch compiles
in the window.  A program without ``prepare_updates`` cannot give that, and
the source refuses it in set-up.

Its checks, each a constant of this file with its reason:

* ``topology_mismatches``: the store's graph at the close against the
  shadow's, field by field, with the tombstones and the tombstone ratio;
* ``layered_graph_mismatches``: layers whose region components (the
  store's repaired ``lg.comp_of_dc``) differ, as partitions, from those of
  the shadow's alive edges built from scratch (``bench/reference.py``);
* ``primary_copies_new_missing``: items made in the window and alive at
  the close without their primary copy; ``dead_item_copies``: copies held
  of items removed in the window;
* ``warm_heat_rel_gap``: the store's heat field after the last batch
  applied against the plain float32 relaxation (``bench/dhd_reference.py``)
  from the field the store held before that batch, over the shadow's alive
  edges, with the store's edge weights and sources, relative sup-norm.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from bench import dhd_reference, reference
from bench.gen import GraphArrays

# the store applies each batch exactly: its graph at the close is the
# stream's, field for field
TOPOLOGY_LIMIT = 0
# layered-graph repair is exact: each layer's region partition equals a
# fresh build's
LAYERED_LIMIT = 0
# every item made in the window gets its primary copy when it is made, and
# a removed item's copies are purged with it
PRIMARY_NEW_LIMIT = 0
DEAD_COPIES_LIMIT = 0
# float32 relaxations that differ only in the order of their sums: set from
# chip readings (PERF.md); a skipped warm solve and a bfloat16 field each
# read at least ten times over it
WARM_HEAT_LIMIT = 1e-5
FIELDS = ("src", "dst", "node_size", "edge_size", "partition", "node_alive", "edge_alive")


def tiny(events: Dict) -> Dict:
    """Batches due every 0.4 s of a 2 s CPU rehearsal window, each touching
    2% of the alive knows edges (0.1% of a tiny graph is one edge)."""
    return dict(events, interval_s=0.4, knows_share_per_batch=0.02)


def batch_count(seconds: float, interval_s: float) -> int:
    """Batches due at ``k * interval_s``, ``k = 1 .. floor(seconds / interval_s) - 1``."""
    return max(0, int(math.floor(seconds / interval_s + 1e-9)) - 1)


class Shadow:
    """The topology as the update stream leaves it, in numpy: append-only
    ids, tombstones for removals (a removed person's edges die with it)."""

    def __init__(self, g: GraphArrays) -> None:
        self.n = int(g.n_nodes)
        self.src, self.dst = g.src.astype(np.int32), g.dst.astype(np.int32)
        self.node_size, self.edge_size = g.node_size, g.edge_size
        self.partition = g.partition.astype(np.int32)
        self.node_alive = np.ones(self.n, bool) if g.node_alive is None else g.node_alive.copy()
        self.edge_alive = (np.ones(len(self.src), bool) if g.edge_alive is None
                           else g.edge_alive.copy())

    def graph(self) -> GraphArrays:
        return GraphArrays(self.n, self.src, self.dst, self.node_size, self.edge_size,
                           self.partition, self.node_alive.copy(), self.edge_alive.copy())

    def apply(self, b) -> Dict:
        """Apply one ``MutationBatch``; returns what the checks need of it."""
        old_n, old_m = self.n, len(self.src)
        nv, ne = len(b.add_vertex_size), len(b.add_edge_src)
        self.n = old_n + nv
        self.node_size = np.concatenate([self.node_size, b.add_vertex_size.astype(np.float32)])
        self.partition = np.concatenate([self.partition, b.add_vertex_partition.astype(np.int32)])
        self.node_alive = np.concatenate([self.node_alive, np.ones(nv, bool)])
        self.src = np.concatenate([self.src, b.add_edge_src.astype(np.int32)])
        self.dst = np.concatenate([self.dst, b.add_edge_dst.astype(np.int32)])
        self.edge_size = np.concatenate([self.edge_size, b.add_edge_size.astype(np.float32)])
        alive_before = self.edge_alive
        self.edge_alive = np.concatenate([self.edge_alive, np.ones(ne, bool)])
        self.edge_alive[b.del_edge_ids] = False
        self.node_alive[b.del_vertex_ids] = False
        gone = np.zeros(self.n, bool)
        gone[b.del_vertex_ids] = True
        self.edge_alive &= ~(gone[self.src] | gone[self.dst])
        was = np.concatenate([alive_before, np.ones(ne, bool)])
        dead_e = np.where(was & ~self.edge_alive)[0]
        new_v = old_n + np.arange(nv)
        new_e = old_m + np.arange(ne)
        mutated = np.concatenate([new_e, dead_e])
        touched = np.unique(np.concatenate([self.src[mutated], self.dst[mutated], new_v,
                                            b.del_vertex_ids]).astype(np.int64))
        return {"old_n": old_n, "nv": nv, "touched": touched, "new_v": new_v, "new_e": new_e,
                "dead_v": np.asarray(b.del_vertex_ids, np.int64), "dead_e": dead_e}


def make_batch(sh: Shadow, upd: Dict, share: float, rng: np.random.Generator):
    """One batch of the stream over the shadow's present topology, with
    knows inserts and deletes of ``share`` of the alive knows edges each."""
    from repro.streaming import MutationBatch

    alive_v = np.where(sh.node_alive)[0]
    alive_e = np.where(sh.edge_alive)[0]
    n_e = max(1, int(round(share * len(alive_e))))
    n_v = max(1, int(round(float(upd["person_share_of_knows"]) * n_e)))
    # DEL1: persons leave (keeping enough behind), DEL8: knows edges end
    leave = (np.sort(rng.choice(alive_v, size=n_v, replace=False))
             if len(alive_v) > 8 * n_v else np.zeros(0, np.int64))
    del_e = np.sort(rng.choice(alive_e, size=min(n_e, len(alive_e)), replace=False))
    stay = np.setdiff1d(alive_v, leave)
    # IU1: persons arrive, each in the region of a drawn alive person, and
    # know 1-3 persons who stay
    lo, hi = upd["knows_per_new_person"]
    new_ids = sh.n + np.arange(n_v)
    k_new = rng.integers(int(lo), int(hi) + 1, size=n_v)
    owner = np.repeat(new_ids, k_new)
    peer = rng.choice(stay, size=len(owner))
    flip = rng.random(len(owner)) < 0.5
    a_src = np.where(flip, peer, owner)
    a_dst = np.where(flip, owner, peer)
    # IU8: friendships between persons who stay, none that exists already
    # or twice in the batch
    u = rng.choice(stay, size=2 * n_e)
    v = rng.choice(stay, size=2 * n_e)
    n_all = sh.n + n_v
    key = u.astype(np.int64) * n_all + v
    have = sh.src[alive_e].astype(np.int64) * n_all + sh.dst[alive_e]
    ok = (u != v) & ~np.isin(key, have) & ~np.isin(v.astype(np.int64) * n_all + u, have)
    _, first = np.unique(key, return_index=True)
    once = np.zeros(len(key), bool)
    once[first] = True
    pick = np.where(ok & once)[0][:n_e]
    src = np.concatenate([a_src, u[pick]]).astype(np.int64)
    dst = np.concatenate([a_dst, v[pick]]).astype(np.int64)
    pb, kb = upd["person_bytes"], upd["knows_bytes"]
    return MutationBatch(
        add_vertex_size=rng.lognormal(np.log(pb["lognormal_median"]), pb["sigma"],
                                      n_v).astype(np.float32),
        add_vertex_partition=sh.partition[rng.choice(alive_v, size=n_v)].astype(np.int32),
        del_vertex_ids=leave.astype(np.int64),
        add_edge_src=src,
        add_edge_dst=dst,
        add_edge_size=rng.lognormal(np.log(kb["lognormal_median"]), kb["sigma"],
                                    len(src)).astype(np.float32),
        del_edge_ids=del_e.astype(np.int64),
    )


class Source:
    def __init__(self, cfg: Dict, events: Dict, st) -> None:
        from repro.core.store import GeoGraphStore

        if not hasattr(GeoGraphStore, "prepare_updates"):
            raise SystemExit("snb_updates: the store has no prepare_updates(), so its update "
                             "programs would compile inside the window; refusing the run")
        self.st = st
        self.upd = cfg["updates"]
        self.reg = reference.regions(cfg)
        self.interval_s = float(events["interval_s"])
        share = float(events.get("knows_share_per_batch", self.upd["knows_share_per_batch"]))
        rng = np.random.default_rng(st.seed + 7)
        sh = self._start()
        for _ in range(batch_count(st.seconds, self.interval_s)):
            self._add(sh, make_batch(sh, self.upd, share, rng))
        self.version = 0
        self.apply_s: List[float] = []
        self.late_s: List[float] = []  # start of each batch past its due instant
        self.sweeps: List[tuple] = []  # (frontier, global) sweeps the store reports
        self.heat_before = None  # the store's field before the last batch applied

    def _start(self) -> Shadow:
        sh = Shadow(self.st.graph)
        self.batches: List = []
        self.applied: List[Dict] = []  # what each batch did to the shadow
        self.graphs = [sh.graph()]  # the shadow after each batch
        return sh

    def _add(self, sh: Shadow, b) -> None:
        self.batches.append(b)
        self.applied.append(sh.apply(b))
        self.graphs.append(sh.graph())

    def replay(self, batches: List) -> None:
        """Take ``batches`` (a test's own) in place of the generated ones."""
        sh = self._start()
        for b in batches:
            self._add(sh, b)

    def warm(self, store) -> None:
        from repro.streaming import MutationBatch

        if store.compact_ratio != float(self.upd["compact_ratio"]):
            raise SystemExit(f"snb_updates: the store compacts at {store.compact_ratio}, "
                             f"the deployment at {self.upd['compact_ratio']}")
        self.programs = store.prepare_updates()
        store.apply_updates(MutationBatch.empty())

    def step(self, store, now: float) -> None:
        k = self.version
        if k == len(self.batches) or now < (k + 1) * self.interval_s:
            return
        heat = store._heat
        self.heat_before = (None if heat is None or heat.heat is None
                            else np.array(heat.vertex_heat, np.float32))
        t0 = time.perf_counter()
        rep = store.apply_updates(self.batches[k])
        old_n, nv = self.applied[k]["old_n"], self.applied[k]["nv"]
        self.st.pool = [p._replace(items=np.where(p.items < old_n, p.items, p.items + nv))
                        for p in self.st.pool]
        self.version += 1
        self.apply_s.append(time.perf_counter() - t0)
        self.late_s.append(now - (k + 1) * self.interval_s)
        self.sweeps.append((int(rep.heat.local_iters), int(rep.heat.global_iters)))

    def topology(self, version: int) -> GraphArrays:
        return self.graphs[version]

    def keep(self, store) -> Dict:
        g, dg = store.g, store._delta_graph
        kept = {f: np.array(getattr(g, f)) for f in FIELDS[:5]}
        kept.update(n_nodes=int(g.n_nodes), tombstone_ratio=float(store.tombstone_ratio()),
                    node_alive=np.array(dg.node_alive), edge_alive=np.array(dg.edge_alive),
                    comp_of_dc=np.array(store.lg.comp_of_dc))
        if self.version and store._heat is not None:
            alive_e, w_e, q = store._heat_inputs()
            kept.update(heat=np.array(store._heat.vertex_heat, np.float64),
                        alive_e=np.array(alive_e), w_e=np.array(w_e), q=np.array(q))
        return kept

    # ------------------------------------------------------------- checks
    def heat_reference(self, kept: Dict, dtype=np.float32) -> Dict:
        """The plain relaxation of the last batch applied (``dtype``: the
        field's precision between sweeps)."""
        g = self.topology(self.version)
        if len(kept["alive_e"]) and kept["alive_e"].max() >= len(g.src):
            return {"heat": None}  # not this topology's edges: the topology check fails
        w = np.zeros(len(g.src))
        w[kept["alive_e"]] = kept["w_e"]
        alive = g.edge_alive
        h0 = None
        if self.heat_before is not None:
            h0 = np.zeros(g.n_nodes)
            h0[: len(self.heat_before)] = self.heat_before
        return dhd_reference.relax(g.n_nodes, g.src[alive], g.dst[alive], w[alive], kept["q"],
                                   h0, self.applied[self.version - 1]["touched"],
                                   self.upd["heat"], dtype=dtype)

    def check(self, kept: Dict, st) -> Dict[str, Dict[str, float]]:
        want = self.topology(self.version)
        bad = sum(not (kept[f].shape == getattr(want, f).shape
                       and np.array_equal(kept[f], getattr(want, f))) for f in FIELDS)
        bad += kept["n_nodes"] != want.n_nodes
        total = want.n_nodes + len(want.src)
        ratio = 1.0 - (int(want.node_alive.sum()) + int(want.edge_alive.sum())) / total
        bad += self.version > 0 and kept["tombstone_ratio"] != ratio
        # layered graph: each layer's region partition against a fresh build
        alive = want.edge_alive
        comp = reference.components(want.partition[want.src[alive]],
                                    want.partition[want.dst[alive]], self.reg)
        got = kept["comp_of_dc"]
        if got.shape != comp.shape:
            lg_bad = comp.shape[0]
        else:
            lg_bad = sum(not _same_partition(a, b) for a, b in zip(got, comp))
        # primary copies of the items made in the window, and none of the removed
        rep = st.replicas
        items = want.alive_items()
        primary = np.concatenate([want.partition, want.partition[want.src]]).astype(np.int64)
        made = _items(self.applied[: self.version], "new_v", "new_e", want.n_nodes)
        made = made[items[made]]
        dead = _items(self.applied[: self.version], "dead_v", "dead_e", want.n_nodes)
        if rep is None or rep.shape[0] != total:
            new_missing, dead_copies = float(len(made)), float(len(dead))
        else:
            new_missing = float((~rep[made, primary[made]]).sum())
            dead_copies = float(rep[dead].sum())
        gap = 0.0
        if self.version:
            ref = self.heat_reference(kept)["heat"]
            gap = math.inf if ref is None else dhd_reference.rel_gap(kept["heat"], ref)
        return {
            "topology_mismatches": {"value": float(bad), "limit": TOPOLOGY_LIMIT},
            "layered_graph_mismatches": {"value": float(lg_bad), "limit": LAYERED_LIMIT},
            "primary_copies_new_missing": {"value": new_missing, "limit": PRIMARY_NEW_LIMIT},
            "dead_item_copies": {"value": dead_copies, "limit": DEAD_COPIES_LIMIT},
            "warm_heat_rel_gap": {"value": gap, "limit": WARM_HEAT_LIMIT},
        }

    def record(self) -> Dict:
        return {"applied": self.version, "due": len(self.batches),
                "apply_s": list(self.apply_s), "late_s": list(self.late_s),
                "ops": [b.n_ops for b in self.batches[: self.version]],
                "sweeps": list(self.sweeps), "programs": getattr(self, "programs", None)}


def _items(applied: List[Dict], vkey: str, ekey: str, n_nodes: int) -> np.ndarray:
    """Item ids at the close (vertex v -> v, edge e -> n_nodes + e) of the
    vertices and edges the batches list under ``vkey`` and ``ekey``."""
    v = [a[vkey] for a in applied] + [np.zeros(0, np.int64)]
    e = [a[ekey] for a in applied] + [np.zeros(0, np.int64)]
    return np.concatenate([np.concatenate(v), n_nodes + np.concatenate(e)]).astype(np.int64)


def _same_partition(a: np.ndarray, b: np.ndarray) -> bool:
    """Whether two labelings of the same DCs group them alike."""
    pairs = np.unique(np.stack([a, b], 1), axis=0)
    return len(pairs) == len(np.unique(a)) == len(np.unique(b))
