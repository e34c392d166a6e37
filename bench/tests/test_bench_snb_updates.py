"""The ``snb_updates`` event source of ``snb.churn`` at a tiny size: its
shadow topology follows the store batch by batch, its plain heat reference
follows the program's DHD step, and each of its checks catches the fault
planted for it."""
import dataclasses
import json
import pathlib
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import dhd_reference, files, gen, harness, reference
from bench.tests.tiny import run_tiny, tiny_cell

SEED = 2**31 + 181
REPO = pathlib.Path(__file__).resolve().parents[2]


def _source():
    return files.load("events", "snb_updates")


@pytest.fixture(scope="module")
def churn_run():
    keep = {}
    _, res = run_tiny("snb.churn", seed=SEED, keep=keep)
    return res, keep


def test_churn_run_is_correct_and_applies_every_batch(churn_run):
    res, keep = churn_run
    st, win = keep["st"], keep["win"]
    assert res["correct"], res["checks"]
    rec = st.events.record()
    assert rec["applied"] == rec["due"] == 4 and all(rec["ops"])
    assert win.compiles == 0
    lim = _source().WARM_HEAT_LIMIT
    assert res["checks"]["warm_heat_rel_gap"] == {
        "value": res["checks"]["warm_heat_rel_gap"]["value"], "limit": lim}
    assert res["checks"]["warm_heat_rel_gap"]["value"] < lim
    # the reference ran the sweeps the store reports for the last batch
    ref = st.events.heat_reference(win.events_kept)
    assert (ref["frontier_sweeps"], ref["sweeps"]) == tuple(rec["sweeps"][-1])
    versions = {s[4] for s in win.samples.values()}
    assert min(versions) == 0 and max(versions) == 4


def test_bfloat16_field_fails_the_heat_check(churn_run):
    """The control: the reference with its field in bfloat16 between
    sweeps, in the program's place, reads over the limit."""
    import ml_dtypes

    _, keep = churn_run
    st, win = keep["st"], keep["win"]
    f32 = st.events.heat_reference(win.events_kept)["heat"]
    bf16 = st.events.heat_reference(win.events_kept, dtype=ml_dtypes.bfloat16)["heat"]
    assert dhd_reference.rel_gap(bf16, f32) > 10 * _source().WARM_HEAT_LIMIT


def _stepped_setup(seed):
    cell, cfg, traffic = tiny_cell("snb.churn")
    st = harness.Setup(cfg, traffic, seed, 2.0)
    st.build_store()
    st.events.warm(st.store)
    return st


def test_shadow_equals_the_store_after_every_batch():
    st = _stepped_setup(SEED + 1)
    src = st.events
    assert len(src.batches) == 4
    for k in range(len(src.batches)):
        src.step(st.store, (k + 1) * src.interval_s)
        assert src.version == k + 1
        st.replicas = np.array(st.store.state.delta)
        checks = src.check(src.keep(st.store), st)
        assert all(c["value"] <= c["limit"] for c in checks.values()), (k, checks)
        g = src.topology(k + 1)
        assert np.array_equal(st.store.g.src, g.src) and st.store.g.n_nodes == g.n_nodes
        assert np.array_equal(st.store._delta_graph.edge_alive, g.edge_alive)


def test_a_batch_holds_the_configured_mix():
    cell, cfg, traffic = tiny_cell("snb.churn")
    mod = _source()
    st = harness.Setup(cfg, traffic, SEED + 2, 2.0)
    b = st.events.batches[0]
    g = st.graph
    n_e = int(round(0.02 * len(g.src)))
    assert len(b.del_edge_ids) == n_e and len(np.unique(b.del_edge_ids)) == n_e
    n_v = max(1, int(round(0.1 * n_e)))
    assert len(b.add_vertex_size) == len(b.del_vertex_ids) == n_v
    new = np.arange(g.n_nodes, g.n_nodes + n_v)
    per_new = [int(np.isin(b.add_edge_src, v).sum() + np.isin(b.add_edge_dst, v).sum())
               for v in new]
    assert all(1 <= k <= 3 for k in per_new)
    assert len(b.add_edge_src) - sum(per_new) <= n_e
    # no new edge touches a person leaving in the batch, none repeats a pair
    assert not np.isin(np.concatenate([b.add_edge_src, b.add_edge_dst]), b.del_vertex_ids).any()
    pairs = set(zip(g.src.tolist(), g.dst.tolist()))
    assert not any((u, v) in pairs or (v, u) in pairs
                   for u, v in zip(b.add_edge_src.tolist(), b.add_edge_dst.tolist()))
    assert mod.batch_count(30.0, 4.0) == 6 and mod.batch_count(2.0, 0.4) == 4


def test_reference_sweep_follows_the_programs_dhd_step():
    """One plain sweep equals the program's jnp ELL step on the same edges."""
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.kernels.ops import _ell_pack_batch

    rng = np.random.default_rng(3)
    n = 300
    src, dst = rng.integers(0, n, 1500), rng.integers(0, n, 1500)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    w = rng.random(len(src)).astype(np.float32) + 1e-3
    h = rng.random(n).astype(np.float32)
    q = rng.random(n).astype(np.float32)
    cols, vals = _ell_pack_batch(n, src, dst, w)
    want = kref.dhd_ell_ref(jnp.asarray(h), jnp.asarray(cols), jnp.asarray(vals),
                            jnp.asarray(q), alpha=0.01, gamma=0.1, beta=0.3)
    u = np.concatenate([src, dst])
    v = np.concatenate([dst, src])
    got = dhd_reference.sweep(h, u, v, np.concatenate([w, w]).astype(np.float64), q,
                              0.01, 0.1, 0.3)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-6, atol=1e-7)


# ------------------------------------------------------------- planted faults
def test_skipped_warm_sweeps_are_caught(monkeypatch):
    from repro.streaming.delta_dhd import StreamingHeat

    def skipped(self, max_iters=None, tol=None, local_iters=0):
        self.residual = 0.0
        return 0

    monkeypatch.setattr(StreamingHeat, "solve", skipped)
    _, res = run_tiny("snb.churn", seed=SEED + 3)
    c = res["checks"]["warm_heat_rel_gap"]
    assert c["value"] > 10 * c["limit"], res["checks"]
    assert not res["correct"]


def test_a_dropped_scattered_row_is_caught(monkeypatch):
    """Each row scatter to the device loses the row whose weights changed
    most: the device solves over a stale row."""
    from repro.streaming.delta_dhd import StreamingHeat

    real = StreamingHeat._sync_device
    dropped = []

    def drops_a_row(self, rows=None):
        if rows is not None and len(rows) > 1 and self._vals_j is not None \
                and self._vals_j.shape == self.vals.shape:
            moved = np.abs(self.vals[rows] - np.asarray(self._vals_j)[rows]).sum(axis=1)
            dropped.append(int(rows[np.argmax(moved)]))
            rows = np.delete(rows, np.argmax(moved))
        return real(self, rows)

    monkeypatch.setattr(StreamingHeat, "_sync_device", drops_a_row)
    _, res = run_tiny("snb.churn", seed=SEED + 4)
    assert dropped
    c = res["checks"]["warm_heat_rel_gap"]
    assert c["value"] > c["limit"], res["checks"]
    assert not res["correct"]


def test_a_missing_new_primary_copy_is_caught(monkeypatch):
    from repro.core.store import GeoGraphStore

    real = GeoGraphStore.apply_updates

    def loses_a_copy(self, batch):
        rep = real(self, batch)
        if rep.n_add_vertices:
            self.state.delta[self.g.n_nodes - rep.n_add_vertices] = False
        return rep

    monkeypatch.setattr(GeoGraphStore, "apply_updates", loses_a_copy)
    _, res = run_tiny("snb.churn", seed=SEED + 5)
    assert res["checks"]["primary_copies_new_missing"]["value"] >= 1, res["checks"]
    assert not res["correct"]


def _bridged_setup():
    """Five regions; Singapore (3) and Beijing (4), 75 ms apart, joined in
    the first bridge layer by one knows edge only."""
    cell, cfg, traffic = tiny_cell("snb.churn")
    rng = np.random.default_rng(7)
    part = np.repeat(np.arange(5), 12)
    src, dst = [], []
    for r in range(5):
        m = np.where(part == r)[0]
        for _ in range(30):
            a, b = rng.choice(m, 2, replace=False)
            src.append(a)
            dst.append(b)
    # 0-1 (69 ms) and 0-2 (80 ms) in the first layer; 3 and 4 reach the
    # others only from the second
    for a, b in [(0, 1), (0, 2), (1, 3), (1, 4), (3, 4)]:
        src.append(np.where(part == a)[0][0])
        dst.append(np.where(part == b)[0][1])
    n = len(part)
    g = gen.graph_arrays(n, src, dst, np.full(n, 256.0), np.full(len(src), 64.0), part)
    st = harness.Setup.__new__(harness.Setup)
    st.cfg, st.seed, st.seconds = cfg, SEED + 6, 2.0
    st.reg = reference.regions(cfg)
    st.graph = g
    st.pool = gen.pattern_pool(g, dict(cfg["pool"], n_patterns=16), st.seed + 1, 5)
    st.replicas = None
    st.build_store()
    st.events = _source().Source(cfg, traffic["events"], st)
    return st, len(src) - 1


@pytest.mark.parametrize("repair", [True, False])
def test_a_skipped_layered_graph_repair_is_caught(monkeypatch, repair):
    """Removing the only Singapore-Beijing edge splits the first layer's
    component: a store that skips the repair keeps it joined."""
    import repro.core.store as store_mod
    from repro.streaming import MutationBatch

    st, bridge = _bridged_setup()
    if not repair:
        monkeypatch.setattr(store_mod, "repair_layered_graph",
                            lambda lg, g, alive: (lg, None))
    src = st.events
    src.replay([dataclasses.replace(MutationBatch.empty(),
                                    del_edge_ids=np.asarray([bridge], np.int64))])
    src.warm(st.store)
    before = st.store.lg.comp_of_dc[1].copy()
    src.step(st.store, src.interval_s)
    st.replicas = np.array(st.store.state.delta)
    checks = src.check(src.keep(st.store), st)
    c = checks["layered_graph_mismatches"]
    if repair:
        assert c["value"] == 0 and before[3] == before[4]
        assert st.store.lg.comp_of_dc[1][3] != st.store.lg.comp_of_dc[1][4]
    else:
        assert c["value"] >= 1, checks
    assert checks["topology_mismatches"]["value"] == 0


def test_a_store_without_prepare_updates_is_refused(monkeypatch):
    from repro.core.store import GeoGraphStore

    monkeypatch.delattr(GeoGraphStore, "prepare_updates")
    with pytest.raises(SystemExit, match="prepare_updates"):
        run_tiny("snb.churn", seed=SEED + 7)


# -------------------------------------------------------------- the readers
def _ev(name, t_ms, d_ms, **stats):
    return NS(name=name, start_ns=t_ms * 1e6, duration_ns=d_ms * 1e6, stats=list(stats.items()))


def test_dhd_roofline_reads_the_sweep_spans(monkeypatch):
    reader = files.load("metrics", "dhd_ell_roofline")
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("heat.sweeps", 10, 20, nodes=1000, edges=5000, iters=3),
        _ev("heat.sweeps", 50, 20, nodes=1000, edges=5000),  # no iters: not read
    ])])
    monkeypatch.setattr(reader, "_sweep_spans", lambda d: reader._spans_of(NS(planes=[host])))
    red = {"devices": ["/device:TPU:0"], "module_events": {"/device:TPU:0": [
        ("jit__heat_relax(7)", 0.012, 1e-6), ("jit__heat_relax(8)", 0.055, 5e-6),
        ("jit__heat_set_rows(2)", 0.013, 9e-6)]}}
    ctx = {"trace": red, "device_kind": "TPU v5 lite", "win": NS(trace_dir="x")}
    want = 100.0 * 4 * (24 * 5000 + 12 * 1000) / 819e9 / 1e-6
    assert reader.read(ctx) == pytest.approx(want)
    assert reader.sweep_bytes(1000, 5000) == 132000


def test_churn_readers_are_declared_for_the_cell():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    names = {m["name"] for m in spec["per_layer"] if "snb.churn" in m.get("workloads", [])}
    assert names == {"apply_ms.churn", "remap_ms.churn", "repair_ms.churn", "reroute_ms.churn",
                     "warm_heat_ms.churn", "dhd_ell_roofline", "window_compiles.churn",
                     "device_idle.churn"}
    for name in names:
        assert callable(files.load("metrics", name).read)
