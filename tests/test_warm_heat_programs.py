"""The update path's device programs: DHD coefficients as operands of the
ELL kernel, shape buckets for the warm solve, and ``prepare_updates``, which
compiles them ahead and changes no state."""
import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import Graph, build_csr
from repro.core.latency import make_paper_env
from repro.core.patterns import Workload, generate_khop_patterns
from repro.core.placement import PlacementConfig
from repro.core.store import GeoGraphStore
from repro.kernels import ref
from repro.kernels.dhd_spmv import dhd_ell_step, dhd_ell_step_batch, dhd_sliced_step
from repro.obs import MetricsRegistry, set_default_registry
from repro.streaming import DeltaGraph, MutationBatch, StreamingHeat, random_churn_batch
from repro.streaming import delta_dhd
from repro.streaming.delta_dhd import _field_bucket, _k_bucket, _slot_bucket, reset_programs


def _ell(n, kmax, seed, batched=0):
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, n, (n, kmax)).astype(np.int32)
    shape = (batched, n, kmax) if batched else (n, kmax)
    vals = (rng.random(shape) * (rng.random(shape) > 0.3)).astype(np.float32)
    heat = rng.random((batched or 1, n)).astype(np.float32)
    q = rng.random((batched or 1, n)).astype(np.float32)
    return heat, cols, vals, q


COEF = [(0.5, 0.1, 0.3), (0.0123, 0.1, 0.3), (0.05, 0.25, 0.7)]


@pytest.mark.parametrize("alpha,gamma,beta", COEF)
@pytest.mark.parametrize("n,kmax,block_n,batched", [(300, 8, 128, 0), (257, 16, 128, 3)])
def test_kernel_with_array_coefficients_equals_static(alpha, gamma, beta, n, kmax, block_n,
                                                      batched):
    """The Pallas path (interpreted): coefficients passed as float32 operands
    give the bits the same coefficients give as constants of the program."""
    heat, cols, vals, q = _ell(n, kmax, 1, batched)
    const = jax.jit(lambda h, c, v, qq: dhd_ell_step_batch(
        h, c, v, qq, alpha=alpha, gamma=gamma, beta=beta, block_n=block_n, interpret=True))
    f32 = np.float32
    got = dhd_ell_step_batch(heat, cols, vals, q, alpha=f32(alpha), gamma=f32(gamma),
                             beta=f32(beta), block_n=block_n, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(const(heat, cols, vals, q)))
    if not batched:
        one = dhd_ell_step(heat[0], cols, vals, q[0], alpha=f32(alpha), gamma=f32(gamma),
                           beta=f32(beta), block_n=block_n, interpret=True)
        assert np.array_equal(np.asarray(one), np.asarray(got)[0])


@pytest.mark.parametrize("alpha,gamma,beta", COEF)
def test_jnp_path_with_array_coefficients_equals_static(alpha, gamma, beta):
    heat, cols, vals, q = _ell(200, 12, 2)
    const = jax.jit(lambda h, c, v, qq: ref.dhd_ell_ref(h, c, v, qq, alpha=alpha, gamma=gamma,
                                                        beta=beta))
    operand = jax.jit(ref.dhd_ell_ref)
    f32 = np.float32
    got = operand(heat[0], cols, vals, q[0], f32(alpha), f32(gamma), f32(beta))
    assert np.array_equal(np.asarray(got), np.asarray(const(heat[0], cols, vals, q[0])))


def test_one_program_serves_every_alpha():
    heat, cols, vals, q = _ell(128, 8, 3)
    dhd_ell_step(heat[0], cols, vals, q[0], alpha=np.float32(0.5), block_n=128, interpret=True)
    before = dhd_ell_step._cache_size()
    for a in (0.01, 0.02, 0.3):
        dhd_ell_step(heat[0], cols, vals, q[0], alpha=np.float32(a), block_n=128,
                     interpret=True)
    assert dhd_ell_step._cache_size() == before


def test_shape_buckets():
    """Field rows: 16 steps an octave, in 256-row blocks; ELL width: 8 slots."""
    assert [_field_bucket(n) for n in (1, 600, 1025, 4097, 65645, 69633)] == [
        1024, 1024, 1280, 4352, 69632, 73728]
    assert all(0 <= _field_bucket(n) - n < n / 16 + 256 for n in range(1024, 300_000, 977))
    assert [_k_bucket(k) for k in (1, 9, 16, 103, 105)] == [8, 16, 16, 104, 112]
    # sliced ELL slot rows: 16 steps an octave, in 8-slot chunks
    assert [_slot_bucket(r) for r in (1, 8, 18280, 18433)] == [8, 8, 18432, 19456]
    assert all(0 <= _slot_bucket(r) - r < r / 16 + 8 for r in range(8, 300_000, 977))


# ------------------------------------------------------------ the store
def _store(n, m, seed):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = src != dst
    g = Graph.from_edges(n, src[keep], dst[keep], partition=rng.integers(0, 5, n))
    env = make_paper_env()
    csr = build_csr(g.n_nodes, g.src, g.dst, symmetrize=True)
    pats = generate_khop_patterns(g, csr, 24, seed=seed + 1, n_dcs=env.n_dcs)
    wl = Workload.from_patterns(pats, g.n_items, env.n_dcs)
    store = GeoGraphStore(g, env, wl, config=PlacementConfig(precache=False, dhd_steps=4))
    store._delta_graph = DeltaGraph(store.g)
    return store, rng


@pytest.fixture
def compiles():
    seen = []

    def listener(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration_secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listener)


def test_batches_compile_nothing_after_prepare(compiles):
    """Batches of different sizes, one of them crossing the field's row
    bucket (1,024 -> 1,280 rows), run only programs compiled ahead."""
    store, rng = _store(1000, 5000, 21)
    assert store.prepare_updates() >= 0
    n_pads = []
    seen = len(compiles)
    for rate in (0.004, 0.03, 0.01, 0.05, 0.002):
        rep = store.apply_updates(random_churn_batch(store._delta_graph, rate, rng))
        assert rep.heat.global_iters >= 1
        n_pads.append(store._heat.cols.shape[0])
    assert n_pads[0] == 1024 and n_pads[-1] == 1280, n_pads
    assert len(compiles) == seen, f"{len(compiles) - seen} compiles after prepare_updates"


def _state(store):
    h = store._heat
    heat = None if h is None else (h.cols.copy(), h.vals.copy(), h.heat.copy(), h.q.copy())
    g = store.g
    return (store.state.delta.copy(), store.route_index.nearest.copy(), heat,
            g.n_nodes, g.src.copy(), g.dst.copy(), store._heat_scale,
            None if store._delta_graph is None else store._delta_graph.edge_alive.copy())


def _assert_same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        if isinstance(x, tuple):
            _assert_same(x, y)
        elif isinstance(x, np.ndarray):
            assert x.dtype == y.dtype and np.array_equal(x, y)
        else:
            assert x == y


def test_prepare_updates_changes_no_state():
    store, rng = _store(400, 1600, 22)
    before = _state(store)
    store.prepare_updates()
    _assert_same(before, _state(store))
    store.apply_updates(random_churn_batch(store._delta_graph, 0.02, rng))
    before = _state(store)
    store.prepare_updates()
    _assert_same(before, _state(store))


def test_empty_batch_readies_the_update_path(compiles):
    """``apply_updates`` of an empty batch compiles the update programs and
    changes nothing; the batches after it compile nothing."""
    store, rng = _store(700, 3000, 23)
    store._delta_graph = None
    before = _state(store)
    reset_programs()
    rep = store.apply_updates(MutationBatch.empty())
    assert rep.n_touched_vertices == 0 and rep.heat is None
    assert len(delta_dhd._PROGRAMS) > 0
    after = _state(store)
    _assert_same(before[:-1], after[:-1])  # the overlay is made, over every edge
    assert after[-1].all()
    seen = len(compiles)
    for _ in range(3):
        store.apply_updates(random_churn_batch(store._delta_graph, 0.01, rng))
    assert len(compiles) == seen


def test_warm_solve_budget_and_tolerance_are_operands(compiles):
    """A starved budget and a tighter tolerance reuse the compiled solve."""
    store, rng = _store(300, 1200, 24)
    store.prepare_updates()
    store.apply_updates(random_churn_batch(store._delta_graph, 0.02, rng))
    seen = len(compiles)
    h = store._heat
    assert h.solve(max_iters=1) == 1
    h.solve(max_iters=200, tol=1e-7)
    assert len(compiles) == seen


# ------------------------------------------------ the sliced warm solve
@pytest.mark.parametrize("alpha,gamma,beta", COEF)
def test_sliced_kernel_with_array_coefficients_equals_static(alpha, gamma, beta):
    """The sliced kernels (interpreted) take the coefficients as operands too
    (two blocks of 16 and 8 slots, and a chunk past the slabs)."""
    heat, cols, vals, q = _ell(512, 16, 4)
    widths = np.array([16, 8])
    src, blk = delta_dhd._slab_index(widths, 16, 32)
    s_cols, s_vals = delta_dhd._cut_slabs(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(src))
    const = jax.jit(lambda h, qq: dhd_sliced_step(h, s_cols, s_vals, blk, qq, alpha=alpha,
                                                  gamma=gamma, beta=beta, interpret=True))
    f32 = np.float32
    got = dhd_sliced_step(heat[0], s_cols, s_vals, blk, q[0], alpha=f32(alpha),
                          gamma=f32(gamma), beta=f32(beta), interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(const(heat[0], q[0])))


class _DenseHeat(StreamingHeat):
    """The warm solve without the sliced layout: global sweeps over the dense
    ELL, and only a row past the dense width rebuilds (run in vertex order
    through ``_vertex_order``)."""

    def _relax_sliced(self, frozen=None, max_iters=None, tol=None):
        n_pad, kmax = self.cols.shape
        frozen = np.zeros(n_pad, bool) if frozen is None else frozen
        return delta_dhd._program("relax", n_pad, kmax)(
            self.heat, self._cols_j, self._vals_j, self.q, frozen, self.heat, self._coef(),
            np.float32(tol or self.tol), np.int32(max_iters or self.max_iters))

    def _outgrown(self, rows):
        return False


def _vertex_order(monkeypatch, fn, *args, **kw):
    with monkeypatch.context() as m:
        m.setattr(delta_dhd, "_solver_order", lambda deg, n_pad: np.arange(n_pad))
        return fn(*args, **kw)


def _heat_inputs(dg, w_all, q_all):
    g = dg.g
    alive = np.where(dg.edge_alive)[0]
    q = q_all[: g.n_nodes] * dg.node_alive
    return g.n_nodes, g.src[alive], g.dst[alive], w_all[alive], q


@pytest.fixture
def registry():
    reg = MetricsRegistry(enabled=True)
    old = set_default_registry(reg)
    yield reg
    set_default_registry(old)


@pytest.mark.parametrize("n,m,rate", [
    (900, 1800, 0.004),  # frontier relaxed on the whole field, every other row held
    (1500, 7000, 0.05),
    (3000, 4500, 0.002),  # frontier relaxed on its own dense sub-ELL
])
def test_sliced_solver_order_matches_dense_path(monkeypatch, n, m, rate):
    """Over a stream of batches (person arrivals and departures, knows
    inserts and deletes), the degree-sorted sliced solve gives the vertex
    field of the dense solve in vertex order, within 1e-6."""
    rng = np.random.default_rng(n)
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = src != dst
    dg = DeltaGraph(Graph.from_edges(n, src[keep], dst[keep], partition=rng.integers(0, 5, n)))
    w_all = (rng.random(10 * m) + 1e-3).astype(np.float32)
    q_all = rng.random(10 * n).astype(np.float32)
    sliced = StreamingHeat(tol=1e-5, max_iters=32)
    dense = _DenseHeat(tol=1e-5, max_iters=32)
    args = _heat_inputs(dg, w_all, q_all)
    sliced.rebuild(*args)
    _vertex_order(monkeypatch, dense.rebuild, *args)
    assert not np.array_equal(sliced.sid, np.arange(len(sliced.sid)))
    assert sliced.slots < sliced.cols.size
    for _ in range(5):
        res = dg.apply(random_churn_batch(dg, rate, rng))
        args = _heat_inputs(dg, w_all, q_all)
        a = sliced.update(*args, touched=res.touched_vertices)
        b = _vertex_order(monkeypatch, dense.update, *args, touched=res.touched_vertices)
        assert (a.local_iters, a.global_iters) == (b.local_iters, b.global_iters)
        want = dense.vertex_heat
        assert np.abs(sliced.vertex_heat - want).max() <= 1e-6 * np.abs(want).max()
    assert dg.g.n_nodes > n  # persons arrived


def test_a_row_outgrowing_its_block_rebuilds(registry):
    """A touched row given more edges than its block holds rebuilds and
    re-sorts (counted as ``heat.block_overflow``); the field is the cold
    rebuild's from the field before."""
    rng = np.random.default_rng(5)
    n = 600
    src, dst = rng.integers(0, n, 3000), rng.integers(0, n, 3000)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    w = (rng.random(len(src)) + 0.01).astype(np.float32)
    q = rng.random(n).astype(np.float32)
    sh = StreamingHeat(tol=1e-5, max_iters=32)
    sh.rebuild(n, src, dst, w, q)
    v = int(np.argsort(sh.sid)[n - 1])  # the last real row: among the narrowest
    row = sh.sid[v]
    width = int(sh.widths[row // 256])
    peers = rng.choice(np.setdiff1d(np.arange(n), [v]), width + 1, replace=False)
    src2 = np.concatenate([src, np.full(len(peers), v)])
    dst2 = np.concatenate([dst, peers])
    w2 = np.concatenate([w, np.full(len(peers), 0.5, np.float32)])
    before = sh.vertex_heat.copy()
    stats = sh.update(n, src2, dst2, w2, q, touched=np.concatenate([[v], peers]))
    assert registry.counter("heat.block_overflow").value == 1
    assert sh.sid[v] < row  # re-sorted: now among the wider rows
    assert int(sh.widths[sh.sid[v] // 256]) > width
    cold = StreamingHeat(tol=1e-5, max_iters=32)
    it = cold.rebuild(n, src2, dst2, w2, q, heat0=before)
    assert stats.global_iters == it
    assert np.array_equal(sh.vertex_heat, cold.vertex_heat)


def test_prepare_predicts_the_sliced_capacity(monkeypatch):
    """At a field of 16k rows (64 blocks, skewed degrees) the slot rows a
    cold build picks are among those ``prepare`` compiles, and the build
    compiles nothing further."""
    rng = np.random.default_rng(9)
    n = 16_500
    hubs = rng.integers(0, n, 40_000) % (n // 8)  # an eighth of the vertices hold most edges
    src, dst = hubs, rng.integers(0, n, 40_000)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    made = []
    real = delta_dhd._program
    monkeypatch.setattr(delta_dhd, "_program", lambda *key: made.append(key))
    StreamingHeat().prepare(n, src, dst)
    monkeypatch.setattr(delta_dhd, "_program", real)
    sliced = {k for k in made if k[0] == "sliced"}
    assert len(sliced) == 2 * len({k[1:3] for k in sliced})  # a capacity and the next
    sh = StreamingHeat(tol=1e-4, max_iters=2)
    used = []
    monkeypatch.setattr(delta_dhd, "_program", lambda *key: used.append(key) or real(*key))
    sh.rebuild(n, src, dst, np.ones(len(src), np.float32), rng.random(n).astype(np.float32))
    assert sh.cols.shape[0] >= 16_000
    assert sh.slots < 0.8 * sh.cols.size
    assert used == [("sliced", sh.cols.shape[0], sh.cols.shape[1], sh.slots // 256)]
    assert used[0] in made
