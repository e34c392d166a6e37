"""Fused route-expansion kernel: Pallas/subset impls vs the jnp oracle vs
``route_online``.

Correctness bar (the fast-path acceptance): every impl produces the scalar
router's exact greedy picks — same coverage argmax, same lowest-DC-id
tie-break, same layer escalation — and the integrated fast path is
bit-identical to the numpy batch path (shared exact f64 epilogue).
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback, see tests/_hypothesis_stub.py
    from _hypothesis_stub import given, settings, st

from repro.core.routing import (
    RouteFastConfig,
    get_route_fast_config,
    route_online,
    route_online_batch,
    set_route_fast_config,
)
from repro.kernels import ops, ref
from repro.kernels.autotune import Autotuner, set_autotuner
from repro.kernels.route_expand import route_expand


def _rand_problem(
    rng,
    R,
    k_lo,
    k_hi,
    D,
    L,
    p_rep=0.35,
    all_ties=False,
    single_origin=False,
    empty_layers=False,
):
    """Random packed batch + layer hierarchy for kernel-level differentials."""
    lens = rng.integers(k_lo, k_hi + 1, R)
    K = int(lens.max())
    bits = np.zeros((R, K), np.int32)
    sizes = np.zeros((R, K), np.float32)
    pow2 = 1 << np.arange(D)
    for r in range(R):
        k = int(lens[r])
        rep = (
            np.ones((k, D), bool)
            if all_ties
            else rng.random((k, D)) < p_rep
        )
        bits[r, :k] = (rep * pow2).sum(axis=1)
        sizes[r, :k] = (rng.random(k) + 0.25).astype(np.float32)
    origin = (
        np.zeros(R, np.int64) if single_origin else rng.integers(0, D, R)
    )
    # comp hierarchy: identity at layer 0, then random monotone coarsenings;
    # with empty_layers the first expansion layer stays identity, so every
    # origin cluster is a singleton and the greedy must escalate through it
    comp = np.zeros((L + 1, D), np.int64)
    comp[0] = np.arange(D)
    prev = np.arange(D)
    for layer in range(1, L + 1):
        if empty_layers and layer == 1:
            comp[layer] = prev
            continue
        groups = max(1, D // (layer + 1))
        prev = rng.integers(0, groups, int(prev.max()) + 1)[prev]
        comp[layer] = prev
    rtt = rng.random((D, D)).astype(np.float32) * 0.2
    rtt = rtt + rtt.T
    np.fill_diagonal(rtt, 0.0)
    ibw = (1.0 / (rng.random((D, D)) * 1e9 + 1e8)).astype(np.float32)
    np.fill_diagonal(ibw, 0.0)
    return bits, sizes, lens.astype(np.int32), origin.astype(np.int32), comp, rtt, ibw


def _assert_outputs_match(got, want, lens):
    served_g, bytes_g, layers_g, miss_g, strag_g, wan_g = got
    served_w, bytes_w, layers_w, miss_w, strag_w, wan_w = want
    for r, k in enumerate(lens):
        np.testing.assert_array_equal(served_g[r, :k], served_w[r, :k])
    np.testing.assert_array_equal(np.asarray(layers_g), np.asarray(layers_w))
    np.testing.assert_array_equal(np.asarray(miss_g), np.asarray(miss_w))
    np.testing.assert_allclose(bytes_g, bytes_w, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(strag_g, strag_w, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(wan_g, wan_w, rtol=1e-5, atol=1e-4)


SWEEP = [
    # R, k_lo, k_hi, D, L, p_rep, all_ties, single_origin, empty_layers
    (8, 1, 24, 5, 3, 0.35, False, False, False),
    (16, 2, 40, 4, 1, 0.5, False, False, False),
    (8, 1, 16, 8, 5, 0.2, False, False, False),
    (8, 4, 20, 5, 3, 0.0, True, False, False),  # all ties -> lowest DC id
    (8, 1, 24, 5, 3, 0.35, False, True, False),  # single-origin batch
    (8, 1, 24, 6, 4, 0.3, False, False, True),  # empty first layer
    (4, 1, 8, 5, 2, 0.05, False, False, False),  # mostly-unresolvable items
]


@pytest.mark.parametrize(
    "R,k_lo,k_hi,D,L,p_rep,ties,single,empty", SWEEP
)
def test_kernel_matches_oracle(R, k_lo, k_hi, D, L, p_rep, ties, single, empty):
    rng = np.random.default_rng(R * 1000 + D * 10 + L)
    prob = _rand_problem(
        rng, R, k_lo, k_hi, D, L, p_rep,
        all_ties=ties, single_origin=single, empty_layers=empty,
    )
    lens = prob[2]
    want = ops.route_expand_batch(*prob, use_kernel=False)
    got = tuple(
        np.asarray(o)
        for o in route_expand(*prob, block_r=8, interpret=True)
    )
    _assert_outputs_match(got, want, lens)


@pytest.mark.parametrize(
    "R,k_lo,k_hi,D,L,p_rep,ties,single,empty", SWEEP
)
def test_subsets_matches_oracle(R, k_lo, k_hi, D, L, p_rep, ties, single, empty):
    rng = np.random.default_rng(R * 7 + D * 31 + L)
    bits, sizes, lens, origin, comp, rtt, ibw = _rand_problem(
        rng, R, k_lo, k_hi, D, L, p_rep,
        all_ties=ties, single_origin=single, empty_layers=empty,
    )
    served_w, _, layers_w, miss_w, _, _ = ops.route_expand_batch(
        bits, sizes, lens, origin, comp, rtt, ibw, use_kernel=False
    )
    # flatten the padded tile into the subset router's stream signature
    req_id = np.repeat(np.arange(len(lens)), lens)
    bits_flat = np.concatenate(
        [bits[r, : lens[r]] for r in range(len(lens))]
    ).astype(np.int64)
    served, layers, miss = ops.route_expand_subsets(
        bits_flat, req_id, len(lens), origin.astype(np.int64), comp
    )
    np.testing.assert_array_equal(layers, np.asarray(layers_w))
    np.testing.assert_array_equal(miss, np.asarray(miss_w))
    lo = 0
    for r, k in enumerate(lens):
        np.testing.assert_array_equal(served[lo : lo + k], served_w[r, :k])
        lo += k


def test_field_word_boundary_consistency():
    """K just below / above the 10-bit field-word gate (512 padded slots)
    must give identical picks: the packed coverage path vs the 1-bit
    fallback is an internal detail, never a behaviour change."""
    rng = np.random.default_rng(99)
    for k_hi in (500, 600):  # pads to 512 (field path) / 1024 (fallback)
        bits, sizes, lens, origin, comp, rtt, ibw = _rand_problem(
            rng, 4, k_hi - 4, k_hi, 5, 3
        )
        want = ops.route_expand_batch(
            bits, sizes, lens, origin, comp, rtt, ibw, use_kernel=False
        )
        req_id = np.repeat(np.arange(4), lens)
        bits_flat = np.concatenate(
            [bits[r, : lens[r]] for r in range(4)]
        ).astype(np.int64)
        served, layers, miss = ops.route_expand_subsets(
            bits_flat, req_id, 4, origin.astype(np.int64), comp
        )
        lo = 0
        for r, k in enumerate(lens):
            np.testing.assert_array_equal(served[lo : lo + k], want[0][r, :k])
            lo += k
        np.testing.assert_array_equal(layers, np.asarray(want[2]))


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    R=st.integers(1, 12),
    D=st.integers(2, 8),
    L=st.integers(1, 4),
    p=st.floats(0.0, 1.0),
)
def test_subsets_vs_oracle_property(seed, R, D, L, p):
    rng = np.random.default_rng(seed)
    bits, sizes, lens, origin, comp, rtt, ibw = _rand_problem(
        rng, R, 1, 20, D, L, p_rep=p
    )
    served_w, _, layers_w, miss_w, _, _ = ops.route_expand_batch(
        bits, sizes, lens, origin, comp, rtt, ibw, use_kernel=False
    )
    req_id = np.repeat(np.arange(R), lens)
    bits_flat = np.concatenate(
        [bits[r, : lens[r]] for r in range(R)]
    ).astype(np.int64)
    served, layers, miss = ops.route_expand_subsets(
        bits_flat, req_id, R, origin.astype(np.int64), comp
    )
    np.testing.assert_array_equal(layers, np.asarray(layers_w))
    np.testing.assert_array_equal(miss, np.asarray(miss_w))
    lo = 0
    for r, k in enumerate(lens):
        np.testing.assert_array_equal(served[lo : lo + k], served_w[r, :k])
        lo += k


# ------------------------------------- one packed output per batch call
def _padded_problem(rng, r_pad, k_pad, D, L):
    """A batch as the fast path hands it over: real requests in the first
    rows, zero-length padded rows after them, zero-size slots past each
    request's length, and every fifth item held by no DC (picked -1)."""
    R = max(1, r_pad - r_pad // 4)
    bits, sizes, lens, origin, comp, rtt, ibw = _rand_problem(
        rng, R, 1, max(1, k_pad - 3), D, L, p_rep=0.3
    )
    K = bits.shape[1]
    bits[:, ::5] = 0
    bits_p = np.zeros((r_pad, k_pad), np.int32)
    sizes_p = np.zeros((r_pad, k_pad), np.float32)
    bits_p[:R, :K] = bits
    sizes_p[:R, :K] = sizes
    lens_p = np.zeros(r_pad, np.int32)
    lens_p[:R] = lens
    origin_p = np.zeros(r_pad, np.int32)
    origin_p[:R] = origin
    return bits_p, sizes_p, lens_p, origin_p, comp, rtt, ibw


def _raw_bits(out):
    """Outputs as exact bit patterns (f32 viewed as int32)."""
    return [
        np.asarray(o).view(np.int32) if np.asarray(o).dtype == np.float32
        else np.asarray(o)
        for o in out
    ]


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("r_pad,k_pad,D,L", [
    (8, 8, 3, 2),
    (64, 128, 5, 3),
    (256, 128, 8, 3),
    (8, 1024, 16, 4),
    (256, 1024, 5, 3),
    (64, 8, 16, 2),
])
def test_packed_batch_matches_unpacked(r_pad, k_pad, D, L, use_kernel):
    """``route_expand_batch`` packs six outputs into one int32 buffer and
    unpacks them on the host: every output equals its unpacked
    counterpart (the oracle jitted directly, or the interpreted kernel) bit
    for bit, in dtype and shape, -1 picks and 0.0 padding included; the
    other impl agrees on every pick and to f32 rounding on the sums."""
    rng = np.random.default_rng(r_pad * 131 + k_pad * 7 + D)
    prob = _padded_problem(rng, r_pad, k_pad, D, L)
    lens = prob[2]
    got = ops.route_expand_batch(*prob, use_kernel=use_kernel, block_r=128)
    oracle = jax.jit(ref.route_expand_ref)(*prob)
    kernel = route_expand(*prob, block_r=128, interpret=True)
    same, other = (kernel, oracle) if use_kernel else (oracle, kernel)
    for g, w in zip(got, same):
        assert (g.dtype, g.shape) == (w.dtype, w.shape)
    for g, w in zip(_raw_bits(got), _raw_bits(same)):
        np.testing.assert_array_equal(g, w)
    _assert_outputs_match(got, [np.asarray(o) for o in other], lens)
    served, bytes_rd, _, _, straggler, wan = got
    valid = np.arange(k_pad)[None, :] < lens[:, None]
    assert (served[valid] == -1).any() and (served[~valid] == -1).all()
    assert (straggler > 0).any()
    pad_rows = lens == 0
    assert pad_rows.any()
    assert not bytes_rd[pad_rows].any() and not straggler[pad_rows].any()
    assert not wan[pad_rows].any()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_route_expand_batch_launches_one_program_with_one_output(
    monkeypatch, use_kernel
):
    """One launch and one fetch per call: ``route_expand_batch`` runs one
    jitted program, and that program lowers with a single output leaf."""
    calls = []
    packed = ops._route_expand_packed

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return packed(*args, **kwargs)

    monkeypatch.setattr(ops, "_route_expand_packed", spy)
    prob = _padded_problem(np.random.default_rng(5), 8, 128, 5, 3)
    ops.route_expand_batch(*prob, use_kernel=use_kernel)
    assert len(calls) == 1
    args, kwargs = calls[0]
    lowered = packed.lower(*args, **kwargs)
    assert len(jax.tree_util.tree_leaves(lowered.out_info)) == 1


# --------------------------------------------------- integrated fast path
@pytest.fixture
def force_fast():
    """Drop every size gate so the fast path takes any batch; restore after."""
    old = get_route_fast_config()
    set_route_fast_config(RouteFastConfig(min_requests=2))
    yield
    set_route_fast_config(old)


def _store_requests(pats, n_dcs, n=30):
    reqs = []
    for i, p in enumerate(pats):
        if len(reqs) >= n:
            break
        if len(p.items):
            reqs.append((p.items, i % n_dcs))
    return reqs


def test_fast_path_matches_route_online(small_store, force_fast):
    store = small_store
    reqs = _store_requests(
        store.workload.patterns, store.lg.env.n_dcs
    )
    batch = route_online_batch(store.lg, store.state, reqs, fast=True)
    for (items, origin), b in zip(reqs, batch):
        s = route_online(store.lg, store.state, items, origin)
        np.testing.assert_array_equal(s.served_by, b.served_by)
        assert s.layers_used == b.layers_used
        assert s.n_missing == b.n_missing
        assert s.latency_s == pytest.approx(b.latency_s, rel=1e-6)


def test_fast_path_bit_identical_to_numpy_batch(small_store, force_fast):
    store = small_store
    reqs = _store_requests(
        store.workload.patterns, store.lg.env.n_dcs
    )
    a = route_online_batch(store.lg, store.state, reqs, fast=False)
    b = route_online_batch(store.lg, store.state, reqs, fast=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.served_by, y.served_by)
        # exact float equality: both paths share the f64 host epilogue
        assert x.latency_s == y.latency_s
        assert x.per_dc_latency == y.per_dc_latency
        assert x.wan_bytes == y.wan_bytes
        assert x.layers_used == y.layers_used and x.n_missing == y.n_missing


def test_fast_path_tile_impl_via_autotuner(small_store, force_fast):
    """A winner table pinning the tile oracle must route identically: the
    autotuner only ever changes *which* impl runs, never the picks."""
    old = set_autotuner(Autotuner())
    try:
        reqs = _store_requests(
            small_store.workload.patterns, small_store.lg.env.n_dcs, n=12
        )
        base = route_online_batch(
            small_store.lg, small_store.state, reqs, fast=False
        )
        tuner = set_autotuner(Autotuner())
        # pin impl=ref for every signature the batch can bucket to
        from repro.kernels.autotune import shape_bucket, signature_key

        lens = [len(it) for it, _ in reqs]
        sig = (
            shape_bucket(len(reqs)),
            shape_bucket(max(lens)),
            small_store.lg.env.n_dcs,
            small_store.lg.n_layers,
        )
        tuner.load({
            "version": 1,
            "tables": {
                tuner.device_kind(): {
                    "route_expand": {
                        signature_key(sig): {"config": {"impl": "ref"}}
                    }
                }
            },
        })
        got = route_online_batch(
            small_store.lg, small_store.state, reqs, fast=True
        )
        for x, y in zip(base, got):
            np.testing.assert_array_equal(x.served_by, y.served_by)
            assert x.latency_s == y.latency_s
            assert x.per_dc_latency == y.per_dc_latency
    finally:
        set_autotuner(old)


@pytest.mark.parametrize("R", [2, 3, 17])
def test_fast_path_odd_batch_sizes(small_store, force_fast, R):
    store = small_store
    reqs = _store_requests(store.workload.patterns, store.lg.env.n_dcs, n=R)
    a = route_online_batch(store.lg, store.state, reqs, fast=False)
    b = route_online_batch(store.lg, store.state, reqs, fast=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.served_by, y.served_by)
        assert x.latency_s == y.latency_s


@pytest.mark.parametrize("path,stage", [
    ("numpy", ("routing.expand", "numpy")),
    ("subsets", ("routing.expand", "subsets")),
    ("tile", ("routing.device", "ref")),
])
def test_traced_serving_is_bit_identical_and_nests(small_store, force_fast, monkeypatch,
                                                   path, stage):
    """Spans time the served path without touching it: the same batch gives
    the same results with the store's tracer on as off, and the stages nest
    under ``store.serve_batch`` in order."""
    from repro.obs import Tracer

    store = small_store
    reqs = _store_requests(store.workload.patterns, store.lg.env.n_dcs)
    if path == "numpy":
        set_route_fast_config(RouteFastConfig(enabled=False))
    if path == "tile":
        monkeypatch.setattr(ops, "SUBSET_MAX_DCS", 0)
    plain = store.serve_batch(reqs, observe=False)
    tracer = Tracer(enabled=True)
    monkeypatch.setattr(store, "tracer", tracer)
    traced = store.serve_batch(reqs, observe=False)
    for x, y in zip(plain, traced):
        np.testing.assert_array_equal(x.served_by, y.served_by)
        assert x.latency_s == y.latency_s
        assert x.per_dc_latency == y.per_dc_latency
        assert x.wan_bytes == y.wan_bytes
        assert x.layers_used == y.layers_used and x.n_missing == y.n_missing
    recs = sorted(tracer.records, key=lambda r: r.t0)
    by_sid = {r.sid: r for r in recs}
    top = [r for r in recs if r.parent is None]
    assert [r.name for r in top] == ["store.serve_batch"]
    kids = [r for r in recs if r.parent == top[0].sid]
    assert [r.name for r in kids] == ["routing.prepare", stage[0], "routing.epilogue"]
    assert kids[0].tags == {"requests": len(reqs),
                            "items": sum(len(it) for it, _ in reqs)}
    assert kids[1].tags["impl"] == stage[1]
    (size,) = [r for r in recs if r.name == "routing.item_size"]
    assert by_sid[size.parent].name == "routing.prepare"
    for a, b in zip(kids, kids[1:]):
        assert a.t1 <= b.t0


def test_fast_flag_false_never_dispatches(small_store, monkeypatch):
    """fast=False must not touch the kernels module at all."""
    import repro.core.routing as routing

    called = []
    monkeypatch.setattr(
        routing, "_route_batch_fast",
        lambda *a, **k: called.append(1),
    )
    reqs = _store_requests(small_store.workload.patterns, 4, n=8)
    route_online_batch(small_store.lg, small_store.state, reqs, fast=False)
    assert not called
