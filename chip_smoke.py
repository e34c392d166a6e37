"""Bring-up smoke run: the store's main path on a TPU, through its public API.

    python chip_smoke.py              # one chip: build, serve, churn, dispatch
    python chip_smoke.py --chips 4    # four chips: the sharded store only

One chip, in one process:

a. device check — fails unless JAX's default device is a TPU;
b. build — ``GeoGraphStore`` with the paper's placement config
   (``configs/geolayer.py``), so placement and pre-caching diffuse heat
   through ``kernels.ops.diffuse_batch``;
c. serve — batches of 256 and 1024 requests through ``serve_batch`` and a
   few hundred through ``StoreClient`` -> ``AdmissionController``, every
   request checked against the scalar ``route_online`` (exact served DCs,
   ``latency_s`` to its f32 byte sums) and bit for bit against the
   kernel-free numpy batch router;
d. churn — one 1% churn batch through ``apply_updates`` (warm DHD on
   ``kernels.ops.dhd_sliced``) and ``flush_migrations()``, then serving
   checked again;
e. dispatch — the ``kernels.dispatch{op,path}`` counters must show
   ``route_expand``, ``diffuse_batch`` and ``dhd_sliced`` on the Pallas
   kernel and never on the reference; then the churned store's DHD step
   over its sliced ELL is checked against ``kernels.ref.dhd_ell_ref`` over
   its dense ELL on the device.

``--chips 4`` runs ``ShardedGeoGraphStore`` over four TPU devices against a
single-process ``GeoGraphStore`` of the same build: identical ``serve_batch``
results, and a migration flush whose waves ship payload rows device to
device, with payload parity checked after every wave.

Earlier lines report sizes and host-clock seconds (not measurements); the
last line is one JSON object naming the device.  Any failed check exits
non-zero.  The compile cache follows ``JAX_COMPILATION_CACHE_DIR``, else
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.geolayer import CONFIG  # noqa: E402
from repro.core.graph import build_csr  # noqa: E402
from repro.core.latency import make_paper_env, make_synthetic_env  # noqa: E402
from repro.core.patterns import Workload, generate_khop_patterns  # noqa: E402
from repro.core.placement import PlacementConfig  # noqa: E402
from repro.core.routing import route_online, route_online_batch  # noqa: E402
from repro.core.store import GeoGraphStore  # noqa: E402
from repro.data.synthetic import community_graph  # noqa: E402
from repro.obs import MetricsRegistry, get_registry, set_default_registry  # noqa: E402
from repro.serve import AdmissionConfig, AdmissionController, StoreClient  # noqa: E402
from repro.streaming import DeltaGraph, random_churn_batch  # noqa: E402

# the bench_serving fast-lane store (community graph, 20 communities, on the
# paper's 5-DC environment, 5-hop / branch-2 patterns of ~124 items) at 4x
# its 26k vertices and 256 patterns: 104k vertices, ~8.2M items
STORE = dict(n_vertices=104_000, n_patterns=1024, hops=5, branch=2)
# the bench_sharded store: 8-DC synthetic environment, 4 shards
SHARDED = dict(n_vertices=12_000, n_patterns=240, n_dcs=8, n_shards=4)
CHECKED_OPS = ("route_expand", "diffuse_batch", "dhd_sliced")


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(chips: int) -> dict:
    """Phase (a): the default backend must be a TPU with ``chips`` devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default device is {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"{chips} TPU chips requested, {len(devs)} found")
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


# ------------------------------------------------------------------ builds
def _graph_workload(n_vertices, n_patterns, n_dcs, n_communities, seed,
                    hops=3, branch=2):
    g = community_graph(
        n_vertices, n_communities=n_communities, p_in=0.02, p_out=0.0005,
        seed=seed, n_dcs=n_dcs,
    )
    csr = build_csr(g.n_nodes, g.src, g.dst, symmetrize=True)
    pats = generate_khop_patterns(
        g, csr, n_patterns, hops=hops, branch=branch, seed=seed + 1,
        n_dcs=n_dcs, n_hot_sources=64,
    )
    return g, Workload.from_patterns(pats, g.n_items, n_dcs)


def build_store(n_vertices: int, n_patterns: int, hops: int, branch: int,
                seed: int = 0) -> GeoGraphStore:
    """Phase (b): the store, built with the paper's placement config."""
    env = make_paper_env()
    g, wl = _graph_workload(
        n_vertices, n_patterns, env.n_dcs, 20, seed, hops=hops, branch=branch
    )
    return GeoGraphStore(g, env, wl, config=CONFIG.placement_config())


def request_stream(store, n: int, seed: int):
    """Sampled pattern requests, 65% from the pattern's home DC and 35% from
    a uniform DC (the bench_serving mix)."""
    rng = np.random.default_rng(seed)
    pats = [p for p in store.workload.patterns if len(p.items)]
    reqs = []
    for _ in range(n):
        p = pats[int(rng.integers(0, len(pats)))]
        home = int(np.argmax(p.r_py))
        origin = home if rng.random() < 0.65 else int(rng.integers(0, store.env.n_dcs))
        reqs.append((p.items, origin))
    return reqs


def check_results(store, reqs, results) -> None:
    """Every result against the references: scalar ``route_online`` gives
    the exact served DCs, and ``latency_s`` to its f32 byte sums (rel 1e-6,
    the tolerance of ``tests/test_serving_batch.py``); the numpy batch
    router (no kernels, same f64 epilogue) gives served DCs, ``latency_s``
    and ``wan_bytes`` bit for bit."""
    base = route_online_batch(store.lg, store.state, reqs, fast=False)
    if not len(results) == len(reqs) == len(base):
        raise AssertionError(f"{len(results)} results for {len(reqs)} requests")
    for i, ((items, origin), got, b) in enumerate(zip(reqs, results, base)):
        want = route_online(store.lg, store.state, items, origin)
        if not np.array_equal(got.served_by, want.served_by):
            raise AssertionError(f"request {i}: served DCs differ from route_online")
        if not np.isclose(got.latency_s, want.latency_s, rtol=1e-6, atol=0.0):
            raise AssertionError(
                f"request {i}: latency {got.latency_s!r} != {want.latency_s!r}"
            )
        if not (np.array_equal(got.served_by, b.served_by)
                and got.latency_s == b.latency_s and got.wan_bytes == b.wan_bytes):
            raise AssertionError(f"request {i}: differs from the numpy batch router")


# ------------------------------------------------------------------ phases
def serve_phase(store, batch_sizes=(256, 1024), n_client: int = 384,
                seed: int = 0) -> dict:
    """Phase (c): direct batches, then the client -> controller path."""
    out = {}
    for bs in batch_sizes:
        reqs = request_stream(store, bs, seed=seed + bs)
        store.serve_batch(reqs, observe=False)  # compile this shape bucket
        t0 = time.perf_counter()
        res = store.serve_batch(reqs)
        out[f"serve_batch_{bs}_s"] = time.perf_counter() - t0
        check_results(store, reqs, res)
    ctl = AdmissionController(
        store, AdmissionConfig(policy="greedy", fairness="fifo", max_batch=256)
    )
    client = StoreClient(ctl)
    reqs = request_stream(store, n_client, seed=seed + 7)
    handles = [client.submit(items, origin) for items, origin in reqs]
    ctl.run_until_idle()
    check_results(store, reqs, [client.result(h) for h in handles])
    out["client_requests"] = len(handles)
    return out


def churn_phase(store, rate: float = 0.01, seed: int = 0) -> dict:
    """Phase (d): one churn batch and a migration flush, then serving
    checked again on the churned store."""
    rng = np.random.default_rng(seed + 3)
    if store._delta_graph is None:
        store._delta_graph = DeltaGraph(store.g)
    batch = random_churn_batch(store._delta_graph, rate, rng)
    t0 = time.perf_counter()
    report = store.apply_updates(batch)
    t_apply = time.perf_counter() - t0
    t0 = time.perf_counter()
    plan = store.flush_migrations()
    t_flush = time.perf_counter() - t0
    reqs = request_stream(store, 256, seed=seed + 11)
    check_results(store, reqs, store.serve_batch(reqs))
    return {
        "churn_ops": int(batch.n_ops),
        "apply_updates_s": t_apply,
        "flush_s": t_flush,
        "moves": len(plan.moves),
        "heat_global_iters": int(report.heat.global_iters),
        "heat_residual": float(report.heat.residual),
    }


def dhd_check(store) -> float:
    """The churned store's DHD step on the dispatched path (its sliced ELL)
    against ``ref.dhd_ell_ref`` over its dense ELL on the same device,
    within f32 tolerance."""
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.streaming.delta_dhd import _cut_slabs

    heat = store._heat
    p = heat.params
    h, q = jnp.asarray(heat.heat), jnp.asarray(heat.q)
    cols, vals = jnp.asarray(heat.cols), jnp.asarray(heat.vals)
    src, blk = heat._slab
    kw = dict(alpha=heat.alpha, gamma=p.gamma, beta=p.beta)
    got = np.asarray(ops.dhd_sliced(h, *_cut_slabs(cols, vals, src), blk, q, **kw))
    want = np.asarray(ref.dhd_ell_ref(h, cols, vals, q, **kw))
    if not np.isfinite(got).all():
        raise AssertionError("kernel DHD field is not finite")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    return float(np.abs(got - want).max())


def dispatch_counts(reg) -> dict:
    """Phase (e): ``kernels.dispatch`` per checked op and path."""
    counts = {
        op: {path: reg.counter("kernels.dispatch", op=op, path=path).value
             for path in ("kernel", "ref")}
        for op in CHECKED_OPS
    }
    for op, c in counts.items():
        if not c["kernel"] > 0 or c["ref"] != 0:
            raise AssertionError(f"{op} dispatch {c}: want kernel > 0, ref == 0")
    return counts


def single_chip(sizes: dict = STORE, seed: int = 0) -> None:
    """Phases (b)-(e) on the default device, telemetry on."""
    old = set_default_registry(MetricsRegistry(enabled=True))
    try:
        t0 = time.perf_counter()
        store = build_store(seed=seed, **sizes)
        pats = [p for p in store.workload.patterns if len(p.items)]
        log(f"# build: {store.g.n_nodes} vertices, {store.g.n_items} items, "
            f"{np.mean([len(p.items) for p in pats]):.1f} items/request, "
            f"{time.perf_counter() - t0:.2f}s incl. compiles")
        log(f"# serve: {serve_phase(store, seed=seed)}")
        log(f"# churn: {churn_phase(store, seed=seed)}")
        log(f"# dispatch: {dispatch_counts(get_registry())}")
        log(f"# dhd kernel vs ref max abs diff: {dhd_check(store)}")
    finally:
        set_default_registry(old)


# ----------------------------------------------------------- four chips
def _assert_same_results(r1, r2) -> None:
    if len(r1) != len(r2):
        raise AssertionError(f"{len(r2)} sharded results for {len(r1)} requests")
    for a, b in zip(r1, r2):
        if not (np.array_equal(a.served_by, b.served_by)
                and a.latency_s == b.latency_s and a.wan_bytes == b.wan_bytes
                and a.layers_used == b.layers_used and a.n_missing == b.n_missing):
            raise AssertionError("sharded serve_batch differs from GeoGraphStore")


def sharded_phase(n_vertices: int, n_patterns: int, n_dcs: int, n_shards: int,
                  seed: int = 0) -> dict:
    """Sharded store on ``n_shards`` devices vs one ``GeoGraphStore`` of the
    same build: serve identity, then a flush whose waves move payload rows
    device to device, parity checked after each wave."""
    import jax

    from repro.distributed.sharded_store import ShardedGeoGraphStore

    env = make_synthetic_env(n_dcs, seed=0)
    # bench_sharded's placement: without pre-caching the flush has replicas
    # to add
    cfg = PlacementConfig(precache=False, dhd_steps=4)
    builds = [
        _graph_workload(n_vertices, n_patterns, n_dcs, 24, seed)
        for _ in range(2)  # stores mutate their graph: one build each
    ]
    t0 = time.perf_counter()
    ref_store = GeoGraphStore(builds[0][0], env, builds[0][1], config=cfg)
    sh = ShardedGeoGraphStore(builds[1][0], env, builds[1][1], config=cfg,
                              n_shards=n_shards, telemetry=True)
    t_build = time.perf_counter() - t0
    devices = {sd.device for sd in sh.shards}
    if len(devices) != n_shards or not devices <= set(jax.devices()):
        raise AssertionError(f"shards not on {n_shards} distinct devices: {devices}")

    reqs = request_stream(ref_store, 256, seed=seed + 5)
    _assert_same_results(ref_store.serve_batch(reqs), sh.serve_batch(reqs))

    rng_a, rng_b = np.random.default_rng(seed + 9), np.random.default_rng(seed + 9)
    for store, rng in ((ref_store, rng_a), (sh, rng_b)):
        store._delta_graph = DeltaGraph(store.g)
        store.apply_updates(random_churn_batch(store._delta_graph, 0.02, rng))
    kw = dict(theta_add=0.3, theta_drop=0.15)
    med = float(np.median(ref_store.g.item_size()))
    window = 3.0 * med / float(env.bw_Bps_safe().min())  # a few items per wave
    ref_plan = ref_store.flush_migrations(window_s=window, **kw)
    plan, applier = sh.begin_flush(window_s=window, **kw)
    waves = cross_device = 0
    while applier.n_remaining:
        wave = applier.apply_next()
        waves += 1
        cross_device += sum(
            sh.shards[sh.origin_shard[b.src]].device
            != sh.shards[sh.origin_shard[b.dst]].device
            for b in wave.links
        )
        worst = sh.verify_payloads()
        if worst != 0.0:
            raise AssertionError(f"payload parity broken after wave {waves}: {worst}")
    applier.finish()
    if sh.verify_payloads() != 0.0:
        raise AssertionError("payload parity broken after flush")
    if plan.n_adds != ref_plan.n_adds or not plan.n_adds or not cross_device:
        raise AssertionError(
            f"flush moved no rows across devices (adds {plan.n_adds} vs "
            f"{ref_plan.n_adds}, cross-device links {cross_device})"
        )
    if not (np.array_equal(ref_store.state.delta, sh.state.delta)
            and sh.verify_partitions()):
        raise AssertionError("sharded placement diverged after the flush")
    reqs = request_stream(ref_store, 256, seed=seed + 6)
    _assert_same_results(ref_store.serve_batch(reqs), sh.serve_batch(reqs))
    return {
        "items": int(ref_store.g.n_items),
        "devices": sorted(str(d) for d in devices),
        "build_s": t_build,
        "waves": waves,
        "cross_device_links": int(cross_device),
        "adds": int(plan.n_adds),
    }


def compile_meter() -> dict:
    """Count XLA backend compiles and their seconds from here on (a hit in
    the persistent compile cache is not a backend compile)."""
    import jax.monitoring

    total = {"programs": 0, "seconds": 0.0}

    def listener(event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            total["programs"] += 1
            total["seconds"] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(listener)
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded store over four chips, and only that")
    args = ap.parse_args(argv)
    device = device_check(args.chips)
    log(f"# device: {device}; compile cache: {enable_compile_cache()}")
    compiles = compile_meter()
    t0 = time.perf_counter()
    if args.chips == 4:
        log(f"# sharded: {sharded_phase(**SHARDED)}")
    else:
        single_chip()
    log(f"# total: {time.perf_counter() - t0:.2f}s; backend compiles: {compiles}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
