"""One run of one cell: set-up, warm-up, the measured window, the check.

The store is driven as a deployment drives it: requests enter through
``StoreClient.submit`` at their scheduled arrival times (open loop), the
``AdmissionController`` batches them greedily on a wall clock, and each
batch goes through the store's ``serve_batch``.

A read's latency runs from its scheduled arrival to the return of the
controller step that served it: queue wait, batching, host packing,
transfers, kernel and epilogue.  The Eq. 1 WAN fetch the controller models
is not a wall-clock cost of the store and is not in it.

A traffic file may name an event source, ``"events": {"source": <name>,
...}``: the file ``bench/events/<name>.py`` defines ``tiny(events)`` (its
CPU rehearsal size) and the class ``Source``, built in :class:`Setup` as
``Source(cfg, events, setup)`` (it generates all its events there, from the
seed, in set-up), which acts on the store inside the window and provides:

* ``warm(store)``: before the window, after the read shapes are warm;
* ``step(store, now)``: once per iteration of the window's loop, before the
  controller's step, inside the ``bench.event`` span; it decides itself
  whether an event is due (open or closed loop).  No step runs after the
  window's close;
* ``version``: the number of events applied so far;
* ``topology(version)``: the graph (:class:`bench.gen.GraphArrays`, with
  its tombstones) after that many events;
* ``keep(store)``: what its check needs of the store, taken after the
  window and before the store is freed;
* ``check(kept, setup)``: ``{number: {"value", "limit"}}``, numbers that
  join the read check and decide ``correct`` (each at most its limit, a
  constant in the source's file);
* ``record()``: what its per-layer readers read, as ``ctx["events"]``.

Each sampled read is checked against what served it: the replica rows of
its items as the store held them when it was answered, and the topology of
the events applied by then.
"""
from __future__ import annotations

import gc
import json
import math
import pathlib
import time
from typing import Dict, List, Optional

import numpy as np

from . import files, gen, reference

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent

# How every cell drives the controller and checks and traces its window.
POLICY = "greedy"  # batches as large as the queue allows, up to MAX_BATCH
MAX_BATCH = 256
CHECK_SAMPLE = 384  # reads drawn from the seed and compared with the reference
DRAIN_LIMIT_S = 60.0  # how long reads due in the window may take past its close
TRACE_FROM = 0.4  # the traced stretch opens at this share of the window
TRACE_SECONDS = 2.0


def log(msg: str) -> None:
    import sys

    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------- cells
def load_cell(name: str):
    """``(cell, config, traffic)`` of the workload ``name`` in BENCHMARK.json."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    cfg = json.loads((REPO / files[cell["config"]]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def limits() -> Dict[str, float]:
    return json.loads((BENCH / "limits.json").read_text())["limits"]


class WallClock:
    """The controller's clock on wall time: ``now`` is seconds since
    ``start``, ``jump_to`` sleeps until the instant, and ``advance`` has
    nothing to do because the measured service time has already passed."""

    def __init__(self) -> None:
        self.t0 = time.perf_counter()

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def advance(self, dt: float) -> None:
        if dt < 0:
            raise ValueError(f"clock cannot go backwards (dt={dt})")

    def jump_to(self, t: float) -> None:
        wait = t - self.now()
        if wait > 0:
            import jax

            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(wait)


def cache_everything() -> None:
    """Keep every compiled program in the persistent cache, however quick
    its compile, so that a cell's second run compiles nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def compile_meter() -> dict:
    """Count XLA backend compiles and their seconds from here on."""
    import jax.monitoring

    total = {"programs": 0, "seconds": 0.0}

    def listener(event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            total["programs"] += 1
            total["seconds"] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(listener)
    return total


# ------------------------------------------------------------------ set-up
class Setup:
    """Everything a run builds before its window."""

    def __init__(self, cfg: Dict, traffic: Dict, seed: int, seconds: float) -> None:
        self.cfg, self.seed, self.seconds = cfg, seed, seconds
        self.reg = reference.regions(cfg)
        D = self.reg.n_dcs
        self.graph = gen.make_graph(cfg["graph"], seed, D)
        self.pool = gen.pattern_pool(self.graph, cfg["pool"], seed + 1, D)
        self.requests = gen.request_stream(
            self.pool, traffic["rate_per_s"], seconds, traffic["home_share"], D, seed + 3,
            traffic["opening_backlog"],
        )
        self.store = None
        self.replicas: Optional[np.ndarray] = None  # the replica map at the window's close
        ev = traffic.get("events")
        self.event_source = None if ev is None else ev["source"]
        self.events = None if ev is None else files.load("events", ev["source"]).Source(
            cfg, ev, self)

    def items(self, pattern: int) -> np.ndarray:
        return self.pool[pattern].items

    def version(self) -> int:
        """Events applied so far (0 without an event source)."""
        return 0 if self.events is None else int(self.events.version)

    def topology(self, version: int) -> gen.GraphArrays:
        """The graph after ``version`` events."""
        return self.graph if self.events is None else self.events.topology(version)

    def build_store(self) -> None:
        """The program's store over the generated graph and pool, placed with
        the configuration's placement (heat diffusion on the chip)."""
        from repro.core.dhd import DHDParams
        from repro.core.graph import Graph
        from repro.core.latency import make_paper_env
        from repro.core.patterns import Pattern, Workload
        from repro.core.placement import PlacementConfig
        from repro.core.store import GeoGraphStore

        env = make_paper_env()
        if not (np.allclose(env.rtt_s, self.reg.rtt_s, rtol=0, atol=1e-12)
                and np.array_equal(env.bw_Bps_safe(), self.reg.bw_Bps)):
            raise SystemExit("the program's region table differs from the configuration's")
        g = self.graph
        graph = Graph(n_nodes=g.n_nodes, src=g.src.copy(), dst=g.dst.copy(),
                      node_size=g.node_size.copy(), edge_size=g.edge_size.copy(),
                      partition=g.partition.copy())
        pats = [Pattern(pid=p.pid, items=p.items.copy(), r_py=p.r_py.copy(),
                        w_py=p.w_py.copy(), eta=p.eta) for p in self.pool]
        D = self.reg.n_dcs
        wl = Workload.from_patterns(pats, graph.n_items, D)
        pc = self.cfg["placement"]
        config = PlacementConfig(
            gamma_max_s=pc["gamma_max_s"], lambda1=pc["lambda1"], lambda2=pc["lambda2"],
            dhd=DHDParams(alpha=pc["dhd_alpha"], gamma=pc["dhd_gamma"], beta=pc["dhd_beta"]),
            theta_quantile=pc["theta_quantile"],
        )
        self.store = GeoGraphStore(graph, env, wl, config=config)


def _warm_batches(st: Setup, rng: np.random.Generator) -> List[list]:
    """One batch per ``(rows, widest request)`` shape bucket the window's
    greedy batches can take: rows 64..MAX_BATCH in powers of two, and each
    power-of-two width bucket the pool's widths reach."""
    widths = np.asarray([len(p.items) for p in st.pool])
    out = []
    rows = 64
    while rows <= MAX_BATCH:
        for k_hi in sorted({1 << int(np.ceil(np.log2(max(w, 8)))) for w in widths}):
            lead = np.where((widths > k_hi // 2) & (widths <= k_hi))[0]
            rest = np.where(widths <= k_hi)[0]
            if not len(lead):
                continue
            pats = [int(lead[0])] + rng.choice(rest, size=rows - 1).tolist()
            out.append([(st.items(p), int(np.argmax(st.pool[p].r_py))) for p in pats])
        rows *= 2
    return out


def warm_up(st: Setup) -> None:
    """Compile every routing-expansion shape bucket the window uses before
    it starts, then warm the event source, if any."""
    rng = np.random.default_rng(st.seed + 6)
    for batch in _warm_batches(st, rng):
        st.store.serve_batch(batch, observe=False)
    if st.events is not None:
        st.events.warm(st.store)


def settle_heap() -> None:
    """Collect set-up's garbage and move every object that survives it out
    of the collector's reach (``gc.freeze``), as a long-running server does
    once it has loaded: a collection in the window then scans only the
    objects the window makes, not the store, the graph and the libraries.
    ``unsettle_heap`` hands them back once the window has closed."""
    gc.collect()
    gc.freeze()


def unsettle_heap() -> None:
    gc.unfreeze()
    gc.collect()


# ------------------------------------------------------------------ window
class Window:
    """What the measured window leaves for the metrics and the check."""

    def __init__(self, n: int) -> None:
        self.done = np.full(n, np.nan)  # completion, seconds after window start
        self.wait = np.full(n, np.nan)  # scheduled arrival -> dispatch
        self.serve_calls: List[tuple] = []  # (t0, t1, requests, items, serve_s)
        # read -> (items, origin, result, replica rows of the items when it
        # was answered, events applied by then)
        self.samples: Dict[int, tuple] = {}
        self.events_kept = None  # the event source's keep(store)
        self.trace_dir: Optional[str] = None
        self.trace_span = (math.nan, math.nan)  # perf_counter instants
        self.trace_open = math.nan  # seconds after the window's start
        self.compiles = 0
        self.compile_s = 0.0
        self.drain_s = 0.0
        self.gc_pauses: List[tuple] = []  # (generation, seconds) of each collection


def gc_meter(pauses: List[tuple]):
    """Record the generation and length of every garbage collection from
    here on; returns the callback, to be removed from ``gc.callbacks``."""
    started = [0.0]

    def cb(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            pauses.append((int(info["generation"]), time.perf_counter() - started[0]))

    gc.callbacks.append(cb)
    return cb


def _trace_options():
    """Host spans and device activity, without the Python function tracer
    (which records every call and slows the host several times over)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_window(st: Setup, trace_dir: Optional[str] = None) -> Window:
    import jax

    from repro.serve import AdmissionConfig, AdmissionController, StoreClient

    req = st.requests
    n = len(req.t)
    S = st.seconds
    win = Window(n)
    sample_rng = np.random.default_rng(st.seed + 5)
    sampled = set(sample_rng.choice(n, size=min(n, CHECK_SAMPLE), replace=False).tolist())

    store = st.store
    serve = store.serve_batch

    def timed_serve(requests, observe=True):
        k = sum(len(it) for it, _ in requests)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.serve_batch", requests=len(requests), items=k):
            out = serve(requests, observe=observe)
        t1 = time.perf_counter()
        win.serve_calls.append((t0, t1, len(requests), k, store.last_serve_seconds))
        return out

    store.serve_batch = timed_serve
    clock = WallClock()
    ctl = AdmissionController(
        store,
        AdmissionConfig(policy=POLICY, max_batch=MAX_BATCH, service_model="measured"),
        clock=clock,
    )
    client = StoreClient(ctl)
    trace_at = (TRACE_FROM * S, TRACE_FROM * S + TRACE_SECONDS)
    tracing = False
    i = 0
    compiles = compile_meter()
    c0 = dict(compiles)
    gc_cb = gc_meter(win.gc_pauses)

    def finish(handles, t_ret):
        for h in handles:
            if h.result is None:  # returned without an answer: never answered
                continue
            r = h.rid
            win.done[r] = t_ret
            win.wait[r] = h.t_dispatch - h.t_submit
            if r in sampled:
                items = np.array(h.items)
                win.samples[r] = (items, h.origin, h.result, store.state.delta[items],
                                  st.version())

    clock.start()
    while True:
        now = clock.now()
        if now >= S:
            break
        if trace_dir is not None:
            if not tracing and now >= trace_at[0] and math.isnan(win.trace_span[0]):
                jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
                tracing = True
                win.trace_span = (time.perf_counter(), math.nan)
                win.trace_open = now
                # the traced stretch runs TRACE_SECONDS from when the
                # profiler is up, however long it took to start
                trace_at = (trace_at[0], clock.now() + TRACE_SECONDS)
            elif tracing and now >= trace_at[1]:
                win.trace_span = (win.trace_span[0], time.perf_counter())
                jax.profiler.stop_trace()
                tracing = False
        if st.events is not None:
            with jax.profiler.TraceAnnotation("bench.event", source=st.event_source):
                st.events.step(store, now)
        while i < n and req.t[i] <= now:
            client.submit(st.items(int(req.pattern[i])), int(req.origin[i]),
                          at=float(req.t[i]))
            i += 1
        if ctl.pending == 0:
            if i < n:  # the controller sleeps until this arrival
                client.submit(st.items(int(req.pattern[i])), int(req.origin[i]),
                              at=float(req.t[i]))
                i += 1
            else:
                clock.jump_to(S)
                break
        with jax.profiler.TraceAnnotation("bench.drain"):
            done = ctl.step()
        finish(done, clock.now())
    if tracing:
        win.trace_span = (win.trace_span[0], time.perf_counter())
        jax.profiler.stop_trace()
    win.compiles = compiles["programs"] - c0["programs"]
    win.compile_s = compiles["seconds"] - c0["seconds"]
    gc.callbacks.remove(gc_cb)
    # every read that arrived in the window is answered, late or not
    t_drain = time.perf_counter()
    while i < n:
        client.submit(st.items(int(req.pattern[i])), int(req.origin[i]), at=float(req.t[i]))
        i += 1
    while ctl.pending and time.perf_counter() - t_drain < DRAIN_LIMIT_S:
        finish(ctl.step(), clock.now())
    win.drain_s = time.perf_counter() - t_drain
    store.serve_batch = serve
    win.trace_dir = trace_dir
    return win


# ----------------------------------------------------------------- metrics
def end_to_end(st: Setup, win: Window) -> Dict[str, float]:
    lat_ms = (win.done - st.requests.t) * 1e3
    ok = ~np.isnan(lat_ms)
    out = {}
    if ok.any():
        out["read_p50_ms"] = float(np.percentile(lat_ms[ok], 50))
        out["read_p99_ms"] = float(np.percentile(lat_ms[ok], 99))
    out["reads_per_s"] = float((win.done <= st.seconds).sum() / st.seconds)
    return out


def before_trace(win: Window) -> np.ndarray:
    """Reads answered before the profiler started, all answered reads in an
    untraced window: starting and stopping the profiler stands the host
    still for most of a second, which host-side latency readers leave out."""
    ok = ~np.isnan(win.done)
    return ok & (win.done < win.trace_open) if math.isfinite(win.trace_open) else ok


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


# ------------------------------------------------------------------- check
def _sizes_and_components(g: gen.GraphArrays, reg: reference.Regions):
    """Item bytes and the region components of the alive edges of ``g``."""
    sizes = np.concatenate([g.node_size, g.edge_size]).astype(np.float64)
    src, dst = g.src, g.dst
    if g.edge_alive is not None:
        src, dst = src[g.edge_alive], dst[g.edge_alive]
    return sizes, reference.components(g.partition[src], g.partition[dst], reg)


def check(st: Setup, win: Window, route_dtype=np.float64) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit.  ``route_dtype`` puts the
    lower-precision control in the program's place (``bench/control.py``)."""
    lim = limits()
    nums: Dict[str, float] = {}
    n = len(st.requests.t)
    D = st.reg.n_dcs
    nums["reads_never_answered"] = float(np.isnan(win.done).sum())
    nums["sampled_reads"] = float(len(win.samples))
    # the primary copies the configuration guarantees, in the map and the
    # topology at the close: each alive vertex at its partition DC, each
    # alive edge at its source's
    g = st.topology(st.version())
    rep = st.replicas
    primary = np.concatenate([g.partition, g.partition[g.src]]).astype(np.int64)
    alive = np.where(g.alive_items())[0]
    if rep is None or rep.shape != (len(primary), D):
        nums["primary_copies_missing"] = float(len(alive))
    else:
        nums["primary_copies_missing"] = float((~rep[alive, primary[alive]]).sum())
    # each sampled read over the replica rows and the topology that served it
    topo = {}
    mism = 0
    lat_gap = 0.0
    wan_gap = 0.0
    for r in sorted(win.samples):
        items, origin, got, rows, version = win.samples[r]
        if version not in topo:
            topo[version] = _sizes_and_components(st.topology(version), st.reg)
        sizes, comp = topo[version]
        if len(items) and items.max() >= len(sizes):  # not an item of that topology
            mism += 1
            continue
        sz = sizes[items]
        want = reference.route(rows, sz, origin, comp, st.reg)
        served = got.served_by
        if route_dtype is not np.float64:
            got = reference.route(rows, sz, origin, comp, st.reg, dtype=route_dtype)
            served = got.served
        if not (np.array_equal(served, want.served) and int(got.layers_used) == want.layers_used):
            mism += 1
        if want.latency_s > 0:
            lat_gap = max(lat_gap, abs(got.latency_s - want.latency_s) / want.latency_s)
        wan_gap = max(wan_gap, abs(got.wan_bytes - want.wan_bytes) / max(float(sz.sum()), 1.0))
    nums["route_mismatches"] = float(mism)
    nums["latency_rel_gap"] = lat_gap
    nums["wan_rel_gap"] = wan_gap
    out = {}
    for k, v in nums.items():
        if k == "sampled_reads":
            out[k] = {"value": v, "limit": float(min(n, CHECK_SAMPLE))}
        else:
            out[k] = {"value": v, "limit": float(lim[k])}
    if st.events is not None:
        for k, c in st.events.check(win.events_kept, st).items():
            if k in out:
                raise ValueError(f"event source {st.event_source!r} reuses check name {k!r}")
            out[k] = {"value": float(c["value"]), "limit": float(c["limit"])}
    return out


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    ok = True
    for k, c in checks.items():
        if k == "sampled_reads":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


# -------------------------------------------------------------- one run
def run_cell(cell: Dict, cfg: Dict, traffic: Dict, seed: int, seconds: float,
             trace: bool, t_process: float, trace_root: Optional[pathlib.Path] = None,
             keep=None) -> Dict:
    """Set up, warm, measure and check one run; returns the result line's
    dict (``keep`` receives the setup and window for callers that check more)."""
    from repro.obs import MetricsRegistry, get_registry, set_default_registry

    st = Setup(cfg, traffic, seed, seconds)
    st.build_store()
    warm_up(st)
    settle_heap()
    old_reg = None
    if trace:
        old_reg = set_default_registry(MetricsRegistry(enabled=True))
    setup_s = time.perf_counter() - t_process
    trace_dir = None
    if trace:
        root = trace_root or (REPO / "bench" / "out")
        trace_dir = str(root / f"trace-{cell['name']}-{seed}")
    try:
        win = run_window(st, trace_dir)
        reg = get_registry()
        spans = list(st.store.tracer.records) if trace else []
    finally:
        if old_reg is not None:
            set_default_registry(old_reg)
    e2e = end_to_end(st, win)
    mem = memory_peak_bytes()
    log(f"# window: {len(st.requests.t)} reads scheduled, {int((~np.isnan(win.done)).sum())} "
        f"answered, {len(win.serve_calls)} serve_batch calls, "
        f"drain after close {win.drain_s:.3f}s")
    log(f"# compiles inside the window: {win.compiles} ({win.compile_s:.3f}s)")
    log(f"# {window_stalls(win)}")
    log(f"# objects the collector tracks after the window: {len(gc.get_objects())}")
    lat_ms = (win.done - st.requests.t) * 1e3
    sec = np.floor(st.requests.t).astype(int)
    worst = sorted(((float(np.nanmax(lat_ms[sec == s])), s) for s in np.unique(sec)
                    if np.isfinite(lat_ms[sec == s]).any()), reverse=True)[:5]
    ok = np.isfinite(lat_ms)
    log("# read latency percentiles (p50 p90 p95 p99 p99.9, ms): "
        + " ".join(f"{v:.3f}" for v in np.percentile(lat_ms[ok], [50, 90, 95, 99, 99.9])))
    log("# slowest read by second of arrival (s: ms): "
        + ", ".join(f"{s}: {v:.1f}" for v, s in worst))
    log(f"# modelled Eq. 1 latency p99 (not in read latency): "
        f"{modelled_p99_ms(win):.3f} ms")
    if st.events is not None:
        log(f"# event source {st.event_source}: {st.version()} events applied in the window")
    ctx = None
    if trace:
        from . import tracefile

        ctx = {"st": st, "win": win, "registry": reg, "spans": spans, "e2e": e2e,
               "trace": tracefile.reduce(win.trace_dir) if win.trace_dir else None}
        if st.events is not None:
            ctx["events"] = st.events.record()
    st.replicas = np.array(st.store.state.delta, bool)
    if st.events is not None:
        win.events_kept = st.events.keep(st.store)
    st.store = None  # the program's state is freed before the reference runs
    unsettle_heap()
    checks = check(st, win)
    result = {
        "correct": passed(checks),
        "attempted": int(len(st.requests.t)),
        "failed": int(np.isnan(win.done).sum()),
        "setup_s": setup_s,
        "e2e": e2e,
        "memory_peak_bytes": mem,
        "ctx": ctx,
        "checks": checks,
    }
    if keep is not None:
        keep["st"], keep["win"] = st, win
    return result


def window_stalls(win: Window) -> str:
    """Where the host stood still in the window: garbage collections by
    generation, the longest ``serve_batch`` calls and the longest gaps
    between two calls."""
    gens = [[s for g, s in win.gc_pauses if g == k] for k in range(3)]
    gc_txt = ", ".join(f"gen{k} {len(p)} ({sum(p):.3f}s, max {max(p, default=0.0):.4f}s)"
                       for k, p in enumerate(gens))
    calls = np.asarray([c[1] - c[0] for c in win.serve_calls])
    starts = np.asarray([c[0] for c in win.serve_calls])
    ends = np.asarray([c[1] for c in win.serve_calls])
    gaps = starts[1:] - ends[:-1] if len(starts) > 1 else np.zeros(0)
    top = lambda a: ", ".join(f"{v * 1e3:.1f}" for v in np.sort(a)[::-1][:5])  # noqa: E731
    return (f"gc in the window: {gc_txt}; longest serve_batch ms: {top(calls)}; "
            f"longest gaps between serve_batch calls ms: {top(gaps)}; "
            f"calls and gaps over 50 ms: {int((calls > 0.05).sum())}, {int((gaps > 0.05).sum())}")


def modelled_p99_ms(win: Window) -> float:
    lat = [s[2].latency_s for s in win.samples.values()]
    return float(np.percentile(lat, 99) * 1e3) if lat else math.nan
