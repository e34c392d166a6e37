"""Telemetry subsystem (repro.obs): registry, tracer, exporters.

Bars under test:
  * P² histograms track numpy's exact quantiles on random streams, for the
    scalar ``observe`` path AND the batch ``observe_many`` path (including
    the sorted-batch marker seeding and heavily tied streams);
  * a disabled registry is a true no-op (shared singleton, nothing stored),
    and span context managers still measure elapsed time when tracing is
    off (report timing fields must not go to zero);
  * span nesting/parenting follows the context-manager stack, and explicit
    ``record()`` spans parent onto returned sids;
  * the Chrome trace-event export is deterministic under the scheduler's
    simulated clock: two identical runs serialize byte-identically;
  * scheduler miss-by-cause counts partition ``deadline_misses`` exactly
    and per-origin p99s cover every served origin (the BENCH_scheduler
    report fields);
  * store reports (``apply_time_s``) are sourced from the span tree;
  * an enabled tracer's live spans land in a running JAX profiler trace as
    host events with their tags, while disabled tracers and ``record()``
    spans leave none, and ``import repro.obs`` loads no jax.
"""
import glob
import json
import math
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core.graph import Graph, build_csr
from repro.core.latency import make_paper_env
from repro.core.patterns import Workload, generate_khop_patterns
from repro.core.placement import PlacementConfig
from repro.core.store import GeoGraphStore
from repro.obs import (
    Histogram,
    MetricsRegistry,
    P2Quantile,
    Tracer,
    export_chrome_trace,
    set_default_registry,
    text_dashboard,
)
from repro.obs.metrics import _NOOP
from repro.serve import AdmissionConfig, AdmissionController
from repro.serve.scheduler import SimClock
from repro.streaming import DeltaGraph, random_churn_batch


# ---------------------------------------------------------------- registry
def test_counter_gauge_identity_and_snapshot():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("requests", origin=3)
    c.inc()
    c.inc(4.0)
    assert reg.counter("requests", origin=3) is c  # keyed identity
    assert c.value == 5.0
    reg.gauge("watermark").set(7.5)
    snap = reg.snapshot()
    assert snap["requests"]["origin=3"] == {"type": "counter", "value": 5.0}
    assert snap["watermark"]["-"]["value"] == 7.5
    reg.reset()
    assert reg.counter("requests", origin=3).value == 0.0
    assert math.isnan(reg.gauge("watermark").value)


def test_merge_folds_counters_gauges_histograms():
    regs = [MetricsRegistry(enabled=True) for _ in range(3)]
    rng = np.random.default_rng(0)
    samples = []
    for i, reg in enumerate(regs):
        reg.counter("serving.requests").inc(10.0 * (i + 1))
        reg.counter("hits", layer=i % 2).inc(1.0)
        reg.gauge("watermark").set(float(i))
        s = rng.random(500)
        samples.append(s)
        reg.histogram("lat", quantiles=(0.5, 0.99)).observe_many(s)
    merged = MetricsRegistry.merge([r.snapshot() for r in regs])
    assert merged["serving.requests"]["-"]["value"] == 60.0
    # per-tag counters fold per tag, not globally
    assert merged["hits"]["layer=0"]["value"] == 2.0
    assert merged["hits"]["layer=1"]["value"] == 1.0
    # gauges: last non-NaN wins (point-in-time reading)
    assert merged["watermark"]["-"]["value"] == 2.0
    h = merged["lat"]["-"]
    allv = np.concatenate(samples)
    assert h["count"] == len(allv)
    assert h["sum"] == pytest.approx(allv.sum())
    assert h["min"] == pytest.approx(allv.min())
    assert h["max"] == pytest.approx(allv.max())
    # count-weighted quantile fold stays near the pooled-stream quantile
    assert h["quantiles"]["p50"] == pytest.approx(
        np.quantile(allv, 0.5), abs=0.05
    )
    # the internal weighting scratch must not leak into the snapshot
    assert "_qweight" not in h


def test_merge_grids_disjoint_inputs_and_type_clashes():
    a, b = MetricsRegistry(enabled=True), MetricsRegistry(enabled=True)
    a.counter_grid("wan", axes=("src", "dst")).add(np.array([[0.0, 3.0], [0.0, 0.0]]))
    b.counter_grid("wan", axes=("src", "dst")).add(np.array([[0.0, 1.0], [2.0, 0.0]]))
    b.counter("only_b").inc(7.0)
    merged = MetricsRegistry.merge([a.snapshot(), b.snapshot()])
    assert merged["wan"]["src=0,dst=1"]["value"] == 4.0
    assert merged["wan"]["src=1,dst=0"]["value"] == 2.0
    # names present in only one snapshot carry through unchanged
    assert merged["only_b"]["-"]["value"] == 7.0
    # merging must not mutate its inputs
    assert a.snapshot()["wan"]["src=0,dst=1"]["value"] == 3.0
    c = MetricsRegistry(enabled=True)
    c.gauge("only_b").set(1.0)  # same (name, tags) cell, different type
    with pytest.raises(ValueError):
        MetricsRegistry.merge([b.snapshot(), c.snapshot()])


def test_counter_keyed_matches_tagged():
    reg = MetricsRegistry(enabled=True)
    key = (("layer", "2"),)
    reg.counter_keyed("hits", key).inc(3.0)
    # the hot-path keyed accessor and the kwargs accessor share the store
    assert reg.counter("hits", layer=2).value == 3.0


def test_matrix_counter_grid_expands_like_tagged_counters():
    reg = MetricsRegistry(enabled=True)
    grid = reg.counter_grid("wan_bytes", axes=("src", "dst"))
    grid.add(np.array([[0.0, 10.0], [0.0, 0.0]]))
    grid.add(np.array([[0.0, 5.0, 0.0], [0.0, 0.0, 2.0], [1.0, 0.0, 0.0]]))
    snap = reg.snapshot()["wan_bytes"]
    # auto-grown shape, nonzero cells only, per-cell counter entries
    assert snap == {
        "src=0,dst=1": {"type": "counter", "value": 15.0},
        "src=1,dst=2": {"type": "counter", "value": 2.0},
        "src=2,dst=0": {"type": "counter", "value": 1.0},
    }
    reg.reset()
    assert reg.snapshot().get("wan_bytes", {}) == {}


def test_disabled_registry_is_shared_noop():
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("a") is _NOOP
    assert reg.gauge("b") is _NOOP
    assert reg.histogram("c") is _NOOP
    assert reg.counter_grid("d", axes=("i", "j")) is _NOOP
    _NOOP.inc()
    _NOOP.set(3.0)
    _NOOP.observe(1.0)
    _NOOP.observe_many([1.0, 2.0])
    _NOOP.add(np.ones((2, 2)))
    assert reg.snapshot() == {}  # nothing was ever stored
    reg.enable()
    assert reg.counter("a") is not _NOOP


def test_to_json_round_trips(tmp_path):
    reg = MetricsRegistry(enabled=True)
    reg.counter("x").inc(2.0)
    path = tmp_path / "metrics.json"
    text = reg.to_json(str(path))
    assert json.loads(path.read_text()) == json.loads(text)
    assert json.loads(text)["x"]["-"]["value"] == 2.0


# -------------------------------------------------------------- histograms
def test_p2_exact_below_five_samples():
    sk = P2Quantile(0.5)
    for v in [3.0, 1.0, 2.0]:
        sk.add(v)
    assert sk.value() == 2.0  # exact small-sample median


@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
def test_histogram_scalar_accuracy_vs_numpy(q):
    rng = np.random.default_rng(17)
    data = rng.normal(10.0, 2.0, 20_000)
    h = Histogram("lat", quantiles=(q,))
    for v in data:
        h.observe(v)
    true = float(np.quantile(data, q))
    assert abs(h.quantile(q) - true) < 0.05  # P² on N(10, 2): tight
    assert h.count == len(data)
    assert h.sum == pytest.approx(data.sum())
    assert h.min == data.min() and h.max == data.max()


@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
def test_histogram_batched_accuracy_vs_numpy(q):
    """observe_many (batch-P²: sorted-batch seeding + rank-count advance)
    must track numpy as closely as the scalar path."""
    rng = np.random.default_rng(23)
    data = rng.normal(10.0, 2.0, 20_000)
    h = Histogram("lat", quantiles=(q,))
    for chunk in np.array_split(data, 80):  # 250-value batches
        h.observe_many(chunk)
    true = float(np.quantile(data, q))
    assert abs(h.quantile(q) - true) < 0.05
    assert h.count == len(data)
    assert h.sum == pytest.approx(data.sum())
    assert h.min == data.min() and h.max == data.max()


def test_histogram_batched_tied_stream():
    """Serving latencies are heavily tied (RTT-quantized).  The capped
    settle pass must still land inside the tie neighbourhood."""
    rng = np.random.default_rng(5)
    rtts = np.array([0.0, 0.04, 0.08, 0.12, 0.226])
    data = rtts[rng.integers(0, 5, 8_000)] + 0.0  # ~5 distinct values
    h = Histogram("lat", quantiles=(0.5, 0.99))
    for chunk in np.array_split(data, 32):
        h.observe_many(np.sort(chunk))
    # estimates must sit within the discrete support's neighbouring levels
    assert abs(h.quantile(0.5) - np.quantile(data, 0.5)) <= 0.05
    assert abs(h.quantile(0.99) - np.quantile(data, 0.99)) <= 0.05


def test_observe_many_small_batches_fall_back_to_scalar():
    h1 = Histogram("a", quantiles=(0.5,))
    h2 = Histogram("b", quantiles=(0.5,))
    vals = [5.0, 1.0, 3.0]
    for v in vals:
        h1.observe(v)
    h2.observe_many(sorted(vals))  # < 5 samples: exact path either way
    assert h1.quantile(0.5) == h2.quantile(0.5) == 3.0
    h = Histogram("c")
    h.observe_many([])  # empty batch is a no-op
    assert h.count == 0


# ----------------------------------------------------------------- tracing
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0  # each clock read advances one tick
        return self.t


def test_span_nesting_and_parenting():
    tr = Tracer(clock=_FakeClock(), enabled=True)
    with tr.span("outer", track="store", batch=7) as outer:
        with tr.span("inner", track="store") as inner:
            assert inner.parent == outer.sid
        with tr.span("inner2", track="store") as inner2:
            assert inner2.parent == outer.sid
    assert outer.parent is None
    recs = {r.name: r for r in tr.records}
    assert recs["inner"].parent == recs["outer"].sid
    assert recs["outer"].tags == {"batch": 7}
    # inner closed before outer: t0/t1 nest strictly under the fake clock
    assert recs["outer"].t0 < recs["inner"].t0 < recs["inner"].t1 < recs["outer"].t1
    assert recs["outer"].dur_s > 0


def test_record_explicit_parenting():
    tr = Tracer(enabled=True)
    root = tr.record("request", 0.0, 5.0, track="requests", origin=2)
    child = tr.record("queue", 0.0, 1.0, track="requests", parent=root)
    assert root is not None and child == root + 1
    by_sid = {r.sid: r for r in tr.records}
    assert by_sid[child].parent == root
    assert by_sid[root].tags == {"origin": 2}


def test_disabled_tracer_noop_span_still_measures():
    clk = _FakeClock()
    tr = Tracer(clock=clk, enabled=False)
    with tr.span("work", track="store") as sp:
        mid = sp.elapsed_s()
    assert len(tr.records) == 0  # nothing retained...
    assert mid > 0 and sp.end() > 0  # ...but elapsed time is real
    assert sp.end() == sp.end()  # end() idempotent


def test_tracer_follows_default_registry_when_unforced():
    tr = Tracer()  # enabled=None: follows the process-default registry
    old = set_default_registry(MetricsRegistry(enabled=True))
    try:
        assert tr.enabled
        with tr.span("s", track="t"):
            pass
        assert len(tr.records) == 1
    finally:
        set_default_registry(old)
    assert not tr.enabled


def test_tracer_reset():
    tr = Tracer(enabled=True)
    tr.record("a", 0.0, 1.0)
    tr.reset()
    assert len(tr) == 0
    assert tr.record("b", 0.0, 1.0) == 0  # sids restart


# ------------------------------------------------- mirror into the profiler
@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Host events of one profiler session in which an enabled tracer, a
    disabled one and ``record()`` each produced spans: name -> [stats]."""
    import jax
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("profile")
    on = Tracer(enabled=True)
    off = Tracer(enabled=False)
    jax.profiler.start_trace(str(d))
    try:
        with on.span("obs.outer", track="t", requests=3, impl="kernel", frac=0.5,
                     skipped=[1]) as outer:
            outer.set_tags(items=12)
            with on.span("obs.inner", track="t"):
                pass
        with off.span("obs.disabled", track="t", requests=1):
            pass
        on.record("obs.recorded", 0.0, 1.0, track="t", requests=2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(d / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("obs."):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    return on, events


def test_live_span_lands_in_profiler_trace(profiled):
    tracer, events = profiled
    (outer,), (inner,) = events["obs.outer"], events["obs.inner"]
    # int, float and string tags pass, the late tag too; a list does not
    assert outer[2] == {"requests": 3, "impl": "kernel", "frac": 0.5, "items": 12}
    assert inner[2] == {}
    assert outer[0] <= inner[0] and inner[0] + inner[1] <= outer[0] + outer[1]
    # the tracer's own records are unchanged by the mirror
    recs = {r.name: r for r in tracer.records}
    assert recs["obs.outer"].tags == {"requests": 3, "impl": "kernel", "frac": 0.5,
                                      "skipped": [1], "items": 12}
    assert recs["obs.inner"].parent == recs["obs.outer"].sid


def test_disabled_and_recorded_spans_leave_no_event(profiled):
    _, events = profiled
    assert "obs.disabled" not in events
    assert "obs.recorded" not in events


def test_import_obs_loads_no_jax():
    import repro

    src = str(pathlib.Path(repro.__file__).resolve().parents[1])
    code = "import sys, repro.obs; print('jax' in sys.modules)"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=dict(os.environ, PYTHONPATH=src), timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"


# --------------------------------------------------------------- exporters
def test_chrome_export_shape_and_lanes(tmp_path):
    tr = Tracer(enabled=True)
    r0 = tr.record("req", 0.0, 2.0, track="requests", origin=1)
    tr.record("queue", 0.0, 1.0, track="requests", parent=r0)
    tr.record("req", 1.0, 3.0, track="requests", origin=2)  # overlaps r0
    tr.record("wave", 0.0, 1.0, track="migration")
    path = tmp_path / "t.trace.json"
    doc = json.loads(export_chrome_trace(tr, str(path)))
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    spans = [e for e in events if e["ph"] == "X"]
    assert {m["args"]["name"] for m in meta} == {"requests", "migration"}
    # overlapping roots spread across lanes; the child shares its root's lane
    req = [e for e in spans if e["pid"] == next(
        m["pid"] for m in meta if m["args"]["name"] == "requests")]
    lanes = {(e["name"], e["ts"]): e["tid"] for e in req}
    assert lanes[("req", 0.0)] != lanes[("req", 1e6)]
    assert lanes[("queue", 0.0)] == lanes[("req", 0.0)]
    assert all(isinstance(v, str) for e in spans for v in e["args"].values())
    assert path.read_text().rstrip("\n") == json.dumps(
        doc, sort_keys=True, separators=(",", ":"))


def test_text_dashboard_lists_instruments_and_spans():
    reg = MetricsRegistry(enabled=True)
    reg.counter("serving.requests").inc(12)
    reg.histogram("lat", quantiles=(0.5,)).observe_many(np.arange(10.0))
    tr = Tracer(enabled=True)
    tr.record("drain", 0.0, 1.0, track="scheduler")
    dash = text_dashboard(reg, tr)
    assert "serving.requests" in dash and "counter=12" in dash
    assert "p50=" in dash
    assert "scheduler/drain" in dash and "n=1" in dash


# ------------------------------------------------- scheduler integration
def _tiny_store(seed=0, n=160, m=900, n_pats=16):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = src != dst
    g = Graph.from_edges(
        n, src[keep], dst[keep], partition=rng.integers(0, 4, n)
    )
    env = make_paper_env()
    csr = build_csr(g.n_nodes, g.src, g.dst, symmetrize=True)
    pats = generate_khop_patterns(g, csr, n_pats, seed=seed + 1, n_dcs=env.n_dcs)
    wl = Workload.from_patterns(pats, g.n_items, env.n_dcs)
    return GeoGraphStore(
        g, env, wl, config=PlacementConfig(precache=False, dhd_steps=4)
    )


def _traced_run(seed=0, n_req=40, deadline_s=0.05):
    store = _tiny_store(seed)
    clock = SimClock()
    tracer = Tracer(clock=clock.now, enabled=True)
    ctl = AdmissionController(
        store, AdmissionConfig(initial_batch=4, max_batch=16),
        clock=clock, tracer=tracer,
    )
    rng = np.random.default_rng(seed + 7)
    pats = [p for p in store.workload.patterns if len(p.items)]
    for i in range(n_req):
        p = pats[int(rng.integers(0, len(pats)))]
        ctl.submit(p.items, origin=int(rng.integers(0, store.env.n_dcs)),
                   deadline_s=deadline_s, at=0.001 * i)
    ctl.run_until_idle()
    return ctl, tracer


def test_sim_clock_trace_export_is_deterministic():
    _, tr_a = _traced_run(seed=3)
    _, tr_b = _traced_run(seed=3)
    a = export_chrome_trace(tr_a)
    b = export_chrome_trace(tr_b)
    assert a == b  # byte-identical: same seed, same simulated timeline
    names = {r.name for r in tr_a.records}
    assert {"request", "queue", "route", "wan_fetch", "drain",
            "serve.step", "serve.form_batch", "serve.complete"} <= names
    steps = [r for r in tr_a.records if r.name == "serve.step"]
    served = [r for r in steps if "requests" in r.tags]
    assert served and sum(r.tags["requests"] for r in served) == 40
    by_sid = {r.sid: r for r in tr_a.records}
    for r in tr_a.records:
        if r.name in ("serve.form_batch", "serve.complete"):
            assert by_sid[r.parent].name == "serve.step"


def test_miss_causes_partition_deadline_misses():
    # a deadline tighter than any WAN RTT forces misses across causes
    ctl, _ = _traced_run(seed=1, n_req=60, deadline_s=0.004)
    m = ctl.metrics()
    assert m["deadline_misses"] > 0
    assert sum(m["misses_by_cause"].values()) == m["deadline_misses"]
    assert set(m["misses_by_cause"]) == {"queue", "service", "straggler"}
    # per-origin p99 covers exactly the origins that completed requests
    assert set(m["p99_by_origin"]) == set(m["served_by_origin"])
    for p99 in m["p99_by_origin"].values():
        assert p99 >= 0.0


# ------------------------------------------------------ store span sourcing
def test_store_report_times_sourced_from_spans():
    store = _tiny_store(seed=9)
    store._delta_graph = DeltaGraph(store.g)
    old = set_default_registry(MetricsRegistry(enabled=True))
    try:
        store.tracer.reset()
        rng = np.random.default_rng(11)
        report = store.apply_updates(
            random_churn_batch(store._delta_graph, 0.02, rng)
        )
    finally:
        set_default_registry(old)
    recs = [r for r in store.tracer.records if r.name == "store.apply_updates"]
    assert len(recs) == 1
    # the public report field is the root span's elapsed time (read just
    # before the span closes), not a hand-threaded perf_counter delta — so
    # it must sit within the recorded span, a sliver under its duration
    assert 0.0 < report.apply_time_s <= recs[0].dur_s
    assert report.apply_time_s == pytest.approx(recs[0].dur_s, rel=0.05)


def test_store_serve_batch_spans_the_demand_deposit():
    store = _tiny_store(seed=4)
    store.tracer = Tracer(enabled=True)
    pats = [p for p in store.workload.patterns if len(p.items)][:6]
    reqs = [(p.items, i % store.env.n_dcs) for i, p in enumerate(pats)]
    store.serve_batch(reqs)
    roots = [r for r in sorted(store.tracer.records, key=lambda r: r.t0)
             if r.parent is None]
    assert [r.name for r in roots] == ["store.serve_batch", "demand.deposit"]
    assert roots[1].tags == {"requests": len(reqs)}
    assert roots[0].t1 <= roots[1].t0
