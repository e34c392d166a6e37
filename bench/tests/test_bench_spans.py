"""The program-span reduction (``bench/spans.py``) and its readers, on a
hand-made profile whose answer is known, and on traces recorded on one
TPU v5e during ``snb.read`` runs: one from a program that mirrors its
spans into the profiler, one (``snb_read_v5e.xplane.pb``) from before it
did, which must give no number and raise nothing."""
import pathlib
from types import SimpleNamespace as NS

import pytest

from bench import spans, tracefile
from bench.run import load_reader

DATA = pathlib.Path(__file__).resolve().parent / "data"
READERS = ["controller_ms.read", "route_prepare_ms.read", "route_device_ms.read",
           "route_epilogue_ms.read", "demand_deposit_ms.read", "idle_unattributed.read"]


def _ev(name, t_ms, d_ms, **stats):
    return NS(name=name, start_ns=t_ms * 1e6, duration_ns=d_ms * 1e6, stats=list(stats.items()))


def _profile(program=True):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("%a = f32[] add()", 12, 2),
                                   _ev("%route_expand.1 = s32[] custom-call()", 35, 3)]),
        NS(name="XLA Modules", events=[]),
    ])
    events = [_ev("bench.drain", 0, 100), _ev("bench.serve_batch", 20, 40, requests=2, items=9)]
    if program:
        events += [
            _ev("serve.step", 0, 90, requests=2),
            _ev("serve.form_batch", 2, 8),
            _ev("store.serve_batch", 20, 30, size=2),
            _ev("routing.prepare", 21, 9, requests=2, items=9),
            _ev("routing.item_size", 22, 3),
            _ev("routing.device", 30, 10, impl="kernel", r_pad=8, k_pad=8),
            _ev("routing.epilogue", 40, 8),
            _ev("demand.deposit", 50, 8, requests=2),
            _ev("serve.complete", 60, 20),
            _ev("routing.device", 150, 10),  # starts after the window
        ]
    lines = [NS(name="python3", events=events)]
    if program:  # a thread with fewer program spans than the serving one
        lines.append(NS(name="other", events=[_ev("serve.step", 0, 1)]))
    return NS(planes=[dev, NS(name="/host:CPU", lines=lines)])


def test_hand_made_profile():
    pd = _profile()
    red = spans.reduce_profile(pd, tracefile.reduce_profile(pd))
    assert red["window_s"] == pytest.approx(0.1)
    # device busy 12-14 and 35-38: idle 0-12, 14-35 and 38-100
    assert red["idle_s"] == pytest.approx(0.095)
    # the 14-35 gap crosses the step, serve_batch, prepare, item_size and
    # device spans and is split among them by where each was innermost
    assert red["idle_by_span"] == pytest.approx({
        "serve.step": 0.022, "serve.form_batch": 0.008, "store.serve_batch": 0.003,
        "routing.prepare": 0.006, "routing.item_size": 0.003, "routing.device": 0.007,
        "routing.epilogue": 0.008, "demand.deposit": 0.008, "serve.complete": 0.020})
    assert red["idle_unattributed_s"] == pytest.approx(0.010)
    assert spans.mean_ms(red, "serve.step", "self_s") == pytest.approx(24.0)
    assert spans.mean_ms(red, "store.serve_batch", "self_s") == pytest.approx(3.0)
    assert spans.mean_ms(red, "routing.prepare", "self_s") == pytest.approx(6.0)
    assert spans.mean_ms(red, "routing.device") == pytest.approx(10.0)
    assert len(red["spans"]["routing.device"]["dur_s"]) == 1
    # the controller: the step minus serve_batch and the deposit
    assert red["controller_s"] == pytest.approx([0.052])
    got = {name: load_reader(name)({"program_spans": red}) for name in READERS}
    assert got == pytest.approx({
        "controller_ms.read": 52.0, "route_prepare_ms.read": 9.0,
        "route_device_ms.read": 10.0, "route_epilogue_ms.read": 8.0,
        "demand_deposit_ms.read": 8.0, "idle_unattributed.read": 100 * 10 / 95})
    assert "controller per step: 52.0000 ms over 1 steps" in spans.table(red)


@pytest.fixture(scope="module")
def recorded():
    """1 s of an ``snb.read`` window (seed 2300014004), recorded with the
    program's spans mirrored: 74 served batches, every one on the kernel."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "snb_read_spans_v5e.xplane.pb"))
    red = tracefile.reduce_profile(pd)
    return pd, red, spans.reduce_profile(pd, red)


STAGES = ["serve.step", "serve.form_batch", "store.serve_batch", "routing.prepare",
          "routing.item_size", "routing.device", "routing.epilogue", "demand.deposit",
          "serve.complete"]


def test_recorded_spans_counts_and_stage_sums(recorded):
    _, red, got = recorded
    assert red["window_s"] == pytest.approx(1.006434865, rel=1e-9)
    assert {name: len(d["dur_s"]) for name, d in got["spans"].items()} == {
        name: 74 for name in STAGES}
    total = {name: sum(d["dur_s"]) for name, d in got["spans"].items()}
    assert total["serve.step"] == pytest.approx(0.936556947, rel=1e-6)
    assert total["store.serve_batch"] == pytest.approx(0.530038250, rel=1e-6)
    assert total["routing.device"] == pytest.approx(0.354923483, rel=1e-6)
    assert total["demand.deposit"] == pytest.approx(0.223207661, rel=1e-6)
    # the three routing stages hold all but the facade's own 2% of serve_batch
    stages = total["routing.prepare"] + total["routing.device"] + total["routing.epilogue"]
    assert 0.97 * total["store.serve_batch"] < stages < total["store.serve_batch"]
    # the controller's own time is the step less the store's spans inside it
    assert sum(got["controller_s"]) == pytest.approx(
        total["serve.step"] - total["store.serve_batch"] - total["demand.deposit"], rel=1e-6)
    # every idle instant goes to one span or to none
    assert got["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert got["idle_unattributed_s"] + sum(got["idle_by_span"].values()) == pytest.approx(
        got["idle_s"], rel=1e-9)
    assert 100 * got["idle_unattributed_s"] / got["idle_s"] == pytest.approx(6.9518, abs=1e-3)
    assert max(got["idle_by_span"], key=got["idle_by_span"].get) == "routing.device"


def test_recorded_spans_tags_and_kernel_names(recorded):
    pd, red, _ = recorded
    tags = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("serve.step", "routing.prepare", "routing.device"):
                    tags.setdefault(e.name, dict(e.stats))
    assert tags["serve.step"] == {"requests": 256}
    assert tags["routing.prepare"]["requests"] == 256 and tags["routing.prepare"]["items"] > 0
    assert tags["routing.device"]["impl"] == "kernel"
    # the named pallas_call keeps the op and module names the roofline reads
    assert tracefile.top(red["op_time"], 1)[0][0] == "%route_expand.1"
    calls = tracefile.device_time_in(red, "bench.serve_batch", "route_expand")
    assert len(calls) == 74 and all(dt > 0 for _, dt in calls)


def test_no_program_spans_gives_no_number():
    pd = _profile(program=False)
    assert spans.reduce_profile(pd, tracefile.reduce_profile(pd)) is None
    for name in READERS:
        assert load_reader(name)({"program_spans": None}) is None


def test_trace_without_program_spans_gives_no_number(tmp_path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "snb_read_v5e.xplane.pb"))
    assert spans.reduce_profile(pd, tracefile.reduce_profile(pd)) is None
    win = NS(trace_dir=str(tmp_path))  # no trace file there
    for name in READERS:
        assert load_reader(name)({"win": win, "trace": {"host": []}}) is None
