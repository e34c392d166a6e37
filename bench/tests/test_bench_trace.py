"""The trace reduction, on a small trace recorded on one TPU v5e during an
``snb.read`` run (2 s of the window, 96 served batches of 256 reads), and on
a hand-made profile whose answer is known."""
import pathlib
from types import SimpleNamespace as NS

import pytest

from bench import tracefile

DATA = pathlib.Path(__file__).resolve().parent / "data" / "snb_read_v5e.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return tracefile.reduce_profile(ProfileData.from_file(str(DATA)))


def test_recorded_trace_window_and_busy(recorded):
    assert recorded["devices"] == ["/device:TPU:0"]
    assert recorded["window_s"] == pytest.approx(1.957597078, rel=1e-9)
    assert recorded["busy_s"] == pytest.approx(0.001714202, rel=1e-6)
    idle = sum(recorded["idle_by_span"].values())
    assert idle == pytest.approx(recorded["window_s"] - recorded["busy_s"], rel=1e-9)
    assert set(recorded["idle_by_span"]) == {"bench.serve_batch", "bench.drain"}


def test_recorded_trace_ops_and_spans(recorded):
    ops = tracefile.top(recorded["op_time"], 3)
    assert ops[0][0] == "%route_expand.1"
    assert ops[0][1] == pytest.approx(0.001048928, rel=1e-6)
    calls = tracefile.device_time_in(recorded, "bench.serve_batch", "route_expand")
    assert len(calls) == 96
    assert all(s["stats"]["requests"] == 256 and dt > 0 for s, dt in calls)
    assert sum(dt for _, dt in calls) == pytest.approx(0.001745032, rel=1e-6)


def _ev(name, t_ms, d_ms, **stats):
    return NS(name=name, start_ns=t_ms * 1e6, duration_ns=d_ms * 1e6, stats=list(stats.items()))


def test_hand_made_profile():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            _ev("%a = f32[] add()", 10, 5), _ev("%b = f32[] mul()", 12, 6),  # union 10-18
            _ev("%a = f32[] add()", 40, 10),  # 40-50
            _ev("%c = f32[] sub()", 200, 5),  # outside the window
        ]),
        NS(name="XLA Modules", events=[_ev("jit_route_expand(1)", 10, 8),
                                       _ev("jit_other(2)", 40, 10)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.drain", 0, 100),
        _ev("bench.serve_batch", 5, 20, requests=3, items=30),
        _ev("bench.wait", 60, 40),
        _ev("other", 0, 300),
    ])])
    red = tracefile.reduce_profile(NS(planes=[dev, host]))
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.018)
    assert red["op_time"] == pytest.approx({"%a": 0.015, "%b": 0.006})
    # gaps: 0-10 and 18-40 (mid 5 and 29: serve_batch spans 5-25, drain
    # otherwise), 50-100 (mid 75: wait)
    assert red["idle_by_span"] == pytest.approx(
        {"bench.serve_batch": 0.010, "bench.drain": 0.022, "bench.wait": 0.050})
    (span, dt), = tracefile.device_time_in(red, "bench.serve_batch", "route_expand")
    assert span["stats"] == {"requests": 3, "items": 30} and dt == pytest.approx(0.008)


def test_idle_inside_an_event_is_charged_to_it():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("%a = f32[] add()", 10, 5)]),
        NS(name="XLA Modules", events=[]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.event", 0, 40, source="toy"),
        _ev("bench.drain", 40, 60),
        _ev("bench.serve_batch", 50, 20, requests=1, items=3),
    ])])
    red = tracefile.reduce_profile(NS(planes=[dev, host]))
    # gaps: 0-10 (event), 15-100 (mid 57.5: serve_batch)
    assert red["idle_by_span"] == pytest.approx(
        {"bench.event": 0.010, "bench.serve_batch": 0.085})
    assert [s["stats"] for s in red["host"] if s["name"] == "bench.event"] == [{"source": "toy"}]
