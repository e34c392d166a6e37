"""The check that decides ``correct`` fails where it must: under the
lower-precision control (the reference in the program's place, in
bfloat16), and with the timed path broken underneath a whole run."""
import ml_dtypes
import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import run_tiny


@pytest.fixture(scope="module")
def read_run():
    keep = {}
    _, res = run_tiny("snb.read", keep=keep)
    return res, keep


def test_control_fails_the_read_cell(read_run):
    res, keep = read_run
    assert res["correct"]
    ctrl = harness.check(keep["st"], keep["win"], route_dtype=ml_dtypes.bfloat16)
    assert not harness.passed(ctrl)
    assert ctrl["latency_rel_gap"]["value"] > ctrl["latency_rel_gap"]["limit"]
    assert ctrl["route_mismatches"]["value"] == 0  # the picks are integers


def test_reads_without_events_are_checked_over_the_map_placement_left(read_run):
    """With no event source the map does not move in the window: every
    sampled read's recorded rows are the map's at the close, at version 0."""
    res, keep = read_run
    st, win = keep["st"], keep["win"]
    assert st.events is None and len(win.samples) == 384
    for items, _, _, rows, version in win.samples.values():
        assert version == 0
        assert rows.dtype == bool and np.array_equal(rows, st.replicas[items])


def test_a_missing_primary_copy_is_not_correct(read_run):
    res, keep = read_run
    st, win = keep["st"], keep["win"]
    assert res["checks"]["primary_copies_missing"]["value"] == 0
    g = st.graph
    saved = st.replicas
    try:
        st.replicas = saved.copy()
        st.replicas[g.n_nodes + 3, g.partition[g.src[3]]] = False  # edge 3's home copy
        checks = harness.check(st, win)
    finally:
        st.replicas = saved
    assert checks["primary_copies_missing"]["value"] == 1
    assert not harness.passed(checks)


def _alter_answers(monkeypatch):
    import repro.core.store as store_mod

    real = store_mod.route_online_batch

    def altered(lg, state, reqs, **kw):
        out = real(lg, state, reqs, **kw)
        for r in out:
            if len(r.served_by):
                r.served_by = r.served_by.copy()
                r.served_by[0] = (r.served_by[0] + 1) % lg.env.n_dcs
        return out

    monkeypatch.setattr(store_mod, "route_online_batch", altered)


def _drop_half(monkeypatch):
    import repro.core.store as store_mod

    real = store_mod.route_online_batch

    def half(lg, state, reqs, **kw):
        keep = max(1, len(reqs) // 2)
        out = real(lg, state, reqs[:keep], **kw)
        return (out * (len(reqs) // keep + 1))[: len(reqs)]

    monkeypatch.setattr(store_mod, "route_online_batch", half)


def _replicas_dropped(monkeypatch):
    """Reads routed over a replica map that lost every copy beyond the
    primaries, in place of the map placement left."""
    import dataclasses

    import repro.core.store as store_mod

    real = store_mod.route_online_batch

    def primaries_only(lg, state, reqs, **kw):
        g = lg.g
        home = np.concatenate([g.partition, g.partition[g.src]])
        delta = np.zeros_like(state.delta)
        delta[np.arange(len(home)), home] = True
        return real(lg, dataclasses.replace(state, delta=delta), reqs, **kw)

    monkeypatch.setattr(store_mod, "route_online_batch", primaries_only)


FAULTS = [
    (_alter_answers, "route_mismatches"),
    (_drop_half, "route_mismatches"),
    (_replicas_dropped, "route_mismatches"),
]


@pytest.mark.parametrize("fault,number", FAULTS,
                         ids=[f"snb.read-{f.__name__.strip('_')}" for f, _ in FAULTS])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault, number):
    fault(monkeypatch)
    _, res = run_tiny("snb.read", seed=2**31 + 23)
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]
    assert np.isfinite(res["attempted"])
