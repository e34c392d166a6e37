"""An event source joins a cell as one new file: the toy churn source
(``bench/tests/toy_churn.py``, found through a redirected events folder)
acts on the store inside a tiny window, its own check joins ``correct``,
and every sampled read is checked against the replica rows and the
topology that served it."""
import pathlib

import numpy as np
import pytest

from bench import files, harness
from bench.tests.tiny import run_tiny

HERE = pathlib.Path(__file__).resolve().parent
# closed loop after 0.3 s of reads over the map placement left
TOY = {"source": "toy_churn", "batches": 12, "rate": 0.001, "vertex_fraction": 0.1,
       "interval_s": 0.3}


@pytest.fixture
def toy_events(monkeypatch):
    monkeypatch.setitem(files.DIRS, "events", HERE)


@pytest.fixture(scope="module")
def churn_run():
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(files.DIRS, "events", HERE)
        keep = {}
        _, res = run_tiny("snb.read", seed=2**31 + 41, keep=keep, seconds=3.0, events=TOY)
    return res, keep


def test_event_source_runs_and_is_checked(churn_run):
    res, keep = churn_run
    st, win = keep["st"], keep["win"]
    assert res["correct"], res["checks"]
    assert res["checks"]["topology_mismatches"] == {"value": 0.0, "limit": 0.0}
    assert st.events.version >= 1
    versions = {s[4] for s in win.samples.values()}
    assert 0 in versions and max(versions) >= 1, versions
    # the store grew with the events, and the check read its map at the close
    assert st.replicas.shape[0] == len(st.topology(st.version()).alive_items())
    assert st.replicas.shape[0] > st.graph.n_nodes + len(st.graph.src)


def test_check_over_the_warm_up_map_misses_after_an_event(churn_run):
    """The reads served after an event, routed over the map the window
    opened on (grown to the new item count) in place of the rows that
    served them, no longer match: the check must read what served them."""
    res, keep = churn_run
    st, win = keep["st"], keep["win"]
    opened = st.events.open_map
    grown = np.zeros((len(st.replicas), opened.shape[1]), bool)
    grown[: len(opened)] = opened
    saved = win.samples
    try:
        win.samples = {r: (it, o, got, grown[it], 0) for r, (it, o, got, _, _) in saved.items()}
        checks = harness.check(st, win)
    finally:
        win.samples = saved
    assert checks["route_mismatches"]["value"] > 0
    assert not harness.passed(checks)


def test_store_losing_part_of_an_event_is_not_correct(toy_events, monkeypatch):
    """The store drops the edge deletions of the first batch it is given:
    the source's own check sees its topology at the close differ from the
    events applied."""
    import dataclasses

    from repro.core.store import GeoGraphStore

    real = GeoGraphStore.apply_updates
    seen = []

    def drops_deletions(self, batch):
        if batch.n_ops and not seen:
            seen.append(batch)
            batch = dataclasses.replace(batch, del_edge_ids=batch.del_edge_ids[:0])
        return real(self, batch)

    monkeypatch.setattr(GeoGraphStore, "apply_updates", drops_deletions)
    _, res = run_tiny("snb.read", seed=2**31 + 43, events=dict(TOY, interval_s=0.0))
    assert seen and len(seen[0].del_edge_ids)
    c = res["checks"]["topology_mismatches"]
    assert c["value"] > c["limit"], res["checks"]
    assert not res["correct"]


def test_traced_run_records_events_and_their_spans(toy_events, tmp_path):
    _, res = run_tiny("snb.read", seed=2**31 + 47, trace=True, tmp=tmp_path, events=TOY)
    assert res["correct"], res["checks"]
    rec = res["ctx"]["events"]
    assert rec["applied"] >= 1 and len(rec["apply_s"]) == rec["applied"] and all(rec["ops"])
    host = res["ctx"]["trace"]["host"]
    spans = [s for s in host if s["name"] == "bench.event"]
    assert spans and all(s["stats"]["source"] == "toy_churn" for s in spans)


def test_unknown_event_source_names_the_file(toy_events):
    with pytest.raises(FileNotFoundError, match=r"no_such_source\.py"):
        run_tiny("snb.read", events={"source": "no_such_source"})
