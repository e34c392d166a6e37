"""Each generator copied into the benchmark reproduces the program's own bit
for bit at a small seed, the reference's region components are the
program's layered graph's, and a graph generator is found by its file."""
import json
import pathlib

import numpy as np
import pytest

from bench import files, gen, reference
from bench.tests.tiny import run_tiny

CONFIG = pathlib.Path(__file__).resolve().parents[1] / "configs" / "ldbc-snb-sf10.5dc.json"
community = files.load("graphs", "community_graph")


def _same_graph(a, b):
    assert a.n_nodes == b.n_nodes
    for f in ("src", "dst", "node_size", "edge_size", "partition"):
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype and np.array_equal(x, y), f


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_community_graph_matches_program(seed):
    from repro.data.synthetic import community_graph

    kw = dict(n_communities=7, p_in=0.05, p_out=0.002, seed=seed, n_dcs=5, geo_affinity=0.8)
    _same_graph(community.community_graph(500, **kw), community_graph(500, **kw))


def test_khop_patterns_match_program():
    from repro.core.graph import Graph, build_csr
    from repro.core.patterns import generate_khop_patterns

    ga = community.community_graph(400, n_communities=6, p_in=0.05, p_out=0.003, seed=8)
    g = Graph(ga.n_nodes, ga.src, ga.dst, ga.node_size, ga.edge_size, ga.partition)
    csr = build_csr(g.n_nodes, g.src, g.dst, symmetrize=True)
    for hops, branch in ((1, 8), (2, 8), (3, 4)):
        kw = dict(hops=hops, branch=branch, seed=2**31 + 1, n_dcs=5, n_hot_sources=24)
        want = generate_khop_patterns(g, csr, 40, **kw)
        got = gen.generate_khop_patterns(ga, 40, **kw)
        for p, q in zip(want, got):
            assert p.pid == q.pid and p.eta == q.eta
            assert np.array_equal(p.items, q.items)
            assert np.array_equal(p.r_py, q.r_py) and np.array_equal(p.w_py, q.w_py)


@pytest.mark.parametrize("seed", [6, 2**31 + 11])
def test_reference_components_match_program(seed):
    from repro.core.graph import Graph
    from repro.core.latency import make_paper_env
    from repro.core.layered_graph import build_layered_graph

    cfg = json.loads(CONFIG.read_text())
    reg = reference.regions(cfg)
    ga = community.community_graph(400, n_communities=6, p_in=0.03, p_out=0.001, seed=seed)
    g = Graph(ga.n_nodes, ga.src, ga.dst, ga.node_size, ga.edge_size, ga.partition)
    lg = build_layered_graph(g, make_paper_env(),
                             latency_interval_s=cfg["regions"]["layer_interval_s"])
    want = lg.comp_of_dc
    got = reference.components(ga.partition[ga.src], ga.partition[ga.dst], reg)
    assert got.shape == want.shape
    # the same partition of DCs at every layer, whatever the labels
    for a, b in zip(got, want):
        assert np.array_equal(a[:, None] == a[None, :], b[:, None] == b[None, :])


def test_request_stream_is_the_seeds():
    pool = [gen.PatternArrays(i, np.arange(3), np.eye(5)[i % 5], np.zeros(5), 1.0)
            for i in range(10)]
    a = gen.request_stream(pool, 500.0, 2.0, 0.65, 5, seed=2**31 + 99, opening_backlog=4)
    b = gen.request_stream(pool, 500.0, 2.0, 0.65, 5, seed=2**31 + 99, opening_backlog=4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert len(a.t) == 4 + 1000
    assert (a.t[:4] == 0).all() and (np.diff(a.t) >= 0).all() and a.t[-1] < 2.0
    home = a.origin == np.asarray([int(np.argmax(pool[p].r_py)) for p in a.pattern])
    assert 0.6 < home.mean() < 0.8  # 65% home plus the uniform draws that land home


def test_seeds_draw_the_same_work_in_another_order():
    pool = [gen.PatternArrays(i, np.arange(3), np.eye(5)[i % 5], np.zeros(5), 1.0)
            for i in range(10)]
    a = gen.request_stream(pool, 700.0, 3.0, 0.65, 5, seed=2**31 + 1, opening_backlog=8)
    b = gen.request_stream(pool, 700.0, 3.0, 0.65, 5, seed=2**31 + 2, opening_backlog=8)
    assert not np.array_equal(a.t, b.t) and not np.array_equal(a.pattern, b.pattern)
    gaps_a, gaps_b = (np.sort(np.diff(np.append(x.t[8:], 3.0))) for x in (a, b))
    assert np.allclose(gaps_a, gaps_b, rtol=0, atol=1e-12)
    assert np.array_equal(np.sort(np.bincount(a.pattern, minlength=10)),
                          np.sort(np.bincount(b.pattern, minlength=10)))
    assert np.bincount(a.pattern).max() - np.bincount(a.pattern).min() <= 1
    # the gaps are an exponential's: mean 1/rate, about as many above the mean as e^-1
    gaps = np.diff(a.t[8:])
    assert abs(gaps.mean() * 700.0 - 1.0) < 0.01
    assert abs((gaps > 1 / 700.0).mean() - np.exp(-1)) < 0.01


def test_exact_counts_keep_the_shares():
    got = np.bincount(gen.exact_counts(np.asarray([0.6, 0.3, 0.1]), 2048))
    assert got.sum() == 2048 and np.abs(got - np.asarray([0.6, 0.3, 0.1]) * 2048).max() < 1


def test_configured_generator_is_its_file():
    spec = json.loads(CONFIG.read_text())["graph"]
    kw = {k: spec[k] for k in ("n_communities", "p_in", "p_out", "geo_affinity")}
    small = dict(spec, n_nodes=300)
    _same_graph(gen.make_graph(small, 2**31 + 3, 5),
                community.community_graph(300, seed=2**31 + 3, n_dcs=5, **kw))


def test_unknown_generator_names_the_file_looked_for():
    with pytest.raises(FileNotFoundError, match=r"bench/graphs/no_such_graph\.py"):
        gen.make_graph({"generator": "no_such_graph"}, 1, 5)
    with pytest.raises(ValueError, match="not a graphs name"):
        gen.make_graph({"generator": "../configs/x"}, 1, 5)


UNIFORM = """
import numpy as np

from bench.gen import graph_arrays


def make(spec, seed, n_dcs):
    rng = np.random.default_rng(seed)
    n = spec["n_nodes"]
    key = np.unique(rng.integers(0, n * n, size=spec["n_edges"]))
    src, dst = key // n, key % n
    keep = src != dst
    return graph_arrays(n, src[keep], dst[keep], np.full(n, 256.0), np.full(keep.sum(), 64.0),
                        rng.integers(0, n_dcs, size=n))


def tiny(spec):
    return dict(spec, n_nodes=300, n_edges=2400)
"""


def test_generator_file_joins_without_edits(tmp_path, monkeypatch):
    """A new generator is one new file: placed in a graphs folder of its
    own, it builds, places and serves a tiny cell on the CPU."""
    (tmp_path / "uniform_graph.py").write_text(UNIFORM)
    monkeypatch.setitem(files.DIRS, "graphs", tmp_path)
    keep = {}
    _, res = run_tiny("snb.read", seed=2**31 + 29, keep=keep,
                      graph={"generator": "uniform_graph", "n_nodes": 10**6, "n_edges": 10**7})
    assert res["correct"], res["checks"]
    g = keep["st"].graph
    assert g.n_nodes == 300 and 2300 < len(g.src) <= 2400
