"""Planted-partition community graph: the stand-in for LDBC SNB's person-knows
graph.  A copy of ``repro.data.synthetic.community_graph`` as it stood when
the benchmark was written; ``bench/tests/test_bench_gen.py`` holds it to the
original bit for bit at a small seed."""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.gen import GraphArrays, graph_arrays


def make(spec: Dict, seed: int, n_dcs: int) -> GraphArrays:
    return community_graph(
        spec["n_nodes"], n_communities=spec["n_communities"], p_in=spec["p_in"],
        p_out=spec["p_out"], seed=seed, n_dcs=n_dcs, geo_affinity=spec["geo_affinity"],
    )


def tiny(spec: Dict) -> Dict:
    """A graph of 600 persons with the same communities, for CPU rehearsals."""
    return dict(spec, n_nodes=600, p_in=0.05, p_out=0.002)


def community_graph(n_nodes: int, n_communities: int = 8, p_in: float = 0.05,
                    p_out: float = 0.002, seed: int = 0, n_dcs: int = 5,
                    geo_affinity: float = 0.8) -> GraphArrays:
    """Planted-partition graph; each community leans towards one home DC."""
    rng = np.random.default_rng(seed)
    comm = rng.integers(0, n_communities, size=n_nodes)
    order = np.argsort(comm)
    comm = comm[order]
    src_l, dst_l = [], []
    for ci in range(n_communities):
        members = np.where(comm == ci)[0]
        k = len(members)
        if k < 2:
            continue
        m_in = rng.binomial(k * (k - 1) // 2, p_in)
        s = members[rng.integers(0, k, size=m_in)]
        d = members[rng.integers(0, k, size=m_in)]
        src_l.append(s)
        dst_l.append(d)
    m_out = rng.binomial(n_nodes * (n_nodes - 1) // 2, p_out)
    src_l.append(rng.integers(0, n_nodes, size=m_out))
    dst_l.append(rng.integers(0, n_nodes, size=m_out))
    src = np.concatenate(src_l)
    dst = np.concatenate(dst_l)
    mask = src != dst
    src, dst = src[mask], dst[mask]
    key = src.astype(np.int64) * n_nodes + dst
    _, idx = np.unique(key, return_index=True)
    src, dst = src[idx], dst[idx]
    home_dc = rng.integers(0, n_dcs, size=n_communities)
    partition = np.where(
        rng.random(n_nodes) < geo_affinity,
        home_dc[comm],
        rng.integers(0, n_dcs, size=n_nodes),
    )
    sizes = rng.lognormal(mean=np.log(256.0), sigma=0.5, size=n_nodes).astype(np.float32)
    esizes = rng.lognormal(mean=np.log(64.0), sigma=0.4, size=len(src)).astype(np.float32)
    return graph_arrays(n_nodes, src, dst, sizes, esizes, partition)
