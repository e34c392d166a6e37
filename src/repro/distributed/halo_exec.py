"""Halo-exchange message passing under shard_map — the *measured*
realization of the GeoLayer placement win for distributed GNNs
(EXPERIMENTS §Perf iteration 7/8).

Baseline distributed message passing all-gathers the full feature matrix
every layer: wire = (P-1)/P * N * d * bytes per layer.  The halo executor
instead exchanges only the rows other shards actually need, with *static*
send lists planned from the graph cut (and prioritized by GeoLayer heat —
``plan_gnn_halo`` picks which remote rows are worth keeping resident):

    per layer:  send_rows = feats[send_idx]        # [P, S_max, d]
                recv_rows = all_to_all(send_rows)  # the halo exchange
                ext = concat([feats_local, recv_rows.reshape(-1, d)])
                msgs -> segment_sum over local edges

wire = P * S_max * d * bytes per layer, with S_max = max rows any shard
exports ≈ boundary size.  The wire ratio vs baseline is measured by
:func:`exchange_stats` (exact byte accounting, no model).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.graph import Graph

__all__ = ["HaloProgram", "build_halo_program", "run_message_passing", "exchange_stats"]


@dataclasses.dataclass
class HaloProgram:
    """Static plan for shard_map halo message passing over a partition.

    All arrays have a leading shard axis [P, ...] (padded, masked):
      send_idx  [P, P, s_max]  rows of shard p to ship to shard q (local ids)
      send_mask [P, P, s_max]
      edge_src  [P, e_max]     index into [local n_max ++ recv (P*s_max)]
      edge_dst  [P, e_max]     local destination index
      edge_mask [P, e_max]
      feats     [P, n_max, d]  built by ``scatter_features``
    """

    n_shards: int
    n_max: int
    s_max: int
    e_max: int
    send_idx: np.ndarray
    send_mask: np.ndarray
    edge_src: np.ndarray
    edge_dst: np.ndarray
    edge_mask: np.ndarray
    local_ids: List[np.ndarray]  # global vertex ids per shard (unpadded)

    def scatter_features(self, feats_global: np.ndarray) -> np.ndarray:
        d = feats_global.shape[1]
        out = np.zeros((self.n_shards, self.n_max, d), feats_global.dtype)
        for p, ids in enumerate(self.local_ids):
            out[p, : len(ids)] = feats_global[ids]
        return out

    def gather_outputs(self, out_sharded: np.ndarray, n_global: int) -> np.ndarray:
        d = out_sharded.shape[-1]
        out = np.zeros((n_global, d), out_sharded.dtype)
        for p, ids in enumerate(self.local_ids):
            out[ids] = out_sharded[p, : len(ids)]
        return out


def build_halo_program(g: Graph, n_shards: int) -> HaloProgram:
    """Plan send lists + local edge index from a partitioned graph.

    Edges are owned by their dst's shard; src rows on other shards enter the
    shard's receive buffer at a deterministic slot (q * s_max + position in
    q's send list to us)."""
    part = g.partition
    local_ids = [np.where(part == p)[0] for p in range(n_shards)]
    g2l = {}
    for p, ids in enumerate(local_ids):
        for i, v in enumerate(ids.tolist()):
            g2l[v] = (p, i)
    n_max = max(len(i) for i in local_ids)

    # who needs what: shard q needs src rows owned by p for q's edges
    need: Dict[Tuple[int, int], List[int]] = {}
    for s, t in zip(g.src.tolist(), g.dst.tolist()):
        ps, _ = g2l[s]
        pq, _ = g2l[t]
        if ps != pq:
            need.setdefault((ps, pq), [])
            if s not in need[(ps, pq)]:
                need[(ps, pq)].append(s)
    s_max = max((len(v) for v in need.values()), default=1)

    send_idx = np.zeros((n_shards, n_shards, s_max), np.int32)
    send_mask = np.zeros((n_shards, n_shards, s_max), bool)
    recv_slot: Dict[Tuple[int, int], int] = {}  # (dst shard, global id) -> slot
    for (ps, pq), verts in need.items():
        for j, v in enumerate(verts):
            send_idx[ps, pq, j] = g2l[v][1]
            send_mask[ps, pq, j] = True
            # receive buffer on q is [P, s_max] flattened: sender-major
            recv_slot[(pq, v)] = ps * s_max + j

    counts = np.bincount([g2l[t][0] for t in g.dst.tolist()], minlength=n_shards)
    e_max = int(counts.max()) if len(counts) else 1
    edge_src = np.zeros((n_shards, e_max), np.int32)
    edge_dst = np.zeros((n_shards, e_max), np.int32)
    edge_mask = np.zeros((n_shards, e_max), bool)
    fill = np.zeros(n_shards, np.int64)
    for s, t in zip(g.src.tolist(), g.dst.tolist()):
        pq, lt = g2l[t]
        ps, ls = g2l[s]
        j = fill[pq]
        edge_dst[pq, j] = lt
        if ps == pq:
            edge_src[pq, j] = ls
        else:  # halo row: offset past the local block
            edge_src[pq, j] = n_max + recv_slot[(pq, s)]
        edge_mask[pq, j] = True
        fill[pq] += 1
    return HaloProgram(
        n_shards=n_shards, n_max=n_max, s_max=s_max, e_max=e_max,
        send_idx=send_idx, send_mask=send_mask,
        edge_src=edge_src, edge_dst=edge_dst, edge_mask=edge_mask,
        local_ids=local_ids,
    )


def run_message_passing(
    prog: HaloProgram,
    mesh: Mesh,
    feats: jnp.ndarray,  # [P, n_max, d] (scatter_features layout)
    weights: jnp.ndarray,  # [d, d] shared message transform (demo layer)
    n_layers: int = 2,
    mode: str = "halo",  # halo | allgather
) -> jnp.ndarray:
    """n_layers of mean-aggregated message passing, halo vs all-gather.

    Both modes compute identical results (tested); they differ only in the
    exchange primitive, i.e. the collective wire bytes."""
    axis = mesh.axis_names[0]
    p_ = prog

    def layer(x, send_idx, send_mask, e_src, e_dst, e_mask):
        # x: [n_max, d] local block (inside shard_map)
        if mode == "halo":
            send = jnp.where(send_mask[..., None], x[send_idx], 0.0)  # [P,s,d]
            recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
            recv = recv.reshape(p_.n_shards * p_.s_max, x.shape[-1])
        else:
            allf = jax.lax.all_gather(x, axis)  # [P, n_max, d]
            # emulate the recv layout from the gathered matrix
            idx_all = jax.lax.all_gather(send_idx, axis)  # [P(src), P(dst), s]
            me = jax.lax.axis_index(axis)
            rows = idx_all[:, me]  # [P, s] rows each sender ships to me
            recv = allf[jnp.arange(p_.n_shards)[:, None], rows].reshape(
                p_.n_shards * p_.s_max, x.shape[-1]
            )
        ext = jnp.concatenate([x, recv], axis=0)
        msg = ext[e_src] @ weights
        msg = jnp.where(e_mask[:, None], msg, 0.0)
        agg = jax.ops.segment_sum(msg, e_dst, num_segments=p_.n_max)
        deg = jax.ops.segment_sum(
            e_mask.astype(x.dtype), e_dst, num_segments=p_.n_max
        )
        return x + jnp.tanh(agg / jnp.maximum(deg, 1.0)[:, None])

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    def run(x, send_idx, send_mask, e_src, e_dst, e_mask):
        x, send_idx = x[0], send_idx[0]
        send_mask, e_src = send_mask[0], e_src[0]
        e_dst, e_mask = e_dst[0], e_mask[0]
        for _ in range(n_layers):
            x = layer(x, send_idx, send_mask, e_src, e_dst, e_mask)
        return x[None]

    return run(
        feats,
        jnp.asarray(prog.send_idx),
        jnp.asarray(prog.send_mask),
        jnp.asarray(prog.edge_src),
        jnp.asarray(prog.edge_dst),
        jnp.asarray(prog.edge_mask),
    )


def exchange_stats(prog: HaloProgram, d: int, n_layers: int, bytes_per: int = 4):
    """Exact wire bytes per device per step for both modes."""
    halo = n_layers * prog.n_shards * prog.s_max * d * bytes_per
    allgather = (
        n_layers * (prog.n_shards - 1) * prog.n_max * d * bytes_per
    )
    return {
        "halo_bytes_per_device": halo,
        "allgather_bytes_per_device": allgather,
        "reduction": allgather / max(halo, 1),
    }
