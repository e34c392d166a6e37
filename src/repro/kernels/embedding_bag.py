"""EmbeddingBag (gather + bag-reduce) — Pallas TPU kernel.

The recsys hot path (BST's item/category history lookup).  TPU adaptation:
instead of per-index HBM gathers (GPU style), the **vocab axis is tiled
through VMEM**: grid = (bag_blocks, vocab_blocks); each step loads a
(block_v x dim) table tile, resolves the in-range indices against it with a
VMEM take + mask, and accumulates into a VMEM scratch — dense, predictable
DMA traffic, no data-dependent HBM addressing.  For Zipf-distributed indices
the hot vocab tiles hit nearly every bag block (good reuse); GeoLayer's
row-replication (DESIGN §4.3) exploits exactly that skew at mesh scale.

``mode='mean'`` normalizes by bag weight inside the finalize step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["embedding_bag"]


def _bag_kernel(
    idx_ref,  # [block_b, L]
    w_ref,  # [block_b, L]
    tab_ref,  # [block_v, D]
    o_ref,  # [block_b, D]
    acc_scr,  # [block_b, D] f32
    wsum_scr,  # [block_b, 1] f32
    *,
    block_v: int,
    mode: str,
):
    iv = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        wsum_scr[...] = jnp.zeros_like(wsum_scr)

    idx = idx_ref[...]  # [bb, L] global vocab ids
    w = w_ref[...].astype(jnp.float32)
    tab = tab_ref[...].astype(jnp.float32)  # [bv, D]
    lo = iv * block_v
    local = idx - lo
    in_range = (local >= 0) & (local < block_v)
    local_c = jnp.clip(local, 0, block_v - 1)
    rows = jnp.take(tab, local_c, axis=0)  # [bb, L, D] VMEM gather
    wm = jnp.where(in_range, w, 0.0)
    acc_scr[...] += jnp.einsum("bl,bld->bd", wm, rows)
    wsum_scr[...] += wm.sum(axis=1, keepdims=True)

    @pl.when(iv == n_v - 1)
    def _finalize():
        out = acc_scr[...]
        if mode == "mean":
            out = out / jnp.maximum(wsum_scr[...], 1e-9)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("mode", "block_b", "block_v", "interpret")
)
def embedding_bag(
    table: jnp.ndarray,  # [V, D]
    indices: jnp.ndarray,  # [B, L] int32
    weights: Optional[jnp.ndarray] = None,  # [B, L]
    mode: str = "sum",
    block_b: int = 128,
    block_v: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    v, d = table.shape
    b, l = indices.shape
    block_b = min(block_b, b)
    block_v = min(block_v, v)
    assert b % block_b == 0 and v % block_v == 0
    if weights is None:
        weights = jnp.ones((b, l), dtype=table.dtype)
    grid = (b // block_b, v // block_v)
    kernel = functools.partial(_bag_kernel, block_v=block_v, mode=mode)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, l), lambda ib, iv: (ib, 0)),
            pl.BlockSpec((block_b, l), lambda ib, iv: (ib, 0)),
            pl.BlockSpec((block_v, d), lambda ib, iv: (iv, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, d), lambda ib, iv: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_b, d), jnp.float32),
            pltpu.VMEM((block_b, 1), jnp.float32),
        ],
        interpret=interpret,
    )(indices, weights, table)
