"""Fused stepwise layered routing expansion — Pallas TPU kernel.

This is the serving hot-spot (paper §VI): per layer, coverage counts of
still-missing items over the replica map, masked argmax replica pick per
request (lowest-DC-id tie-break), assign hits, repeat until no cluster DC
covers anything, escalate — then fold served bytes into Eq. 1 latency,
straggler and WAN cost.  TPU adaptation: the replica map is **bit-packed**
(one int32 lane per item, bit d = "DC d holds a replica"), so a request
block is a dense ``[block_r, Kp]`` int32 tile in VMEM and per-DC coverage is
a shift-and-mask popcount over the item axis — no ``[R, K, D]`` f32 cube.

The expansion runs one early-exit ``while_loop`` over (layer, greedy pass)
per block: a pass that assigns items anywhere in the block stays in the
layer, a pass with zero progress escalates the shared layer pointer.  Extra
greedy passes are idempotent per request, so the block-lockstep walk equals
per-request greedy exactly (see ``ref.route_expand_ref``); the iteration
bound ``L * (D + 1)`` covers the worst case of D - 1 productive picks plus
one no-progress pass per layer.  Coverage counts are 0/1 sums, exact in f32
below 2^24 items.

Outputs per request block: served DC per item slot (int32, -1 unresolved),
per-DC served bytes, and a stats row (layers used, final missing count,
straggler seconds, WAN bytes, missing-after-each-layer) packed into one
128-lane f32 vector.

Grid: 1-D over request blocks — requests are independent, so any batch size
is eligible via row padding (pad requests have zero valid items; they
resolve to all-unserved with zero cost).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["route_expand", "STATS_LANES", "STAT_MISS_BASE"]

# stats row lane layout (f32): 0 = layers_used, 1 = final missing count,
# 2 = straggler seconds, 3 = WAN bytes, STAT_MISS_BASE + l = missing after
# layer l (l = 0 .. n_layers)
STATS_LANES = 128
STAT_MISS_BASE = 8
# cells of one [block_r, k_pad] 4-byte block (1 MiB): bits, sizes and served
# double-buffered come to 6 MiB
_TILE_CELLS = 1 << 18


def _expand_kernel(
    bits_ref,  # [block_r, Kp] i32 replica bitmask per item slot
    sizes_ref,  # [block_r, Kp] f32 bytes (0 where padded)
    lens_ref,  # [block_r, 1] i32 real item count
    origin_ref,  # [block_r, 1] i32
    allowed_ref,  # [block_r, L, Dp] f32 cluster mask per layer
    origin_oh_ref,  # [block_r, Dp] f32
    rtt_ref,  # [block_r, Dp] f32 RTT d -> origin
    ibw_ref,  # [block_r, Dp] f32 1 / bandwidth d -> origin
    served_ref,  # out [block_r, Kp] i32
    bytes_ref,  # out [block_r, STATS_LANES] f32 (lane d = bytes from DC d)
    stats_ref,  # out [block_r, STATS_LANES] f32
    *,
    n_layers: int,
    n_dc: int,
):
    bits = bits_ref[...]
    sizes = sizes_ref[...]
    lens = lens_ref[...]  # [block_r, 1]
    origin = origin_ref[...]  # [block_r, 1]
    origin_oh = origin_oh_ref[...]
    rtt = rtt_ref[...]
    ibw = ibw_ref[...]
    block_r, k_pad = bits.shape
    d_pad = allowed_ref.shape[2]
    f32 = sizes.dtype

    iota_k = jax.lax.broadcasted_iota(jnp.int32, (block_r, k_pad), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_r, STATS_LANES), 1)
    d_lane = jax.lax.broadcasted_iota(jnp.int32, (block_r, d_pad), 1)

    valid = iota_k < lens
    local = valid & (((bits >> origin) & 1) > 0)
    # loop-carried masks are int32 0/1: Mosaic cannot carry a bool vector
    # through the while_loop
    missing0 = (valid & jnp.logical_not(local)).astype(jnp.int32)
    # field-word coverage (see ref.route_expand_ref): for item tiles <= 512
    # wide, spread bit d of each item into a 10-bit field, 3 DCs per int32
    # word — one reduction per word yields 3 exact per-DC popcounts
    use_fields = k_pad <= 512
    if use_fields:
        words = []
        for w in range((n_dc + 2) // 3):
            acc = jnp.zeros_like(bits)
            for j, d in enumerate(range(w * 3, min(w * 3 + 3, n_dc))):
                acc = acc + (((bits >> d) & 1) << (10 * j))
            words.append(acc)

    def _coverage(missing):  # missing: bool [block_r, Kp]
        cover = jnp.zeros((block_r, d_pad), f32)
        if use_fields:
            for w, word in enumerate(words):
                s = jnp.where(missing, word, 0).sum(axis=1, keepdims=True)
                for j in range(min(3, n_dc - w * 3)):
                    cnt = ((s >> (10 * j)) & 1023).astype(f32)
                    cover = jnp.where(d_lane == w * 3 + j, cnt, cover)
            return cover
        masked = jnp.where(missing, bits, 0)
        for d in range(n_dc):
            cnt = ((masked >> d) & 1).astype(f32).sum(axis=1, keepdims=True)
            cover = jnp.where(d_lane == d, cnt, cover)
        return cover
    served0 = jnp.where(local, origin, jnp.int32(-1))
    miss_stats0 = jnp.where(
        lane == STAT_MISS_BASE,
        missing0.astype(f32).sum(axis=1, keepdims=True),
        jnp.zeros((block_r, STATS_LANES), f32),
    )
    max_iters = n_layers * (n_dc + 1)

    def cond(c):
        _, missing, layer, _, _, it = c
        return (layer < n_layers) & (missing.max() > 0) & (it < max_iters)

    def body(c):
        served, missing, layer, layers_used, miss_stats, it = c
        missing = missing > 0
        a_l = allowed_ref[:, layer, :]  # [block_r, Dp], read per layer
        layers_used = jnp.where(
            missing.any(axis=1, keepdims=True)
            & (a_l.max(axis=1, keepdims=True) > 0),
            (layer + 1).astype(f32),
            layers_used,
        )
        cover = jnp.where(a_l > 0, _coverage(missing), f32.type(0.0))
        gain = cover.max(axis=1, keepdims=True)
        # first index achieving the max == argmax == lowest-DC-id tie-break
        best = jnp.where(cover == gain, d_lane, d_pad).min(axis=1, keepdims=True)
        has = ((bits >> best) & 1) > 0
        hit = missing & (gain > 0) & has
        progressed = hit.any()
        new_missing = missing & jnp.logical_not(hit)
        miss_stats = jnp.where(
            progressed,
            miss_stats,
            jnp.where(
                lane == STAT_MISS_BASE + layer + 1,
                new_missing.astype(f32).sum(axis=1, keepdims=True),
                miss_stats,
            ),
        )
        return (
            jnp.where(hit, best, served),
            new_missing.astype(jnp.int32),
            jnp.where(progressed, layer, layer + 1),
            layers_used,
            miss_stats,
            it + 1,
        )

    served, missing, _, layers_used, miss_stats, _ = jax.lax.while_loop(
        cond,
        body,
        (
            served0,
            missing0,
            jnp.int32(0),
            jnp.zeros((block_r, 1), f32),
            miss_stats0,
            jnp.int32(0),
        ),
    )
    served_ref[...] = served

    # Eq. 1 fold: per-DC served bytes, straggler latency, WAN bytes.  D is a
    # handful, so static per-DC column folds beat a one-hot matmul here.
    sz = jnp.where(valid, sizes, f32.type(0.0))
    bytes_out = jnp.zeros((block_r, STATS_LANES), f32)
    straggler = jnp.zeros((block_r, 1), f32)
    wan = jnp.zeros((block_r, 1), f32)
    for d in range(n_dc):
        b_d = jnp.where(served == d, sz, f32.type(0.0)).sum(axis=1, keepdims=True)
        bytes_out = jnp.where(lane == d, b_d, bytes_out)
        at_origin = origin == d  # [block_r, 1]
        lat_d = jnp.where(
            at_origin,
            f32.type(0.0),
            rtt[:, d : d + 1] + b_d * ibw[:, d : d + 1],
        )
        served_d = (served == d).astype(f32).sum(axis=1, keepdims=True) > 0
        straggler = jnp.maximum(straggler, jnp.where(served_d, lat_d, 0.0))
        wan = wan + b_d * (1.0 - origin_oh[:, d : d + 1])
    bytes_ref[...] = bytes_out

    stats = miss_stats
    stats = jnp.where(lane == 0, layers_used, stats)
    final_missing = missing.astype(f32).sum(axis=1, keepdims=True)
    stats = jnp.where(lane == 1, final_missing, stats)
    stats = jnp.where(lane == 2, straggler, stats)
    stats = jnp.where(lane == 3, wan, stats)
    stats_ref[...] = stats


def _pad_axis(x: jnp.ndarray, axis: int, to: int) -> jnp.ndarray:
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def route_expand(
    bits: jnp.ndarray,  # [R, K] i32 per-item replica bitmask (bit d = DC d)
    sizes: jnp.ndarray,  # [R, K] f32 item bytes (0 where padded)
    lens: jnp.ndarray,  # [R] i32 real item count per request
    origin: jnp.ndarray,  # [R] i32 origin DC per request
    comp: jnp.ndarray,  # [hier + 1, D] i32 layer component ids
    rtt: jnp.ndarray,  # [D, D] f32 env RTT matrix
    ibw: jnp.ndarray,  # [D, D] f32 elementwise 1 / bandwidth matrix
    *,
    block_r: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Pallas route-expansion; same contract as ``ref.route_expand_ref``.

    Derives the per-request cluster masks and origin-relative cost columns
    on-device (tiny [L, D] / [D, D] gathers), pads requests to ``block_r``
    multiples, DCs to a sublane multiple of 8 and item slots to a lane
    multiple of 128, runs the fused kernel over a request-block grid, and
    slices back.  The stats row requires ``STAT_MISS_BASE + n_layers + 1 <=
    STATS_LANES`` (plenty for the paper's latency hierarchies) and ``n_dc <=
    STATS_LANES``.
    """
    R, K = bits.shape
    L = comp.shape[0] - 1
    D = comp.shape[1]
    assert STAT_MISS_BASE + L + 1 <= STATS_LANES
    assert D <= STATS_LANES
    k_pad = -(-max(K, 1) // 128) * 128
    # wide item tiles get fewer request rows, so the double-buffered
    # [block_r, k_pad] blocks stay inside the default scoped VMEM
    block_r = min(block_r, max(8, _TILE_CELLS // k_pad // 8 * 8))
    block_r = max(8, min(block_r, -(-R // 8) * 8))
    r_pad = -(-R // block_r) * block_r
    d_pad = -(-max(D, 1) // 8) * 8

    origin = origin.astype(jnp.int32)
    comp_l = comp[1:].astype(jnp.int32)  # [L, D]
    comp_o = jnp.transpose(comp_l[:, origin])  # [R, L]
    allowed = (comp_l[None, :, :] == comp_o[:, :, None]) & (
        jnp.arange(D, dtype=jnp.int32)[None, None, :] != origin[:, None, None]
    )
    oh = (
        jnp.arange(D, dtype=jnp.int32)[None, :] == origin[:, None]
    ).astype(jnp.float32)
    rtt_ro = jnp.transpose(rtt[:, origin]).astype(jnp.float32)
    ibw_ro = jnp.transpose(ibw[:, origin]).astype(jnp.float32)

    bits_p = _pad_axis(_pad_axis(bits.astype(jnp.int32), 1, k_pad), 0, r_pad)
    sizes_p = _pad_axis(_pad_axis(sizes.astype(jnp.float32), 1, k_pad), 0, r_pad)
    lens_p = _pad_axis(lens.astype(jnp.int32)[:, None], 0, r_pad)
    origin_p = _pad_axis(origin[:, None], 0, r_pad)
    allowed_p = _pad_axis(
        _pad_axis(allowed.astype(jnp.float32), 2, d_pad), 0, r_pad
    )
    oh_p = _pad_axis(_pad_axis(oh, 1, d_pad), 0, r_pad)
    rtt_p = _pad_axis(_pad_axis(rtt_ro, 1, d_pad), 0, r_pad)
    ibw_p = _pad_axis(_pad_axis(ibw_ro, 1, d_pad), 0, r_pad)

    grid = (r_pad // block_r,)
    served_p, bytes_p, stats_p = pl.pallas_call(
        functools.partial(_expand_kernel, n_layers=L, n_dc=D),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, L, d_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_r, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, d_pad), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, STATS_LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_r, STATS_LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((r_pad, STATS_LANES), jnp.float32),
            jax.ShapeDtypeStruct((r_pad, STATS_LANES), jnp.float32),
        ],
        interpret=interpret,
        name="route_expand",
    )(bits_p, sizes_p, lens_p, origin_p, allowed_p, oh_p, rtt_p, ibw_p)

    served = served_p[:R, :K]
    bytes_rd = bytes_p[:R, :D]
    layers_used = stats_p[:R, 0].astype(jnp.int32)
    miss_after = stats_p[:R, STAT_MISS_BASE : STAT_MISS_BASE + L + 1].astype(
        jnp.int32
    )
    straggler = stats_p[:R, 2]
    wan = stats_p[:R, 3]
    return served, bytes_rd, layers_used, miss_after, straggler, wan
