"""Directed-Heat-Diffusion step over ELL adjacency — Pallas TPU kernel.

This is the paper's compute hot-spot (Eqs. 7-8 iterated to steady state for
placement scoring, pre-caching and eviction).  TPU adaptation (DESIGN §2):
a GPU implementation would scatter per edge; here the adjacency is packed as
**symmetric ELL** (each row = padded neighbor list) so every row's update is a
dense VPU reduction, tiled ``block_n`` rows at a time in VMEM.

Two passes (both O(n * kmax)):
  1. ``_count_kernel`` — |N_u^out| = # strictly-lower-heat neighbors per row.
  2. ``_flow_kernel``  — inflow - outflow per row given the global n_out.

The neighbour gathers (``heat[cols]``, ``n_out[cols]``) run as XLA gathers
in the jitted wrappers; the kernels see only transposed ``[kmax, block_n]``
neighbour tiles and lane-dense ``[1, block_n]`` tiles of each row's own
heat / |N^out|, so they are dense masked reductions over the neighbour
axis and VMEM holds a few tiles whatever ``n`` is (``_row_block`` narrows
the tile for wide rows).  Overflow edges
beyond kmax live in a COO tail handled by ``ops.dhd_step`` with segment ops.

Arbitrary row counts are handled by padding inside the wrappers: pad rows
are isolated zero-weight self-loops (no flow in or out, |N^out| = 0), so the
padded result sliced back to ``n`` rows is exact and any cluster size takes
the kernel path.

``dhd_ell_step_batch`` runs B independent heat fields over one shared column
structure with a 2-D grid (batch × row-blocks); ``vals`` may be per-batch
(``[B, n, kmax]``), which is how the placement arena diffuses every
candidate's super-node topology in a single launch.  ``dhd_ell_step`` is
the same kernels at B = 1.  Placement's diffusion (``ops.diffuse_batch``),
``ops.dhd_step``/``dhd_step_batch`` and the streaming frontier sub-solve
keep this dense layout.

``dhd_sliced_step`` runs the same per-row math over a *sliced* ELL: each
block of ``SLICE_ROWS`` rows keeps only as many slots as its widest row
needs, the blocks' ``[w_b, SLICE_ROWS]`` slabs laid end to end, so both
gathers run over the slabs' slots alone.  The streaming warm solve's
global sweeps use it over rows sorted by degree (``streaming.delta_dhd``'s
solver order), where the sort makes neighbouring rows alike in width.

The coefficients ``alpha``, ``gamma`` and ``beta`` are float32 operands, not
compile-time constants: the flow kernel reads ``alpha`` from SMEM, so a
caller whose contraction clamp moves with the graph (the streaming warm
solve) reuses one program per shape.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dhd_ell_step", "dhd_ell_step_batch", "dhd_sliced_step"]

# cells (8-padded neighbour slots x row lanes) of one f32 tile: 256 KiB, so
# the flow pass's three tiles double-buffered plus its temporaries stay a
# few MiB, far inside the default scoped VMEM
_TILE_CELLS = 1 << 16


def _pad_rows(
    heat: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray, block_n: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Pad to a row-count multiple of ``block_n`` with isolated self-loops.

    ``heat`` may be [n] or [B, n]; ``vals`` [n, kmax] or [B, n, kmax].
    Pad rows get heat 0 and zero-weight self-edges, so they never exchange
    heat with real rows and the sliced result is exact."""
    n = heat.shape[-1]
    kmax = cols.shape[1]
    n_pad = -(-n // block_n) * block_n
    if n_pad == n:
        return heat, cols, vals, n
    pad = n_pad - n
    pad_cols = jnp.broadcast_to(
        jnp.arange(n, n_pad, dtype=cols.dtype)[:, None], (pad, kmax)
    )
    cols = jnp.concatenate([cols, pad_cols], axis=0)
    if vals.ndim == 3:
        vals = jnp.concatenate(
            [vals, jnp.zeros((vals.shape[0], pad, kmax), vals.dtype)], axis=1
        )
    else:
        vals = jnp.concatenate([vals, jnp.zeros((pad, kmax), vals.dtype)], axis=0)
    zpad = jnp.zeros((*heat.shape[:-1], pad), heat.dtype)
    heat = jnp.concatenate([heat, zpad], axis=-1)
    return heat, cols, vals, n


def _row_block(n: int, kmax: int, block_n: int) -> int:
    """Rows per tile: at most ``block_n``, a lane multiple of 128 unless one
    block spans all ``n`` rows, and capped so one ``[kmax, rows]`` f32 tile
    stays within ``_TILE_CELLS`` — wide super-node rows (the placement
    arena's) get narrower tiles instead of overflowing VMEM."""
    slots = -(-kmax // 8) * 8
    bn = max(128, min(block_n, _TILE_CELLS // slots) // 128 * 128)
    return n if bn >= n else bn


def _tile(ref):
    """This program's ``[kmax, block_n]`` tile of a 2-D or batched 3-D ref."""
    return ref[0] if len(ref.shape) == 3 else ref[...]


def _out_count(h_u, h_nb, vals):
    """Per-row count of strictly-lower-heat neighbours in a ``[slots, rows]``
    tile: a ``[1, rows]`` partial |N^out|."""
    return ((vals > 0) & (h_u > h_nb)).astype(jnp.float32).sum(axis=0, keepdims=True)


def _net_inflow(alpha, h_u, nout_u, h_nb, nout_nb, vals):
    """Per-row inflow less outflow over a ``[slots, rows]`` tile: a
    ``[1, rows]`` partial delta (linear in the slots, so partial tiles add)."""
    nout_u = jnp.maximum(nout_u, 1.0)
    nout_nb = jnp.maximum(nout_nb, 1.0)
    out_mask = (vals > 0) & (h_u > h_nb)
    in_mask = (vals > 0) & (h_nb > h_u)
    outflow = (alpha / nout_u * vals * jnp.where(out_mask, h_u - h_nb, 0.0)).sum(
        axis=0, keepdims=True
    )
    inflow = (alpha / nout_nb * vals * jnp.where(in_mask, h_nb - h_u, 0.0)).sum(
        axis=0, keepdims=True
    )
    return inflow - outflow


def _count_kernel(h_u_ref, h_nb_ref, vals_ref, nout_ref):
    nout_ref[0] = _out_count(h_u_ref[0], h_nb_ref[0], _tile(vals_ref))


def _flow_kernel(alpha_ref, h_u_ref, nout_u_ref, h_nb_ref, nout_nb_ref, vals_ref, delta_ref):
    delta_ref[0] = _net_inflow(alpha_ref[0], h_u_ref[0], nout_u_ref[0], h_nb_ref[0],
                               nout_nb_ref[0], _tile(vals_ref))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def dhd_ell_step_batch(
    heat: jnp.ndarray,  # [B, n] float32
    cols: jnp.ndarray,  # [n, kmax] int32 shared symmetric ELL (pad = self)
    vals: jnp.ndarray,  # [n, kmax] shared or [B, n, kmax] per-batch weights
    q: jnp.ndarray,  # [B, n] source heat
    alpha=0.5,  # float32 operands: a new value reuses the compiled program
    gamma=0.1,
    beta=0.3,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched DHD update: B heat fields, one shared column structure.

    2-D grid over (batch, row-blocks).  Neighbour heat and neighbour
    |N^out| are XLA gathers in this wrapper, handed to the kernels
    transposed as ``[1, kmax, block_n]`` tiles next to ``[1, 1, block_n]``
    tiles of the rows' own values.  With 3-D ``vals`` each batch element
    diffuses over its own edge weights (zero = edge absent for that
    element), matching ``ref.dhd_ell_ref_batch`` row-for-row.
    """
    b, n = heat.shape
    kmax = cols.shape[1]
    block_n = _row_block(n, kmax, block_n)
    heat_p, cols, vals, _ = _pad_rows(heat, cols, vals, block_n)
    n_pad = cols.shape[0]
    grid = (b, n_pad // block_n)
    # rows on lanes, neighbour slots on sublanes: per-row values are
    # lane-dense [B, 1, n_pad] rows and every reduction runs over sublanes
    cols_t = cols.T  # [kmax, n_pad]
    vals_t = jnp.swapaxes(vals, -1, -2).astype(jnp.float32)
    # flat element gathers: indexing [B, n_pad] by column would gather
    # [B, 1] slices, which the TPU lays out with B on 128-padded lanes
    flat = jnp.arange(b, dtype=jnp.int32)[:, None, None] * n_pad + cols_t[None]
    h = heat_p.astype(jnp.float32)
    h_u = h[:, None, :]  # [B, 1, n_pad]
    h_nb = h.reshape(-1)[flat]  # [B, kmax, n_pad]
    row_spec = pl.BlockSpec((1, 1, block_n), lambda bb, i: (bb, 0, i))
    tile_spec = pl.BlockSpec((1, kmax, block_n), lambda bb, i: (bb, 0, i))
    if vals.ndim == 3:
        vals_spec = tile_spec
    else:
        vals_spec = pl.BlockSpec((kmax, block_n), lambda bb, i: (0, i))
    row_shape = jax.ShapeDtypeStruct((b, 1, n_pad), jnp.float32)
    alpha = jnp.reshape(jnp.asarray(alpha, jnp.float32), (1,))
    gamma = jnp.asarray(gamma, jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)

    n_out = pl.pallas_call(
        _count_kernel,
        grid=grid,
        in_specs=[row_spec, tile_spec, vals_spec],
        out_specs=row_spec,
        out_shape=row_shape,
        interpret=interpret,
        name="dhd_ell_count",
    )(h_u, h_nb, vals_t)

    delta = pl.pallas_call(
        _flow_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_spec, row_spec, tile_spec,
                  tile_spec, vals_spec],
        out_specs=row_spec,
        out_shape=row_shape,
        interpret=interpret,
        name="dhd_ell_flow",
    )(alpha, h_u, n_out, h_nb, n_out.reshape(-1)[flat], vals_t)

    return (1.0 - gamma) * (heat + delta[:, 0, :n]) + beta * q


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def dhd_ell_step(
    heat: jnp.ndarray,  # [n] float32
    cols: jnp.ndarray,  # [n, kmax] int32 symmetric ELL (pad = self)
    vals: jnp.ndarray,  # [n, kmax] float32 (0 where padded)
    q: jnp.ndarray,  # [n] source heat
    alpha=0.5,  # float32 operands, as in dhd_ell_step_batch
    gamma=0.1,
    beta=0.3,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """One DHD update; ELL part only (COO tail composed in ``ops.dhd_step``).
    The batched kernels at B = 1."""
    return dhd_ell_step_batch(
        heat[None], cols, vals, q[None], alpha=alpha, gamma=gamma, beta=beta,
        block_n=block_n, interpret=interpret,
    )[0]


# ------------------------------------------------------------- sliced ELL
# Rows per block and neighbour slots per chunk of the sliced layout.
SLICE_ROWS = 256
SLICE_CHUNK = 8


def _first_chunk(blk_ref):
    """Whether this grid step opens its block's run of chunks."""
    c = pl.program_id(0)
    return (c == 0) | (blk_ref[c] != blk_ref[jnp.maximum(c - 1, 0)])


def _count_sliced_kernel(blk_ref, h_u_ref, h_nb_ref, vals_ref, nout_ref):
    @pl.when(_first_chunk(blk_ref))
    def _():
        nout_ref[...] = jnp.zeros_like(nout_ref)

    nout_ref[...] += _out_count(h_u_ref[...], h_nb_ref[...], vals_ref[...])


def _flow_sliced_kernel(blk_ref, alpha_ref, h_u_ref, nout_u_ref, h_nb_ref, nout_nb_ref,
                        vals_ref, delta_ref):
    @pl.when(_first_chunk(blk_ref))
    def _():
        delta_ref[...] = jnp.zeros_like(delta_ref)

    delta_ref[...] += _net_inflow(alpha_ref[0], h_u_ref[...], nout_u_ref[...], h_nb_ref[...],
                                  nout_nb_ref[...], vals_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def dhd_sliced_step(
    heat: jnp.ndarray,  # [n] float32, n a multiple of SLICE_ROWS
    cols: jnp.ndarray,  # [S, SLICE_ROWS] int32 sliced ELL (pad = self, or 0 past the slabs)
    vals: jnp.ndarray,  # [S, SLICE_ROWS] float32 (0 where padded)
    blk: jnp.ndarray,  # [S // SLICE_CHUNK] int32 row block of each chunk, ascending
    q: jnp.ndarray,  # [n] source heat
    alpha=0.5,  # float32 operands, as in dhd_ell_step_batch
    gamma=0.1,
    beta=0.3,
    interpret: bool = False,
) -> jnp.ndarray:
    """One DHD update over a sliced ELL: each block of ``SLICE_ROWS`` rows
    keeps only as many neighbour slots as its widest row needs.

    Block ``b`` is a ``[w_b, SLICE_ROWS]`` slab of ``cols``/``vals`` (slots
    on sublanes, rows on lanes, as the dense kernels' tiles), the slabs laid
    end to end in block order; ``blk`` names the block of each
    ``SLICE_CHUNK``-slot chunk.  Chunks past the last slab belong to the
    last block with zero weights, so they add nothing.  The two gathers
    (``heat[cols]``, ``n_out[cols]``) run over the slabs' slots alone; the
    kernels walk the chunks on a 1-D grid with the block ids prefetched to
    SMEM, and each block's partial counts and flows add up in its resident
    ``[1, SLICE_ROWS]`` output tile.  Tile shapes are fixed, so one program
    serves every layout of the same ``[n]`` and ``[S, SLICE_ROWS]`` shapes.
    Matches ``dhd_ell_step`` over the same rows up to float summation order.
    """
    n = heat.shape[0]
    chunks = blk.shape[0]
    h = heat.astype(jnp.float32)
    h_row = h[None, :]  # [1, n]
    h_nb = h[cols]  # [S, SLICE_ROWS]
    row_spec = pl.BlockSpec((1, SLICE_ROWS), lambda c, b: (0, b[c]))
    tile_spec = pl.BlockSpec((SLICE_CHUNK, SLICE_ROWS), lambda c, b: (c, 0))
    row_shape = jax.ShapeDtypeStruct((1, n), jnp.float32)
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

    n_out = pl.pallas_call(
        _count_sliced_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(chunks,),
            in_specs=[row_spec, tile_spec, tile_spec], out_specs=row_spec),
        out_shape=row_shape,
        compiler_params=params,
        interpret=interpret,
        name="dhd_sliced_count",
    )(blk, h_row, h_nb, vals)

    alpha = jnp.reshape(jnp.asarray(alpha, jnp.float32), (1,))
    delta = pl.pallas_call(
        _flow_sliced_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(chunks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_spec, row_spec, tile_spec,
                      tile_spec, tile_spec],
            out_specs=row_spec),
        out_shape=row_shape,
        compiler_params=params,
        interpret=interpret,
        name="dhd_sliced_flow",
    )(blk, alpha, h_row, n_out, h_nb, n_out[0][cols], vals)

    gamma = jnp.asarray(gamma, jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    return (1.0 - gamma) * (heat + delta[0]) + beta * q
