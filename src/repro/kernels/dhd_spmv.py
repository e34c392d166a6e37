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
the same kernels at B = 1.

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

__all__ = ["dhd_ell_step", "dhd_ell_step_batch"]

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


def _count_kernel(h_u_ref, h_nb_ref, vals_ref, nout_ref):
    h_u = h_u_ref[0]  # [1, block_n]
    out_mask = (_tile(vals_ref) > 0) & (h_u > h_nb_ref[0])
    nout_ref[0] = out_mask.astype(jnp.float32).sum(axis=0, keepdims=True)


def _flow_kernel(alpha_ref, h_u_ref, nout_u_ref, h_nb_ref, nout_nb_ref, vals_ref, delta_ref):
    alpha = alpha_ref[0]
    h_u = h_u_ref[0]  # [1, block_n]
    nout_u = jnp.maximum(nout_u_ref[0], 1.0)
    h_nb = h_nb_ref[0]  # [kmax, block_n]
    nout_nb = jnp.maximum(nout_nb_ref[0], 1.0)
    vals = _tile(vals_ref)
    out_mask = (vals > 0) & (h_u > h_nb)
    in_mask = (vals > 0) & (h_nb > h_u)
    outflow = (alpha / nout_u * vals * jnp.where(out_mask, h_u - h_nb, 0.0)).sum(
        axis=0, keepdims=True
    )
    inflow = (alpha / nout_nb * vals * jnp.where(in_mask, h_nb - h_u, 0.0)).sum(
        axis=0, keepdims=True
    )
    delta_ref[0] = inflow - outflow


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
