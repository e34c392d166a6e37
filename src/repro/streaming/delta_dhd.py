"""Warm-started DHD steady state for streaming updates (tentpole, part 3).

The store keeps the previous equilibrium heat field; a mutation batch only
perturbs the field near the touched vertices, so the new equilibrium is
reached in far fewer sweeps than a cold solve:

  1. *frontier pre-solve* — extract the touched frontier plus a one-ring halo,
     clamp the halo to its current (globally-correct) heat, and relax the
     frontier on the small sub-ELL;
  2. *global sweeps* — run full-graph DHD steps from the pre-solved field
     until the residual drops below tolerance.

Rows are kept in a *solver order*: vertices by degree, descending (a
stable sort made at each cold build), then the field's pad rows, which new
vertices take.  The ELL adjacency lives in that order twice: dense
(``[n_pad, kmax]``, on the host as the source of truth and mirrored on the
device, patched row-wise per batch) and, for the global sweeps, *sliced*
(``kernels.ops.dhd_sliced``): each 256-row block keeps only as many slots
as its widest row needs, so the sweeps gather only about 60% of the dense
slots on a degree-skewed graph.  The sliced slabs are cut from the device's
dense mirror once per solve.  The frontier sub-solve runs the dense kernels
over its own small sub-ELL.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dhd import DHDParams, steady_state
from ..kernels import ops
from ..kernels.dhd_spmv import SLICE_CHUNK, SLICE_ROWS
from ..obs import Tracer, get_registry

__all__ = ["StreamingHeat", "WarmStats", "STREAMING_DHD_PARAMS"]

# Constant-source fixed-point iteration needs the Theorem-1 contraction
# regime; the paper's alpha=0.5 placement default is tuned for the *decaying*
# source runs and overshoots ||L_dir||_inf here.  alpha below is only an
# upper cap — ``StreamingHeat._effective_alpha`` clamps it per graph so the
# update map is a contraction with a unique equilibrium.
STREAMING_DHD_PARAMS = DHDParams(alpha=0.05, gamma=0.1, beta=0.3)


@dataclasses.dataclass
class WarmStats:
    frontier_size: int
    halo_size: int
    local_iters: int
    global_iters: int
    residual: float  # sup-norm step size at exit: carried-over staleness


# Shapes are bucketed so that a stream of batches runs a handful of compiled
# programs, each compiled once (``StreamingHeat.prepare`` compiles them ahead).
# The field's rows go to 16 steps an octave (at most 1/16 of padding, since
# every sweep gathers over every slot), rounded to the kernel's 256-row
# block; the dense ELL width to a multiple of 8 slots; the sliced ELL's slot
# rows (the sum of its blocks' widths) to 16 steps an octave, in 8-slot
# chunks; the frontier sub-solve's rows and the scattered row sets to a
# power of two.  Pad rows are isolated self-loops with zero weight and zero
# source, so they hold heat 0 forever; pad entries of a row scatter re-write
# the last real row with its own contents.
_ROW_MIN = 1024
_SCATTER_MIN = 64
# the ELL keeps this many slots free above the widest row at a cold build
# (and each sliced block above its widest row), so streaming edge growth
# rarely overflows a row (an overflow rebuilds and re-sorts); prepare() also
# reaches a row growing by this many edges
_K_HEADROOM = 8
# each sliced block keeps at least this many slots above its widest row
# (then rounds up to 8).  Rows sorted by degree make nearly every row of a
# block as wide as its widest, so this is each row's room to grow before an
# overflow rebuild re-sorts; at SF10 the blocks then hold 56.6% of the
# dense slots (60.0% with 8)
_BLOCK_HEADROOM = 4


def _pow2(n: int, least: int) -> int:
    return max(least, 1 << max(0, int(n) - 1).bit_length())


def _octave_bucket(n: int, step: int) -> int:
    """The least of ``m * 2^e`` (``m`` in 16..31) that holds ``n``, rounded
    up to a multiple of ``step``: at most 1/16 over."""
    e = max(0, n.bit_length() - 5)  # n < 32 * 2^e
    return -(-(-(-n // (1 << e)) << e) // step) * step


def _field_bucket(n: int) -> int:
    """Rows of the field for ``n`` vertices, in 256-row blocks."""
    return _octave_bucket(max(int(n), _ROW_MIN), SLICE_ROWS)


def _k_bucket(k: int) -> int:
    return max(8, -(-int(k) // 8) * 8)


def _slot_bucket(rows: int) -> int:
    """Slot rows of the sliced ELL for ``rows`` in use, in whole chunks."""
    return _octave_bucket(max(int(rows), 1), SLICE_CHUNK)


def _solver_order(deg: np.ndarray, n_pad: int) -> np.ndarray:
    """Solver row -> vertex: the vertices by degree, descending (stable),
    then the pad rows ``len(deg)..n_pad-1`` in place."""
    return np.concatenate([np.argsort(-deg, kind="stable"), np.arange(len(deg), n_pad)])


def _block_widths(deg_rows: np.ndarray) -> np.ndarray:
    """Slots of each block of ``SLICE_ROWS`` rows (degrees in solver order):
    its widest row plus ``_BLOCK_HEADROOM``, to a multiple of 8."""
    top = deg_rows.reshape(-1, SLICE_ROWS).max(axis=1).astype(np.int64)
    return np.maximum(8, -(-(top + _BLOCK_HEADROOM) // 8) * 8)


def _sliced_rows(deg: np.ndarray, n_pad: int) -> int:
    """Slot rows in use after a cold build over vertex degrees ``deg``."""
    d = np.zeros(n_pad, np.int64)
    d[: len(deg)] = -np.sort(-deg)
    return int(_block_widths(d).sum())


def _slab_index(widths: np.ndarray, kmax: int, cap: int) -> Tuple[np.ndarray, np.ndarray]:
    """Where each slot row of the sliced ELL comes from, and its block.

    Slot row ``off_b + j`` (``j < w_b``) is slot ``j`` of block ``b``: row
    ``b * kmax + j`` of the dense ELL laid out block-major
    (``[n_blocks * kmax, SLICE_ROWS]``); rows past the last slab read -1
    (zero weight).  ``blk[c]`` is the block of chunk ``c``; chunks past the
    last slab belong to the last block."""
    nb = len(widths)
    off = np.concatenate([[0], np.cumsum(widths)])
    b = np.repeat(np.arange(nb), widths)
    src = np.full(cap, -1, np.int32)
    src[: off[-1]] = b * kmax + np.arange(off[-1]) - off[b]
    blk = np.full(cap // SLICE_CHUNK, nb - 1, np.int32)
    blk[: off[-1] // SLICE_CHUNK] = b[::SLICE_CHUNK]
    return src, blk


def _sym_halves(
    src: np.ndarray, dst: np.ndarray, w: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Each undirected edge as two directed halves (u->v and v->u)."""
    uu = np.concatenate([src, dst]).astype(np.int64)
    vv = np.concatenate([dst, src]).astype(np.int64)
    ww = np.concatenate([w, w]).astype(np.float32)
    return uu, vv, ww


def _fill_rows(
    cols: np.ndarray,
    vals: np.ndarray,
    rows: np.ndarray,
    uu: np.ndarray,
    vv: np.ndarray,
    ww: np.ndarray,
) -> bool:
    """Recompute the ELL rows in ``rows`` from directed halves (uu -> vv).

    Returns False when some row overflows kmax (caller must rebuild)."""
    kmax = cols.shape[1]
    sel = np.isin(uu, rows)
    uu, vv, ww = uu[sel], vv[sel], ww[sel]
    order = np.argsort(uu, kind="stable")
    uu, vv, ww = uu[order], vv[order], ww[order]
    counts = np.bincount(uu, minlength=cols.shape[0])
    if counts[rows].max(initial=0) > kmax:
        return False
    # reset to self-pad, then scatter each row's neighbor run
    cols[rows] = rows[:, None]
    vals[rows] = 0.0
    starts = np.zeros(cols.shape[0] + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    for u in rows.tolist():
        lo, hi = int(starts[u]), int(starts[u + 1])
        k = hi - lo
        if k:
            cols[u, :k] = vv[lo:hi]
            vals[u, :k] = ww[lo:hi]
    return True


def _relax_loop(step, heat, q, frozen, frozen_heat, tol, max_iters):
    """Sweeps ``heat <- step(heat)`` until one moves no entry by ``tol`` or
    ``max_iters`` ran; rows in ``frozen`` stay at ``frozen_heat``.  Returns
    the field, the sweeps run, and the sup-norm move of one more sweep."""

    def sweep(h):
        return jnp.where(frozen, frozen_heat, step(h))

    h, it = steady_state(heat, lambda hh, _: sweep(hh), lambda k: q,
                         max_iters=max_iters, tol=tol)
    return h, it, jnp.max(jnp.abs(sweep(h) - h))


def _heat_relax(heat, cols, vals, q, frozen, frozen_heat, coef, tol, max_iters, *, use_kernel):
    """The relaxation over a dense ``[rows, kmax]`` ELL (frontier sub-solve)."""
    def step(h):
        return ops.dhd_step(h, cols, vals, q, alpha=coef[0], gamma=coef[1], beta=coef[2],
                            use_kernel=use_kernel)

    return _relax_loop(step, heat, q, frozen, frozen_heat, tol, max_iters)


def _cut_slabs(cols, vals, src):
    """The sliced ELL's slabs cut from the dense ``[n_pad, kmax]`` ELL: one
    row gather of its block-major layout (slot row ``t`` from row
    ``src[t]``; rows past the slabs, ``src`` -1, get column 0, weight 0)."""
    n_pad, kmax = cols.shape
    live = (src >= 0)[:, None]

    def cut(x):
        bm = x.reshape(n_pad // SLICE_ROWS, SLICE_ROWS, kmax).transpose(0, 2, 1)
        return jnp.where(live, bm.reshape(-1, SLICE_ROWS)[jnp.maximum(src, 0)], 0)

    return cut(cols), cut(vals)


def _heat_relax_sliced(heat, cols, vals, src, blk, q, frozen, frozen_heat, coef, tol, max_iters,
                       *, use_kernel):
    """The relaxation over the sliced ELL, its slabs cut from the dense
    ``[n_pad, kmax]`` mirror once per solve."""
    s_cols, s_vals = _cut_slabs(cols, vals, src)

    def step(h):
        return ops.dhd_sliced(h, s_cols, s_vals, blk, q, alpha=coef[0], gamma=coef[1],
                              beta=coef[2], use_kernel=use_kernel)

    return _relax_loop(step, heat, q, frozen, frozen_heat, tol, max_iters)


def _heat_set_rows(cols, vals, idx, rows_cols, rows_vals):
    return cols.at[idx].set(rows_cols), vals.at[idx].set(rows_vals)


# compiled programs by (kind, shapes, kernel path); shared by every instance
_PROGRAMS: Dict[tuple, object] = {}  # geolint: allow[GL001] — reset_programs()


def reset_programs() -> None:
    """Drop every compiled program (test isolation; each recompiles on use)."""
    _PROGRAMS.clear()


def _program(kind: str, rows: int, kmax: int, r: int = 0):
    """The compiled program of ``kind`` over a dense ``[rows, kmax]`` ELL,
    compiled on first use: ``relax`` (the frontier sub-solve), ``sliced``
    (the global sweeps over ``r`` slot rows of sliced ELL cut from it) or
    ``scatter`` (``r`` rows written into it)."""
    use_kernel = ops.on_tpu()
    key = (kind, rows, kmax, r, use_kernel)
    prog = _PROGRAMS.get(key)
    if prog is None:
        f32, i32 = jnp.float32, jnp.int32
        sd = jax.ShapeDtypeStruct
        ell = (sd((rows, kmax), i32), sd((rows, kmax), f32))
        vec = sd((rows,), f32)
        tail = (vec, sd((rows,), jnp.bool_), vec, sd((3,), f32), sd((), f32), sd((), i32))
        if kind == "relax":
            low = jax.jit(_heat_relax, static_argnames="use_kernel").lower(
                vec, *ell, *tail, use_kernel=use_kernel)
        elif kind == "sliced":
            layout = (sd((r,), i32), sd((r // SLICE_CHUNK,), i32))
            low = jax.jit(_heat_relax_sliced, static_argnames="use_kernel").lower(
                vec, *ell, *layout, *tail, use_kernel=use_kernel)
        else:
            args = (*ell, sd((r,), i32), sd((r, kmax), i32), sd((r, kmax), f32))
            low = jax.jit(_heat_set_rows).lower(*args)
        prog = _PROGRAMS[key] = low.compile()
    return prog


class StreamingHeat:
    """Persistent DHD equilibrium over the alive graph, warm-updated per batch.

    ``rebuild`` performs the cold construction (and is the overflow fallback):
    it sorts the rows into solver order and sizes each sliced block;
    ``update`` patches the touched ELL rows and re-solves warm; ``prepare``
    compiles ahead every program the next batches can run.  Each solve is
    one compiled program per shape bucket, with ``alpha``, the tolerance and
    the sweep budget as operands.  Every array here is in solver order;
    ``vertex_heat`` maps the field back to vertex order.
    """

    def __init__(
        self,
        params: DHDParams = STREAMING_DHD_PARAMS,
        max_iters: int = 300,
        tol: float = 1e-6,
        tracer: Optional[Tracer] = None,
    ) -> None:
        self.params = params
        self.alpha = params.alpha  # clamped per-graph by _effective_alpha
        self.max_iters = max_iters
        self.tol = tol
        self.tracer = tracer if tracer is not None else Tracer()
        self.n_nodes = 0
        self.n_edges = 0  # alive undirected edges of the last build or update
        self.sid: Optional[np.ndarray] = None  # [n_pad] vertex -> solver row
        self.cols: Optional[np.ndarray] = None  # [n_pad, kmax] int32 solver ids
        self.vals: Optional[np.ndarray] = None  # [n_pad, kmax] float32
        self.heat: Optional[np.ndarray] = None  # [n_pad] float32
        self.q: Optional[np.ndarray] = None  # [n_pad] float32
        self.widths: Optional[np.ndarray] = None  # [n_pad // SLICE_ROWS] slots a block
        # staleness metric: sup-norm change of one more sweep from the field
        # the last solve() exited with (0 at equilibrium, >0 when the sweep
        # budget ran out first).  Surfaced via WarmStats / UpdateReport.
        self.residual: float = 0.0
        # device-resident dense adjacency; refreshed by row scatter on warm
        # updates; the sliced layout's slab index and chunk blocks
        self._cols_j: Optional[jnp.ndarray] = None
        self._vals_j: Optional[jnp.ndarray] = None
        self._slab: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None

    def _sync_device(self, rows: Optional[np.ndarray] = None) -> None:
        """Mirror cols/vals to device — full upload, or a row scatter when
        only ``rows`` changed (saves the [n, kmax] host->device copy that
        otherwise dominates small warm updates).  The scatter's row count is
        padded to a power of two, so a few programs serve every batch."""
        n_pad = self.cols.shape[0]
        full = rows is None or self._cols_j is None or self._cols_j.shape != self.cols.shape
        r = 0 if full or not len(rows) else _pow2(len(rows), _SCATTER_MIN)
        full = full or r > n_pad // 2
        with self.tracer.span("heat.sync", track="store",
                              rows=n_pad if full else len(rows),
                              bucket=n_pad if full else r):
            if full:
                self._cols_j = jnp.asarray(self.cols)
                self._vals_j = jnp.asarray(self.vals)
            elif r:
                idx = np.full(r, rows[-1], np.int32)
                idx[: len(rows)] = rows
                prog = _program("scatter", n_pad, self.cols.shape[1], r)
                self._cols_j, self._vals_j = prog(
                    self._cols_j, self._vals_j, idx, self.cols[idx], self.vals[idx])
                get_registry().counter("heat.scatter_rows", bucket=r).inc(len(rows))

    def _set_layout(self) -> None:
        """Upload the sliced layout (slab index and chunk blocks) of the
        present block widths."""
        used = int(self.widths.sum())
        src, blk = _slab_index(self.widths, self.cols.shape[1], _slot_bucket(used))
        self._slab = (jnp.asarray(src), jnp.asarray(blk))

    @property
    def slots(self) -> int:
        """Slots each global sweep gathers over (the sliced ELL's capacity)."""
        return 0 if self._slab is None else int(self._slab[0].shape[0]) * SLICE_ROWS

    @property
    def vertex_heat(self) -> Optional[np.ndarray]:
        """Equilibrium heat for the real vertices, in vertex order."""
        return None if self.heat is None else self.heat[self.sid[: self.n_nodes]]

    def _effective_alpha(self) -> float:
        """Clamp alpha into the Theorem-1 contraction regime.

        ||L_dir||_inf <= max_e A_e + max_v weighted_deg(v) for any heat
        ordering (out-flows average over |N^out|, in-flows are bounded by the
        incident weight sum), so alpha <= 0.5 * gamma / ((1-gamma) * bound)
        makes the update map a contraction.  That is what guarantees a
        *unique* steady state — without it the ReLU-gated flow has multiple
        equilibria and warm vs cold solves can land on different ones.
        Recomputed after every topology patch so warm updates and cold
        rebuilds of the same graph always iterate the same map.
        """
        p = self.params
        wdeg = float(self.vals.sum(axis=1).max(initial=0.0))
        wmax = float(self.vals.max(initial=0.0))
        bound = wmax + wdeg
        if bound <= 0.0:
            return p.alpha
        safe = 0.5 * p.gamma / ((1.0 - p.gamma) * bound)
        return min(p.alpha, safe)

    # ---------------------------------------------------------- programs
    def prepare(self, n_nodes: int, src: np.ndarray, dst: np.ndarray) -> int:
        """Compile every program the next batches can run, changing no state.

        Reachable from the present graph (``n_nodes`` vertices, alive edges
        ``src``/``dst``): the field's rows, and the next bucket once fewer
        than 1/32 of them are free; the ELL width in use (or the one a cold
        build picks) and each width a rebuild picks while the widest row
        grows by up to ``_K_HEADROOM`` edges; the sliced slot rows in use
        and those a rebuild over these degrees picks (new pad blocks add
        their 8 slots), each with the next bucket above; every frontier
        sub-solve size up to half the field (a larger frontier relaxes on
        the whole field's sliced program); each row-scatter size up to half
        the field.  Returns the programs made."""
        deg = np.bincount(np.concatenate([src, dst]).astype(np.int64), minlength=n_nodes)
        d = int(deg.max(initial=1))
        grown = range(d, d + _K_HEADROOM + 1)
        if self.cols is not None:
            n_pad, kmax = self.cols.shape
            widths = {kmax} | {_k_bucket(x + _K_HEADROOM) for x in grown if x > kmax}
            used = {_sliced_rows(deg, n_pad), int(self.widths.sum())}
        else:
            n_pad = _field_bucket(n_nodes)
            widths = {_k_bucket(x + _K_HEADROOM) for x in grown}
            used = {_sliced_rows(deg, n_pad)}
        fields = [n_pad] + ([_field_bucket(n_pad + 1)] if 32 * (n_pad - n_nodes) < n_pad else [])
        pad_block = _k_bucket(_BLOCK_HEADROOM)
        before = len(_PROGRAMS)
        for k in sorted(widths):
            for n in fields:
                caps = {_slot_bucket(u + (n - n_pad) // SLICE_ROWS * pad_block) for u in used}
                caps.add(_slot_bucket(max(caps) + 1))
                for c in sorted(caps):
                    _program("sliced", n, k, c)
                r = _SCATTER_MIN
                while r <= n // 2:
                    _program("scatter", n, k, r)
                    r *= 2
            rows = _ROW_MIN
            while rows <= fields[-1] // 2:
                _program("relax", rows, k)
                rows *= 2
        return len(_PROGRAMS) - before

    def _coef(self) -> np.ndarray:
        p = self.params
        return np.asarray([self.alpha, p.gamma, p.beta], np.float32)

    def _relax_sliced(self, frozen=None, max_iters: Optional[int] = None,
                      tol: Optional[float] = None):
        """The compiled relaxation of the whole field over the sliced ELL
        (rows in ``frozen`` held at their present heat); returns (field,
        sweeps, residual) on device."""
        n_pad, kmax = self.cols.shape
        if frozen is None:
            frozen = np.zeros(n_pad, bool)
        src, blk = self._slab
        return _program("sliced", n_pad, kmax, src.shape[0])(
            self.heat, self._cols_j, self._vals_j, src, blk, self.q, frozen, self.heat,
            self._coef(), np.float32(tol or self.tol), np.int32(max_iters or self.max_iters))

    # ----------------------------------------------------------- cold path
    def rebuild(
        self,
        n_nodes: int,
        src: np.ndarray,
        dst: np.ndarray,
        weights: np.ndarray,
        q: np.ndarray,
        heat0: Optional[np.ndarray] = None,
    ) -> int:
        """Cold build of the symmetric ELL in solver order + full solve.
        Returns iterations.

        ``heat0`` warm-seeds the solve from a prior field of length
        ``n_nodes`` (vertex order) — the compaction re-key path, where the
        topology arrays are renumbered but the equilibrium is (row-permuted)
        unchanged, and the overflow fallback, which rebuilds (and re-sorts)
        under the same field."""
        uu, vv, ww = _sym_halves(src, dst, weights)
        deg = np.bincount(uu, minlength=n_nodes) if len(uu) else np.zeros(n_nodes, np.int64)
        kmax = _k_bucket(int(deg.max(initial=1)) + _K_HEADROOM)
        n_pad = _field_bucket(n_nodes)
        vid = _solver_order(deg, n_pad)
        self.sid = np.empty(n_pad, np.int64)
        self.sid[vid] = np.arange(n_pad)
        deg_rows = np.zeros(n_pad, np.int64)
        deg_rows[:n_nodes] = deg[vid[:n_nodes]]
        self.widths = _block_widths(deg_rows)
        self.n_nodes = n_nodes
        self.n_edges = len(src)
        self.cols = np.repeat(np.arange(n_pad, dtype=np.int32)[:, None], kmax, axis=1)
        self.vals = np.zeros((n_pad, kmax), np.float32)
        if len(uu):
            _fill_rows(self.cols, self.vals, np.arange(n_nodes), self.sid[uu], self.sid[vv], ww)
        self.q = np.zeros(n_pad, np.float32)
        self.q[self.sid[:n_nodes]] = np.asarray(q, np.float32)
        self.heat = self.q.copy()
        if heat0 is not None:
            self.heat[self.sid[:n_nodes]] = np.asarray(heat0, np.float32)
        self.alpha = self._effective_alpha()
        self._sync_device()
        self._set_layout()
        return self.solve()

    # --------------------------------------------------------------- solve
    def solve(self, max_iters: Optional[int] = None, tol: Optional[float] = None,
              local_iters: int = 0) -> int:
        """Full-graph sweeps from the current field until the residual < tol.

        One compiled program (``lax.while_loop``) runs the whole fixed-point
        iteration on device over the sliced ELL and one more probe sweep that
        prices the carried-over staleness: how far one more iteration would
        still move the field (0 when converged within tol).  ``local_iters``
        only tags the span: the frontier sweeps that ran before it."""
        if self._cols_j is None:
            self._sync_device()
        if self._slab is None:
            self._set_layout()
        with self.tracer.span("heat.sweeps", track="store", n_pad=self.cols.shape[0],
                              slots=self.slots, layout="sliced", nodes=self.n_nodes,
                              edges=self.n_edges, local=int(local_iters)) as sp:
            h, it, res = self._relax_sliced(max_iters=max_iters, tol=tol)
            self.heat = np.array(h)  # np.array: jax buffers are read-only views
            self.residual = float(res)
            it = int(it)
            sp.set_tags(iters=it)
        return it

    # ---------------------------------------------------------- warm path
    def _neighbors_of(self, mask: np.ndarray) -> np.ndarray:
        """Vertices adjacent to the masked set (via the current ELL rows)."""
        rows = np.where(mask)[0]
        if len(rows) == 0:
            return np.zeros(0, np.int64)
        nb = self.cols[rows][self.vals[rows] > 0]
        return np.unique(nb.astype(np.int64))

    def _grow(self, n_nodes: int) -> None:
        """Grow the field to hold ``n_nodes`` vertices: the new rows are pad
        rows in place (new vertices take them) in blocks of the pad width."""
        n_pad_old, kmax = self.cols.shape
        n_pad = _field_bucket(n_nodes)
        extra = n_pad - n_pad_old
        pad_cols = np.repeat(np.arange(n_pad_old, n_pad, dtype=np.int32)[:, None], kmax, axis=1)
        self.cols = np.concatenate([self.cols, pad_cols])
        self.vals = np.concatenate([self.vals, np.zeros((extra, kmax), np.float32)])
        self.heat = np.concatenate([self.heat, np.zeros(extra, np.float32)])
        self.sid = np.concatenate([self.sid, np.arange(n_pad_old, n_pad)])
        self.widths = np.concatenate([self.widths,
                                      _block_widths(np.zeros(extra, np.int64))])
        self._slab = None

    def _outgrown(self, rows: np.ndarray) -> bool:
        """Whether a row in ``rows`` holds an edge past its block's width."""
        w = self.widths[rows // SLICE_ROWS]
        past = np.arange(self.cols.shape[1])[None, :] >= w[:, None]
        return bool((self.vals[rows] != 0)[past].any())

    def update(
        self,
        n_nodes: int,
        src: np.ndarray,
        dst: np.ndarray,
        weights: np.ndarray,
        q: np.ndarray,
        touched: np.ndarray,
        halo_hops: int = 1,
        local_iters: int = 16,
        max_frontier_frac: float = 0.2,
    ) -> WarmStats:
        """Absorb a topology/source delta and re-solve warm.

        ``src/dst/weights`` describe the *alive* undirected edges of the new
        graph; ``touched`` are the vertices whose incident edges or sources
        changed (new vertices included, ids at the end of the range).
        """
        if self.cols is None:
            it = self.rebuild(n_nodes, src, dst, weights, q)
            return WarmStats(n_nodes, 0, 0, it, self.residual)
        if n_nodes > self.cols.shape[0]:
            self._grow(n_nodes)
        self.n_nodes = n_nodes
        self.n_edges = len(src)
        self.q = np.zeros(self.cols.shape[0], np.float32)
        self.q[self.sid[:n_nodes]] = np.asarray(q, np.float32)

        touched = np.sort(self.sid[np.unique(np.asarray(touched, np.int64))])
        uu, vv, ww = _sym_halves(src, dst, weights)
        if (not _fill_rows(self.cols, self.vals, touched, self.sid[uu], self.sid[vv], ww)
                or self._outgrown(touched)):
            # a touched row outgrew its block (or kmax) — rebuild and re-sort,
            # same field
            get_registry().counter("heat.block_overflow").inc()
            it = self.rebuild(n_nodes, src, dst, weights, q, heat0=self.vertex_heat.copy())
            return WarmStats(len(touched), 0, 0, it, self.residual)
        self.alpha = self._effective_alpha()
        self._sync_device(rows=touched)
        if self._slab is None:
            self._set_layout()

        # --- frontier pre-solve over F + clamped halo ---------------------
        # Only worth it when the frontier stays a small fraction of the
        # graph; at high churn the expansion covers nearly every vertex and
        # the local phase would just duplicate the global sweeps.
        local_done = halo = 0
        frontier = touched
        if 0 < len(touched) <= max_frontier_frac * n_nodes:
            fmask = np.zeros(self.cols.shape[0], dtype=bool)
            fmask[touched] = True
            for _ in range(halo_hops):
                fmask[self._neighbors_of(fmask)] = True
            frontier = np.where(fmask)[0]
            if len(frontier) <= max_frontier_frac * n_nodes:
                local_done, halo = self._local_solve(fmask, frontier, local_iters)

        # --- global mop-up sweeps ----------------------------------------
        it = self.solve(local_iters=local_done)
        return WarmStats(
            frontier_size=len(frontier),
            halo_size=halo,
            local_iters=local_done,
            global_iters=it,
            residual=self.residual,
        )

    def _local_solve(self, fmask, frontier, local_iters: int) -> Tuple[int, int]:
        """Relax the frontier alone, every other row held at its present
        heat.  Returns the sweeps run and the halo's size (the frontier's
        neighbours and their ghost ring)."""
        bmask = np.zeros_like(fmask)
        bmask[self._neighbors_of(fmask)] = True
        bmask &= ~fmask
        # ghost ring: halo rows are kept complete so their |N^out| is
        # exact, which needs their out-of-halo neighbors present too
        cmask = np.zeros_like(fmask)
        cmask[self._neighbors_of(bmask)] = True
        cmask &= ~(fmask | bmask)
        sub = np.concatenate([frontier, np.where(bmask)[0], np.where(cmask)[0]])
        halo = len(sub) - len(frontier)
        # pad the subproblem to a power of two up to half the field's rows:
        # sub sizes vary per batch, and the buckets let consecutive batches
        # reuse one program (pad rows = isolated, held at 0).  A larger one
        # relaxes the frontier on the whole field's sliced ELL, every other
        # row held: the same sweeps.
        n_sub = _pow2(len(sub), _ROW_MIN)
        if 2 * n_sub > len(fmask):
            h, k_local, _ = self._relax_sliced(frozen=~fmask, max_iters=local_iters)
            self.heat[frontier] = np.asarray(h)[frontier]
            return int(k_local), halo
        return self._sub_solve(frontier, int(bmask.sum()), sub, n_sub, local_iters), halo

    def _sub_solve(self, frontier, n_halo: int, sub, n_sub: int, local_iters: int) -> int:
        """Relax the frontier on its own dense sub-ELL of ``n_sub`` rows
        (frontier, halo, ghost ring), every row but the frontier's held at
        its present heat.  Returns the sweeps run."""
        n_pad, kmax = self.cols.shape
        lmap = np.full(n_pad, -1, dtype=np.int64)
        lmap[sub] = np.arange(len(sub))
        rows_fb = sub[: len(frontier) + n_halo]
        cols_l = np.repeat(np.arange(n_sub, dtype=np.int32)[:, None], kmax, axis=1)
        vals_l = np.zeros((n_sub, kmax), np.float32)
        cols_l[: len(rows_fb)] = lmap[self.cols[rows_fb]].astype(np.int32)
        vals_l[: len(rows_fb)] = self.vals[rows_fb]
        frozen = np.arange(n_sub) >= len(frontier)
        q_sub = np.zeros(n_sub, np.float32)
        q_sub[: len(sub)] = self.q[sub]
        h_sub = np.zeros(n_sub, np.float32)
        h_sub[: len(sub)] = self.heat[sub]
        h_sub, k_local, _ = _program("relax", n_sub, kmax)(
            h_sub, cols_l, vals_l, q_sub, frozen, h_sub, self._coef(), np.float32(self.tol),
            np.int32(local_iters))
        self.heat[frontier] = np.asarray(h_sub)[: len(frontier)]
        return int(k_local)
