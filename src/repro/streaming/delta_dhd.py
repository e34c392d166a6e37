"""Warm-started DHD steady state for streaming updates (tentpole, part 3).

The store keeps the previous equilibrium heat field; a mutation batch only
perturbs the field near the touched vertices, so the new equilibrium is
reached in far fewer sweeps than a cold solve:

  1. *frontier pre-solve* — extract the touched frontier plus a one-ring halo,
     clamp the halo to its current (globally-correct) heat, and relax the
     frontier on the small sub-ELL;
  2. *global sweeps* — run full-graph DHD steps from the pre-solved field
     until the residual drops below tolerance.

Both phases go through :func:`repro.kernels.ops.dhd_step`, i.e. the Pallas
ELL kernel on TPU and the vectorized jnp reference on CPU.  The ELL adjacency
is patched row-wise per batch (only touched rows are recomputed) rather than
rebuilt.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dhd import DHDParams, steady_state
from ..kernels import ops
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
# every sweep gathers over every slot of every row), rounded to the kernel's
# 256-row block; the ELL width to a multiple of 8 slots; the frontier
# sub-solve's rows and the scattered row sets to a power of two.  Pad rows
# are isolated self-loops with zero weight and zero source, so they hold heat
# 0 forever; pad entries of a row scatter re-write the last real row with its
# own contents.
_ROW_MIN = 1024
_SCATTER_MIN = 64
# the ELL keeps this many slots free above the widest row at a cold build,
# so streaming edge growth rarely overflows a row (an overflow rebuilds);
# prepare() also reaches a row growing by this many edges
_K_HEADROOM = 8


def _pow2(n: int, least: int) -> int:
    return max(least, 1 << max(0, int(n) - 1).bit_length())


def _field_bucket(n: int) -> int:
    """Rows of the field for ``n`` vertices: the least of ``m * 2^e`` (``m``
    in 16..31) that holds them, rounded up to a multiple of 256."""
    n = max(int(n), _ROW_MIN)
    e = max(0, n.bit_length() - 5)  # n < 32 * 2^e
    return -(-(-(-n // (1 << e)) << e) // 256) * 256


def _k_bucket(k: int) -> int:
    return max(8, -(-int(k) // 8) * 8)


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


def _heat_relax(heat, cols, vals, q, frozen, frozen_heat, coef, tol, max_iters, *, use_kernel):
    """Sweeps ``heat <- step(heat)`` until one moves no entry by ``tol`` or
    ``max_iters`` ran; rows in ``frozen`` stay at ``frozen_heat``.  Returns
    the field, the sweeps run, and the sup-norm move of one more sweep."""

    def sweep(h):
        nh = ops.dhd_step(h, cols, vals, q, alpha=coef[0], gamma=coef[1], beta=coef[2],
                          use_kernel=use_kernel)
        return jnp.where(frozen, frozen_heat, nh)

    h, it = steady_state(heat, lambda hh, _: sweep(hh), lambda k: q,
                         max_iters=max_iters, tol=tol)
    return h, it, jnp.max(jnp.abs(sweep(h) - h))


def _heat_set_rows(cols, vals, idx, rows_cols, rows_vals):
    return cols.at[idx].set(rows_cols), vals.at[idx].set(rows_vals)


# compiled programs by (kind, shapes, kernel path); shared by every instance
_PROGRAMS: Dict[tuple, object] = {}  # geolint: allow[GL001] — reset_programs()


def reset_programs() -> None:
    """Drop every compiled program (test isolation; each recompiles on use)."""
    _PROGRAMS.clear()


def _program(kind: str, rows: int, kmax: int, r: int = 0):
    """The compiled relaxation over ``[rows, kmax]`` or row scatter of ``r``
    rows into it, compiled on first use."""
    use_kernel = ops.on_tpu()
    key = (kind, rows, kmax, r, use_kernel)
    prog = _PROGRAMS.get(key)
    if prog is None:
        f32, i32 = jnp.float32, jnp.int32
        sd = jax.ShapeDtypeStruct
        ell = (sd((rows, kmax), i32), sd((rows, kmax), f32))
        if kind == "relax":
            vec = sd((rows,), f32)
            args = (vec, *ell, vec, sd((rows,), jnp.bool_), vec, sd((3,), f32),
                    sd((), f32), sd((), i32))
            low = jax.jit(_heat_relax, static_argnames="use_kernel").lower(
                *args, use_kernel=use_kernel)
        else:
            args = (*ell, sd((r,), i32), sd((r, kmax), i32), sd((r, kmax), f32))
            low = jax.jit(_heat_set_rows).lower(*args)
        prog = _PROGRAMS[key] = low.compile()
    return prog


class StreamingHeat:
    """Persistent DHD equilibrium over the alive graph, warm-updated per batch.

    ``rebuild`` performs the cold construction (and is the overflow fallback);
    ``update`` patches the touched ELL rows and re-solves warm; ``prepare``
    compiles ahead every program the next batches can run.  Each solve is
    one compiled program per shape bucket, with ``alpha``, the tolerance and
    the sweep budget as operands.
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
        self.cols: Optional[np.ndarray] = None  # [n_pad, kmax] int32
        self.vals: Optional[np.ndarray] = None  # [n_pad, kmax] float32
        self.heat: Optional[np.ndarray] = None  # [n_pad] float32
        self.q: Optional[np.ndarray] = None  # [n_pad] float32
        # staleness metric: sup-norm change of one more sweep from the field
        # the last solve() exited with (0 at equilibrium, >0 when the sweep
        # budget ran out first).  Surfaced via WarmStats / UpdateReport.
        self.residual: float = 0.0
        # device-resident adjacency; refreshed by row scatter on warm updates
        self._cols_j: Optional[jnp.ndarray] = None
        self._vals_j: Optional[jnp.ndarray] = None

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

    @property
    def vertex_heat(self) -> Optional[np.ndarray]:
        """Equilibrium heat for the real vertices (pad rows stripped)."""
        return None if self.heat is None else self.heat[: self.n_nodes]

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
        grows by up to ``_K_HEADROOM`` edges; every frontier sub-solve size
        below the field; each row-scatter size up to half the field.
        Returns the programs made."""
        deg = np.bincount(np.concatenate([src, dst]).astype(np.int64), minlength=n_nodes)
        d = int(deg.max(initial=1))
        grown = range(d, d + _K_HEADROOM + 1)
        if self.cols is not None:
            n_pad, kmax = self.cols.shape
            widths = {kmax} | {_k_bucket(x + _K_HEADROOM) for x in grown if x > kmax}
        else:
            n_pad = _field_bucket(n_nodes)
            widths = {_k_bucket(x + _K_HEADROOM) for x in grown}
        fields = [n_pad] + ([_field_bucket(n_pad + 1)] if 32 * (n_pad - n_nodes) < n_pad else [])
        before = len(_PROGRAMS)
        for k in sorted(widths):
            for n in fields:
                _program("relax", n, k)
                r = _SCATTER_MIN
                while r <= n // 2:
                    _program("scatter", n, k, r)
                    r *= 2
            rows = _ROW_MIN
            while rows < fields[-1]:
                _program("relax", rows, k)
                rows *= 2
        return len(_PROGRAMS) - before

    def _relax(self, heat, cols_j, vals_j, q, frozen=None, frozen_heat=None,
               max_iters: Optional[int] = None, tol: Optional[float] = None):
        """One compiled relaxation; returns (field, sweeps, residual) on device."""
        rows, kmax = cols_j.shape
        if frozen is None:
            frozen = np.zeros(rows, bool)
            frozen_heat = np.zeros(rows, np.float32)
        p = self.params
        coef = np.asarray([self.alpha, p.gamma, p.beta], np.float32)
        return _program("relax", rows, kmax)(
            heat, cols_j, vals_j, q, frozen, frozen_heat, coef,
            np.float32(tol or self.tol), np.int32(max_iters or self.max_iters))

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
        """Cold build of the symmetric ELL + full solve.  Returns iterations.

        ``heat0`` warm-seeds the solve from a prior field of length
        ``n_nodes`` — the compaction re-key path, where the topology arrays
        are renumbered but the equilibrium is (row-permuted) unchanged, and
        the overflow fallback, which rebuilds wider rows under the same field."""
        uu, vv, ww = _sym_halves(src, dst, weights)
        deg = np.bincount(uu, minlength=n_nodes) if len(uu) else np.zeros(n_nodes, np.int64)
        kmax = _k_bucket(int(deg.max(initial=1)) + _K_HEADROOM)
        n_pad = _field_bucket(n_nodes)
        self.n_nodes = n_nodes
        self.n_edges = len(src)
        self.cols = np.repeat(np.arange(n_pad, dtype=np.int32)[:, None], kmax, axis=1)
        self.vals = np.zeros((n_pad, kmax), np.float32)
        if len(uu):
            _fill_rows(self.cols, self.vals, np.arange(n_nodes), uu, vv, ww)
        self.q = np.zeros(n_pad, np.float32)
        self.q[:n_nodes] = np.asarray(q, np.float32)
        self.heat = self.q.copy()
        if heat0 is not None:
            self.heat[:n_nodes] = np.asarray(heat0, np.float32)
        self.alpha = self._effective_alpha()
        self._sync_device()
        return self.solve()

    # --------------------------------------------------------------- solve
    def solve(self, max_iters: Optional[int] = None, tol: Optional[float] = None,
              local_iters: int = 0) -> int:
        """Full-graph sweeps from the current field until the residual < tol.

        One compiled program (``lax.while_loop``) runs the whole fixed-point
        iteration on device and one more probe sweep that prices the
        carried-over staleness: how far one more iteration would still move
        the field (0 when converged within tol).  ``local_iters`` only tags
        the span: the frontier sweeps that ran before it."""
        if self._cols_j is None:
            self._sync_device()
        n_pad, kmax = self.cols.shape
        with self.tracer.span("heat.sweeps", track="store", n_pad=n_pad, k_pad=kmax,
                              nodes=self.n_nodes, edges=self.n_edges,
                              local=int(local_iters)) as sp:
            h, it, res = self._relax(self.heat, self._cols_j, self._vals_j, self.q,
                                     max_iters=max_iters, tol=tol)
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
        n_pad_old = self.cols.shape[0]
        if n_nodes > n_pad_old:
            n_pad = _field_bucket(n_nodes)
            kmax = self.cols.shape[1]
            extra = n_pad - n_pad_old
            pad_cols = np.repeat(
                np.arange(n_pad_old, n_pad, dtype=np.int32)[:, None], kmax, axis=1
            )
            self.cols = np.concatenate([self.cols, pad_cols])
            self.vals = np.concatenate([self.vals, np.zeros((extra, kmax), np.float32)])
            self.heat = np.concatenate([self.heat, np.zeros(extra, np.float32)])
        self.n_nodes = n_nodes
        self.n_edges = len(src)
        self.q = np.zeros(self.cols.shape[0], np.float32)
        self.q[:n_nodes] = np.asarray(q, np.float32)

        touched = np.unique(np.asarray(touched, np.int64))
        uu, vv, ww = _sym_halves(src, dst, weights)
        if not _fill_rows(self.cols, self.vals, touched, uu, vv, ww):
            # a touched row outgrew kmax — rebuild wider rows, same field
            it = self.rebuild(n_nodes, src, dst, weights, q, heat0=self.heat[:n_nodes].copy())
            return WarmStats(len(touched), 0, 0, it, self.residual)
        self.alpha = self._effective_alpha()
        self._sync_device(rows=touched)

        # --- frontier pre-solve over F + clamped halo ---------------------
        # Only worth it when the frontier stays a small fraction of the
        # graph; at high churn the expansion covers nearly every vertex and
        # the local phase would just duplicate the global sweeps.
        n_pad = self.cols.shape[0]
        local_done = 0
        frontier = touched
        bmask = cmask = None
        if len(touched) and len(touched) <= max_frontier_frac * n_nodes:
            fmask = np.zeros(n_pad, dtype=bool)
            fmask[touched] = True
            for _ in range(halo_hops):
                fmask[self._neighbors_of(fmask)] = True
            frontier = np.where(fmask)[0]
            bmask = np.zeros(n_pad, dtype=bool)
            bmask[self._neighbors_of(fmask)] = True
            bmask &= ~fmask
            # ghost ring: halo rows are kept complete so their |N^out| is
            # exact, which needs their out-of-halo neighbors present too
            cmask = np.zeros(n_pad, dtype=bool)
            cmask[self._neighbors_of(bmask)] = True
            cmask &= ~(fmask | bmask)
        if (
            bmask is not None
            and len(frontier) <= max_frontier_frac * n_nodes
            and len(frontier)
        ):
            sub = np.concatenate([frontier, np.where(bmask)[0], np.where(cmask)[0]])
            # pad the subproblem to a power of two below the field's rows, or
            # to the field's: sub sizes vary per batch, and the buckets let
            # consecutive batches reuse one program (pad rows = isolated,
            # held at 0)
            n_sub = _pow2(len(sub), _ROW_MIN)
            n_sub = n_sub if n_sub < n_pad else n_pad
            lmap = np.full(n_pad, -1, dtype=np.int64)
            lmap[sub] = np.arange(len(sub))
            rows_fb = sub[: len(frontier) + int(bmask.sum())]
            cols_l = np.repeat(
                np.arange(n_sub, dtype=np.int32)[:, None], self.cols.shape[1], axis=1
            )
            vals_l = np.zeros((n_sub, self.cols.shape[1]), np.float32)
            cols_l[: len(rows_fb)] = lmap[self.cols[rows_fb]].astype(np.int32)
            vals_l[: len(rows_fb)] = self.vals[rows_fb]
            # every row but the frontier's is held at its present heat
            frozen = np.arange(n_sub) >= len(frontier)
            q_sub = np.zeros(n_sub, np.float32)
            q_sub[: len(sub)] = self.q[sub]
            h_sub = np.zeros(n_sub, np.float32)
            h_sub[: len(sub)] = self.heat[sub]
            h_sub, k_local, _ = self._relax(h_sub, cols_l, vals_l, q_sub, frozen, h_sub,
                                            max_iters=local_iters)
            local_done = int(k_local)
            self.heat[frontier] = np.asarray(h_sub)[: len(frontier)]

        # --- global mop-up sweeps ----------------------------------------
        it = self.solve(local_iters=local_done)
        return WarmStats(
            frontier_size=len(frontier),
            halo_size=0 if bmask is None else int(bmask.sum() + cmask.sum()),
            local_iters=local_done,
            global_iters=it,
            residual=self.residual,
        )
