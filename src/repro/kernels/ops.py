"""Public kernel API: jit'd wrappers dispatching Pallas kernel vs jnp oracle.

Policy: on TPU backends the Pallas kernels run compiled; on CPU (this
container) the default is the pure-jnp reference (fast, vectorized) while
``interpret=True`` forces the kernel body through the Pallas interpreter for
validation.  ``use_kernel`` can be pinned explicitly by callers/tests.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from ..obs import get_registry
from .autotune import get_autotuner
from .dhd_spmv import dhd_ell_step, dhd_ell_step_batch, dhd_sliced_step
from .embedding_bag import embedding_bag as _embedding_bag_kernel
from .flash_attention import flash_attention as _flash_attention_kernel
from .route_expand import route_expand as _route_expand_kernel

__all__ = [
    "attention",
    "dhd_step",
    "dhd_step_batch",
    "dhd_sliced",
    "diffuse_batch",
    "bag_lookup",
    "edge_cache_stats",
    "on_tpu",
    "route_expand_batch",
    "route_expand_candidates",
    "route_expand_subsets",
]


# ------------------------------------------------------- dispatch telemetry
def _obs_dispatch(op: str, path: str) -> None:
    reg = get_registry()
    if reg.enabled:
        reg.counter("kernels.dispatch", op=op, path=path).inc()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _attention_with_vjp(causal: bool, window: Optional[int], block_q: int,
                        block_kv: int, interpret: bool):
    """Trainable flash attention: Pallas kernel forward, reference-math
    backward (the standard pattern until a fused bwd kernel lands — the
    bwd recomputes attention from the saved q/k/v, so no S x S residuals
    are stored either way)."""

    @jax.custom_vjp
    def f(q, k, v):
        return _flash_attention_kernel(
            q, k, v, causal=causal, window=window,
            block_q=block_q, block_kv=block_kv, interpret=interpret,
        )

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, pullback = jax.vjp(
            lambda q_, k_, v_: ref.attention_ref(
                q_, k_, v_, causal=causal, window=window
            ),
            q, k, v,
        )
        return pullback(g)

    f.defvjp(fwd, bwd)
    return f


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    use_kernel: Optional[bool] = None,
    block_q: int = 128,
    block_kv: int = 128,
) -> jnp.ndarray:
    """FlashAttention when kernel-eligible, dense reference otherwise.

    Kernel eligibility: TPU backend (or explicit request) and block-divisible
    sequence lengths.  The kernel path is differentiable (custom VJP with a
    recompute backward), so it serves training and serving alike."""
    if use_kernel is None:
        use_kernel = on_tpu()
    sq, skv = q.shape[2], k.shape[2]
    divisible = sq % min(block_q, sq) == 0 and skv % min(block_kv, skv) == 0
    if use_kernel and divisible:
        fn = _attention_with_vjp(
            causal, window, min(block_q, sq), min(block_kv, skv), not on_tpu()
        )
        return fn(q, k, v)
    return ref.attention_ref(q, k, v, causal=causal, window=window)


# --------------------------------------------------- COO-tail edge recovery
# Rebuilding + deduping the full undirected edge list from (ELL, tail) is a
# host-side O(nnz log nnz) pass; streaming stores call dhd_step with the SAME
# adjacency arrays every sweep, so the deduped arrays are cached keyed on the
# *identity* of the inputs.  Entries hold strong references to their keys'
# arrays, so a live cache entry's ids can never be reused by a new object.
# CONTRACT: adjacency arrays passed to dhd_step/dhd_step_batch with a tail
# must not be mutated in place afterwards (jnp arrays — the expected input —
# are immutable; numpy callers must replace, not rewrite, their buffers), or
# the identity key would serve the pre-mutation edge list.
_EDGE_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()  # geolint: allow[GL001]
_EDGE_CACHE_MAX = 8


def reset_kernel_caches() -> None:
    """Drop the identity-keyed edge cache and the subset-mask table
    (test isolation hook; both rebuild lazily on next use)."""
    _EDGE_CACHE.clear()
    _SUBSET_HAS_CACHE.clear()


def edge_cache_stats() -> dict:
    """Edge-cache hit/miss counts from the process-default registry.

    Counts live in the registry (so ``registry.reset()`` clears them
    between benchmark runs); a disabled registry reports zeros."""
    reg = get_registry()
    hits = reg.counter("kernels.edge_cache", event="hit").value
    misses = reg.counter("kernels.edge_cache", event="miss").value
    hits = 0.0 if hits != hits else hits  # NaN from the no-op singleton
    misses = 0.0 if misses != misses else misses
    total = hits + misses
    return {
        "hits": int(hits),
        "misses": int(misses),
        "hit_rate": hits / total if total else 0.0,
    }


def _tail_edges(
    n: int, cols, vals, tail_src, tail_dst, tail_val
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Exact undirected (a, b, w) covering ELL rows + COO tail, deduped on
    the canonical (min, max) key (an edge may sit in one endpoint's ELL row
    while overflowing the other's)."""
    key = (n, id(cols), id(vals), id(tail_src), id(tail_dst), id(tail_val))
    hit = _EDGE_CACHE.get(key)
    if hit is not None:
        _EDGE_CACHE.move_to_end(key)
        get_registry().counter("kernels.edge_cache", event="hit").inc()
        return hit[1]
    cols_np, vals_np = np.asarray(cols), np.asarray(vals)
    iu, ik = np.nonzero(vals_np > 0)
    e_src = np.concatenate([iu, np.asarray(tail_src)])
    e_dst = np.concatenate([cols_np[iu, ik], np.asarray(tail_dst)])
    e_w = np.concatenate([vals_np[iu, ik], np.asarray(tail_val)])
    a = np.minimum(e_src, e_dst)
    b = np.maximum(e_src, e_dst)
    _, first = np.unique(a.astype(np.int64) * n + b, return_index=True)
    out = (
        jnp.asarray(a[first], jnp.int32),
        jnp.asarray(b[first], jnp.int32),
        jnp.asarray(e_w[first], jnp.float32),
    )
    _EDGE_CACHE[key] = ((cols, vals, tail_src, tail_dst, tail_val), out)
    get_registry().counter("kernels.edge_cache", event="miss").inc()
    while len(_EDGE_CACHE) > _EDGE_CACHE_MAX:
        _EDGE_CACHE.popitem(last=False)
    return out


def dhd_step(
    heat: jnp.ndarray,
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    q: jnp.ndarray,
    tail_src: Optional[jnp.ndarray] = None,
    tail_dst: Optional[jnp.ndarray] = None,
    tail_val: Optional[jnp.ndarray] = None,
    alpha: float = 0.5,
    gamma: float = 0.1,
    beta: float = 0.3,
    use_kernel: Optional[bool] = None,
    block_n: int = 256,
) -> jnp.ndarray:
    """DHD update over ELL (+ optional COO tail for overflow edges).

    The tail contributes to both |N_u^out| and the flows; since the ELL
    kernel computes counts internally, tail edges are folded in by running
    the edge-list reference over the tail *jointly* with per-row ELL flows
    only when a tail exists (rare: >q98 degree).  Placement confines DHD to
    clusters, so the no-tail fast path dominates.  The kernel path pads to
    the block size internally, so any row count is eligible.
    """
    if use_kernel is None:
        use_kernel = on_tpu()
    has_tail = tail_src is not None and tail_src.size > 0
    if has_tail:
        # Tail edges change |N_u^out| globally, so the blocked kernel cannot
        # be patched additively — use the exact edge-list formulation over
        # the (cached) reconstructed undirected edge list.
        n = heat.shape[0]
        a, b, w = _tail_edges(n, cols, vals, tail_src, tail_dst, tail_val)
        from ..core.dhd import dhd_step_edges

        out = dhd_step_edges(
            heat, a, b, w, q, n, alpha=alpha, gamma=gamma, beta=beta
        )
        _obs_dispatch("dhd_step", "tail_edges")
        return out
    if use_kernel:
        out = dhd_ell_step(
            heat, cols, vals, q, alpha=alpha, gamma=gamma, beta=beta,
            block_n=block_n, interpret=not on_tpu(),
        )
        _obs_dispatch("dhd_step", "kernel")
        return out
    out = ref.dhd_ell_ref(heat, cols, vals, q, alpha=alpha, gamma=gamma, beta=beta)
    _obs_dispatch("dhd_step", "ref")
    return out


def dhd_sliced(
    heat: jnp.ndarray,  # [n]
    cols: jnp.ndarray,  # [S, SLICE_ROWS] sliced ELL
    vals: jnp.ndarray,  # [S, SLICE_ROWS]
    blk: jnp.ndarray,  # [S // SLICE_CHUNK] row block of each chunk
    q: jnp.ndarray,  # [n]
    alpha: float = 0.5,
    gamma: float = 0.1,
    beta: float = 0.3,
    use_kernel: Optional[bool] = None,
) -> jnp.ndarray:
    """DHD update over a sliced ELL (``dhd_spmv.dhd_sliced_step``: the
    streaming warm solve's degree-sorted layout); the Pallas kernels on TPU,
    the segment-sum reference otherwise."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        out = dhd_sliced_step(heat, cols, vals, blk, q, alpha=alpha, gamma=gamma, beta=beta,
                              interpret=not on_tpu())
        _obs_dispatch("dhd_sliced", "kernel")
        return out
    out = ref.dhd_sliced_ref(heat, cols, vals, blk, q, alpha=alpha, gamma=gamma, beta=beta)
    _obs_dispatch("dhd_sliced", "ref")
    return out


def dhd_step_batch(
    heat: jnp.ndarray,  # [B, n]
    cols: jnp.ndarray,  # [n, kmax]
    vals: jnp.ndarray,  # [n, kmax] shared or [B, n, kmax] per-batch
    q: jnp.ndarray,  # [B, n]
    tail_src: Optional[jnp.ndarray] = None,
    tail_dst: Optional[jnp.ndarray] = None,
    tail_val: Optional[jnp.ndarray] = None,
    alpha: float = 0.5,
    gamma: float = 0.1,
    beta: float = 0.3,
    use_kernel: Optional[bool] = None,
    block_n: int = 256,
) -> jnp.ndarray:
    """Batched :func:`dhd_step`: B heat fields over one shared adjacency.

    Dispatch mirrors the single-seed path: batched Pallas ELL kernel when
    kernel-eligible, batched jnp reference otherwise, exact batched edge
    form when a COO tail exists (shared ``vals`` only — the tail rebuild is
    a per-adjacency operation)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    has_tail = tail_src is not None and tail_src.size > 0
    if has_tail:
        if vals.ndim == 3:
            raise ValueError("COO-tail batching requires shared [n, kmax] vals")
        n = heat.shape[1]
        a, b, w = _tail_edges(n, cols, vals, tail_src, tail_dst, tail_val)
        from ..core.dhd import dhd_step_edges_batch

        out = dhd_step_edges_batch(
            heat, a, b, w, q, n, alpha=alpha, gamma=gamma, beta=beta
        )
        _obs_dispatch("dhd_step_batch", "tail_edges")
        return out
    if use_kernel:
        out = dhd_ell_step_batch(
            heat, cols, vals, q, alpha=alpha, gamma=gamma, beta=beta,
            block_n=block_n, interpret=not on_tpu(),
        )
        _obs_dispatch("dhd_step_batch", "kernel")
        return out
    out = ref.dhd_ell_ref_batch(
        heat, cols, vals, q, alpha=alpha, gamma=gamma, beta=beta
    )
    _obs_dispatch("dhd_step_batch", "ref")
    return out


# --------------------------------------------------- batched diffusion loop
def _ell_pack_batch(
    n: int, src: np.ndarray, dst: np.ndarray, weight: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack an undirected edge list into tail-free symmetric ELL, vectorized.

    ``weight`` may be [m] (shared) or [B, m] (per-seed); the column structure
    is shared so per-seed variants differ only in ``vals``."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    uu = np.concatenate([src, dst])
    vv = np.concatenate([dst, src])
    w = np.asarray(weight, np.float32)
    wb = np.concatenate([w, w], axis=-1)  # [..., 2m]
    order = np.argsort(uu, kind="stable")
    uu, vv, wb = uu[order], vv[order], wb[..., order]
    counts = np.bincount(uu, minlength=n)
    kmax = max(int(counts.max(initial=1)), 1)
    starts = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(len(uu)) - starts[uu]
    cols = np.broadcast_to(np.arange(n, dtype=np.int32)[:, None], (n, kmax)).copy()
    cols[uu, pos] = vv.astype(np.int32)
    if w.ndim == 2:
        vals = np.zeros((w.shape[0], n, kmax), np.float32)
        vals[:, uu, pos] = wb
    else:
        vals = np.zeros((n, kmax), np.float32)
        vals[uu, pos] = wb
    return cols, vals


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_steps", "half_life"))
def _diffuse_edges_loop(
    src, dst, weight, h0, q0, *, n_nodes, n_steps, alpha, gamma, beta, half_life
):
    from ..core.dhd import dhd_step_edges_batch, source_heat

    def body(k, h):
        q = source_heat(q0, k, half_life=half_life)
        return dhd_step_edges_batch(
            h, src, dst, weight, q, n_nodes,
            alpha=alpha, gamma=gamma, beta=beta,
        )

    return jax.lax.fori_loop(0, n_steps, body, h0)


@functools.partial(
    jax.jit, static_argnames=("n_steps", "half_life", "block_n", "interpret")
)
def _diffuse_ell_loop(
    cols, vals, h0, q0, *,
    n_steps, alpha, gamma, beta, half_life, block_n, interpret
):
    from ..core.dhd import source_heat

    def body(k, h):
        q = source_heat(q0, k, half_life=half_life)
        return dhd_ell_step_batch(
            h, cols, vals, q, alpha=alpha, gamma=gamma, beta=beta,
            block_n=block_n, interpret=interpret,
        )

    return jax.lax.fori_loop(0, n_steps, body, h0)


def diffuse_batch(
    n_nodes: int,
    src: np.ndarray,
    dst: np.ndarray,
    weight: np.ndarray,  # [m] shared or [B, m] per-seed
    seeds: np.ndarray,  # [B, n]
    base_heat: Optional[np.ndarray] = None,
    params=None,
    n_steps: int = 32,
    use_kernel: Optional[bool] = None,
    block_n: int = 256,
) -> np.ndarray:
    """Backend for :func:`repro.core.dhd.diffuse_affinity_batch`.

    Runs the whole decaying-source loop on device: the batched Pallas ELL
    kernel (edge list packed tail-free once per call) when kernel-eligible,
    the vmapped edge form otherwise.  The DHD coefficients are float32
    operands of both loops, so other coefficients reuse the program."""
    from ..core.dhd import DHDParams

    p = params or DHDParams()
    coef = {k: np.float32(getattr(p, k)) for k in ("alpha", "gamma", "beta")}
    if use_kernel is None:
        use_kernel = on_tpu()
    seeds_j = jnp.asarray(seeds, jnp.float32)
    if base_heat is None:
        h0 = seeds_j
    else:
        h0 = seeds_j + jnp.asarray(np.atleast_2d(base_heat), jnp.float32)
    half_life = max(n_steps / 4.0, 1.0)
    if use_kernel:
        cols, vals = _ell_pack_batch(n_nodes, src, dst, weight)
        h = _diffuse_ell_loop(
            jnp.asarray(cols), jnp.asarray(vals), h0, seeds_j,
            n_steps=n_steps, half_life=half_life, block_n=block_n,
            interpret=not on_tpu(), **coef,
        )
        _obs_dispatch("diffuse_batch", "kernel")
    else:
        w = np.asarray(weight, np.float32)
        h = _diffuse_edges_loop(
            jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
            jnp.asarray(w), h0, seeds_j,
            n_nodes=n_nodes, n_steps=n_steps, half_life=half_life, **coef,
        )
        _obs_dispatch("diffuse_batch", "ref")
    return np.asarray(h)


# ------------------------------------------------------ fused route expansion
@functools.partial(jax.jit, static_argnames=("use_kernel", "block_r", "interpret"))
def _route_expand_packed(
    bits, sizes, lens, origin, comp, rtt, ibw, *, use_kernel, block_r, interpret
):
    """The expansion (Pallas kernel or jnp oracle) and a pack of its six
    outputs into one ``[R, K + L + D + 4]`` int32 array, so a call leaves
    one buffer to fetch.  Columns: served (K), layers_used (1), miss_after
    (L + 1), then the f32 outputs bytes_rd (D), straggler_s (1) and
    wan_bytes (1), bit-cast (exact).  ``_unpack_route`` undoes it."""
    i32 = jnp.int32
    args = (
        bits.astype(i32), sizes.astype(jnp.float32), lens.astype(i32),
        origin.astype(i32), comp.astype(i32), rtt.astype(jnp.float32),
        ibw.astype(jnp.float32),
    )
    if use_kernel:
        out = _route_expand_kernel(*args, block_r=block_r, interpret=interpret)
    else:
        out = ref.route_expand_ref(*args)
    served, bytes_rd, layers_used, miss_after, straggler, wan = out
    f32_cols = jnp.concatenate([bytes_rd, straggler[:, None], wan[:, None]], axis=1)
    return jnp.concatenate([
        served, layers_used[:, None], miss_after,
        jax.lax.bitcast_convert_type(f32_cols, i32),
    ], axis=1)


def _unpack_route(packed: np.ndarray, K: int, L: int, D: int) -> Tuple[np.ndarray, ...]:
    """Column views of a fetched ``_route_expand_packed`` output, in the
    order and dtypes of ``ref.route_expand_ref``."""
    f = packed[:, K + L + 2:].view(np.float32)
    return (
        packed[:, :K], f[:, :D], packed[:, K], packed[:, K + 1:K + L + 2],
        f[:, D], f[:, D + 1],
    )


# precomputed tag keys: the route dispatch sits inside the 5% serving
# telemetry budget, so it books a memoized keyed counter handle
_ROUTE_OBS_KEYS = {
    path: (("op", "route_expand"), ("path", path))
    for path in ("kernel", "ref", "subsets")
}


def _route_obs(path: str) -> None:
    reg = get_registry()
    if not reg.enabled:
        return
    # handle memoized per registry (dropped with the instruments by
    # MetricsRegistry.clear()): one dict get instead of a keyed lookup
    cache_key = "kernels.route:" + path
    counter = reg._handle_cache.get(cache_key)
    if counter is None:
        counter = reg.counter_keyed("kernels.dispatch", _ROUTE_OBS_KEYS[path])
        reg._handle_cache[cache_key] = counter
    counter.inc()


def route_expand_candidates(
    backend: Optional[str] = None, n_dcs: Optional[int] = None
) -> list:
    """Autotuner candidate configs for ``route_expand`` on ``backend``.

    TPU sweeps the Pallas kernel's request-block shapes against the compiled
    oracle; CPU pits the jitted oracle against the subset-histogram router
    (the interpreted kernel exists for validation, not speed).  The subset
    candidate is offered only when the DC count keeps its ``2**D`` histogram
    small (``n_dcs`` unknown counts as eligible — dispatch re-checks)."""
    backend = backend or jax.default_backend()
    cands = [{"impl": "ref"}]
    if backend == "tpu":
        cands += [{"impl": "kernel", "block_r": b} for b in (32, 64, 128, 256)]
    elif n_dcs is None or n_dcs <= SUBSET_MAX_DCS:
        cands.append({"impl": "subsets"})
    return cands


def route_expand_batch(
    bits: np.ndarray,  # [R, K] i32 per-item replica bitmask (bit d = DC d)
    sizes: np.ndarray,  # [R, K] f32 item bytes (0 where padded)
    lens: np.ndarray,  # [R] real item count per request
    origin: np.ndarray,  # [R] origin DC per request
    comp: np.ndarray,  # [hier + 1, D] layer component ids
    rtt: np.ndarray,  # [D, D] env RTT matrix
    ibw: np.ndarray,  # [D, D] elementwise 1 / bandwidth matrix
    use_kernel: Optional[bool] = None,
    block_r: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, ...]:
    """Fused stepwise layered expansion + Eq. 1 fold for a packed batch.

    Dispatch: an autotuner winner for ``(R, K, D, L)`` (see
    ``kernels.autotune``) pins impl and block shape; without one, TPU takes
    the Pallas kernel and CPU the jitted oracle — both produce the oracle's
    exact greedy picks (``ref.route_expand_ref``).  Returns numpy
    ``(served, bytes_rd, layers_used, miss_after, straggler_s, wan_bytes)``.

    One launch, one fetch: the four per-batch host arrays go up as
    arguments of the one jitted call, whose dispatch uploads them together
    (``comp``, ``rtt`` and ``ibw`` may already live on the device), and the
    program returns the six outputs packed into a single int32 buffer,
    fetched once and handed back as column views.
    """
    R, K = bits.shape
    L = comp.shape[0] - 1
    D = comp.shape[1]
    if use_kernel is None or block_r is None:
        cfg = get_autotuner().lookup("route_expand", (R, K, D, L)) or {}
        if use_kernel is None:
            impl = cfg.get("impl", "kernel" if on_tpu() else "ref")
            use_kernel = impl == "kernel"
        if block_r is None:
            block_r = int(cfg.get("block_r", 128))
    if interpret is None:
        interpret = not on_tpu()
    packed = np.asarray(_route_expand_packed(
        bits, sizes, lens, origin, comp, rtt, ibw,
        use_kernel=bool(use_kernel), block_r=int(block_r), interpret=bool(interpret),
    ))
    _route_obs("kernel" if use_kernel else "ref")
    return _unpack_route(packed, K, L, D)


# subset-histogram router: with D data centers an item's routing behaviour is
# fully determined by its replica bitmask, so a batch collapses to at most
# 2**D distinct item classes per request.  Histogramming the flat item stream
# over (request, bitmask) turns every greedy pass into [R, 2**D]-sized work —
# independent of the item count — which on CPU beats both the jitted oracle
# and the (interpreted) kernel by a wide margin for small D.
SUBSET_MAX_DCS = 8

_SUBSET_HAS_CACHE: dict = {}  # geolint: allow[GL001]


def _subset_has(n_dc: int) -> Tuple[np.ndarray, np.ndarray]:
    hit = _SUBSET_HAS_CACHE.get(n_dc)
    if hit is None:
        s = np.arange(1 << n_dc, dtype=np.int64)
        has = ((s[:, None] >> np.arange(n_dc)) & 1).astype(bool)  # [S, D]
        hit = (has, has.astype(np.float64))
        _SUBSET_HAS_CACHE.clear()
        _SUBSET_HAS_CACHE[n_dc] = hit
    return hit


def route_expand_subsets(
    bits_flat: np.ndarray,  # [K] i32/i64 per-item replica bitmask, flat stream
    req_id: np.ndarray,  # [K] request id per flat item (sorted by request)
    n_requests: int,
    origin: np.ndarray,  # [R] origin DC per request
    comp: np.ndarray,  # [hier + 1, D] layer component ids
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stepwise layered expansion over per-request replica-subset histograms.

    Runs the exact greedy of ``route_online`` (same coverage counts — an
    item contributes to a DC's coverage iff its bitmask holds that DC's bit —
    same lowest-DC-id argmax tie-break, same layer escalation) but on
    ``[R, 2**D]`` subset counts, then scatters each subset's serving DC back
    to its items with one gather.  Returns
    ``(served [K] i64, layers_used [R] i64, miss_after [R, hier + 1] i64)``;
    the byte/latency fold is left to the caller's exact host epilogue.
    """
    R = int(n_requests)
    L = comp.shape[0] - 1
    D = comp.shape[1]
    S = 1 << D
    has, has_f = _subset_has(D)
    # [R, S] item count per (request, replica subset); exact as f64 (< 2^53)
    cnt = np.bincount(
        req_id * S + bits_flat.astype(np.int64), minlength=R * S
    ).reshape(R, S).astype(np.float64)
    origin_in = has[:, origin].T  # [R, S] subset holds the origin's bit
    serve = np.where(origin_in, origin[:, None], -1)  # [R, S] per-subset DC
    missing = ~origin_in
    miss_cnt = (cnt * missing).sum(axis=1)
    miss_after = np.zeros((R, L + 1), dtype=np.int64)
    miss_after[:, 0] = miss_cnt
    ar_R = np.arange(R)
    layers_used = np.zeros(R, dtype=np.int64)
    for layer in range(1, L + 1):
        if not miss_cnt.any():
            break  # untouched miss_after columns stay 0 == fully resolved
        cl = comp[layer]
        allowed = cl[origin][:, None] == cl[None, :]  # [R, D]
        allowed[ar_R, origin] = False
        layers_used = np.where(
            (miss_cnt > 0) & allowed.any(axis=1), layer, layers_used
        )
        while True:
            cover = (cnt * missing) @ has_f  # [R, D] exact integer counts
            cover[~allowed] = 0.0
            best = cover.argmax(axis=1)  # first max == lowest DC id
            progressed = cover[ar_R, best] > 0
            if not progressed.any():
                break
            hit = missing & has[:, best].T & progressed[:, None]
            serve = np.where(hit, best[:, None], serve)
            missing &= ~hit
            miss_cnt = (cnt * missing).sum(axis=1)
        miss_after[:, layer] = miss_cnt
    served = serve[req_id, bits_flat]
    _route_obs("subsets")
    return served, layers_used, miss_after


def bag_lookup(
    table: jnp.ndarray,
    indices: jnp.ndarray,
    weights: Optional[jnp.ndarray] = None,
    mode: str = "sum",
    use_kernel: Optional[bool] = None,
    block_b: int = 128,
    block_v: int = 1024,
) -> jnp.ndarray:
    """EmbeddingBag lookup (sum/mean)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    b, _ = indices.shape
    v, _ = table.shape
    divisible = b % min(block_b, b) == 0 and v % min(block_v, v) == 0
    if use_kernel and divisible:
        return _embedding_bag_kernel(
            table, indices, weights, mode=mode,
            block_b=block_b, block_v=block_v, interpret=not on_tpu(),
        )
    return ref.embedding_bag_ref(table, indices, weights, mode=mode)
