"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret mode
on CPU, shape/dtype sweeps in tests/test_kernels_*.py) and the fallback used
by ``ops.py`` when running on platforms without Pallas support.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "attention_ref",
    "dhd_ell_ref",
    "dhd_ell_ref_batch",
    "dhd_sliced_ref",
    "embedding_bag_ref",
    "route_expand_ref",
]


def attention_ref(
    q: jnp.ndarray,  # [B, Hq, Sq, D]
    k: jnp.ndarray,  # [B, Hkv, Skv, D]
    v: jnp.ndarray,  # [B, Hkv, Skv, D]
    causal: bool = True,
    window: Optional[int] = None,  # sliding-window size (local attention)
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Dense softmax attention with GQA head grouping + causal/local masks.

    With Sq < Skv (decode/chunked prefill), query position i is aligned to
    absolute position ``i + Skv - Sq`` (the suffix convention)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kr) * scale
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", probs, vr).astype(q.dtype)


def dhd_ell_ref(
    heat: jnp.ndarray,  # [n]
    cols: jnp.ndarray,  # [n, kmax] symmetric ELL neighbor ids (pad = self)
    vals: jnp.ndarray,  # [n, kmax] edge weights (0 where padded)
    q: jnp.ndarray,  # [n] source heat this step
    alpha: float = 0.5,
    gamma: float = 0.1,
    beta: float = 0.3,
) -> jnp.ndarray:
    """DHD step (Eqs. 7-8) over a symmetric ELL adjacency.

    Returns the updated heat.  Matches ``core.dhd.dhd_step_edges`` on the
    corresponding undirected edge list (each edge present in both rows).
    """
    h_nb = heat[cols]  # [n, kmax]
    h_u = heat[:, None]
    active = vals > 0
    out_mask = active & (h_u > h_nb)
    in_mask = active & (h_nb > h_u)
    # |N_u^out| — strictly-lower-heat neighbors of u
    n_out = jnp.maximum(out_mask.sum(axis=1), 1).astype(heat.dtype)
    outflow = (
        alpha / n_out[:, None] * vals * jnp.where(out_mask, h_u - h_nb, 0.0)
    ).sum(axis=1)
    # inflow from each hotter neighbor j uses |N_j^out|
    inflow = (
        alpha / n_out[cols] * vals * jnp.where(in_mask, h_nb - h_u, 0.0)
    ).sum(axis=1)
    return (1.0 - gamma) * (heat + inflow - outflow) + beta * q


def dhd_ell_ref_batch(
    heat: jnp.ndarray,  # [B, n]
    cols: jnp.ndarray,  # [n, kmax] symmetric ELL neighbor ids (shared)
    vals: jnp.ndarray,  # [n, kmax] shared or [B, n, kmax] per-seed weights
    q: jnp.ndarray,  # [B, n] source heat this step
    alpha: float = 0.5,
    gamma: float = 0.1,
    beta: float = 0.3,
) -> jnp.ndarray:
    """Batched DHD step: B independent heat fields over one shared ELL
    column structure.  ``vals`` may carry per-seed edge weights (3-D); a
    zero weight deactivates the edge for that seed only, which is how the
    placement arena runs per-candidate super-node topologies through one
    shared adjacency.  Row ``b`` equals ``dhd_ell_ref(heat[b], cols,
    vals[b], q[b])``.
    """
    h_nb = heat[:, cols]  # [B, n, kmax]
    h_u = heat[:, :, None]
    vals_b = vals if vals.ndim == 3 else vals[None]
    active = vals_b > 0
    out_mask = active & (h_u > h_nb)
    in_mask = active & (h_nb > h_u)
    n_out = jnp.maximum(out_mask.sum(axis=-1), 1).astype(heat.dtype)  # [B, n]
    outflow = (
        alpha / n_out[..., None] * vals_b * jnp.where(out_mask, h_u - h_nb, 0.0)
    ).sum(axis=-1)
    inflow = (
        alpha / n_out[:, cols] * vals_b * jnp.where(in_mask, h_nb - h_u, 0.0)
    ).sum(axis=-1)
    return (1.0 - gamma) * (heat + inflow - outflow) + beta * q


def dhd_sliced_ref(
    heat: jnp.ndarray,  # [n], n a multiple of R
    cols: jnp.ndarray,  # [S, R] sliced ELL neighbour ids (slots x rows of a block)
    vals: jnp.ndarray,  # [S, R] edge weights (0 where padded)
    blk: jnp.ndarray,  # [C] row block of each chunk of S // C slots
    q: jnp.ndarray,  # [n] source heat this step
    alpha: float = 0.5,
    gamma: float = 0.1,
    beta: float = 0.3,
) -> jnp.ndarray:
    """DHD step (Eqs. 7-8) over the sliced ELL of
    ``dhd_spmv.dhd_sliced_step``: slot ``[s, j]`` is an edge of row
    ``blk[s // chunk] * R + j``.  Per-row sums add each chunk's slots, then
    the chunks of a block (segment sums over ``blk``)."""
    s, r = cols.shape
    c = blk.shape[0]
    hb = heat.reshape(-1, r)  # [n_blocks, R]
    h_u = hb[blk][:, None, :]  # [C, 1, R]
    h_nb = heat[cols].reshape(c, s // c, r)
    w = vals.reshape(c, s // c, r)
    active = w > 0
    out_mask = active & (h_u > h_nb)
    in_mask = active & (h_nb > h_u)

    def per_row(x):  # [C, chunk, R] per slot -> [n] per row
        return jax.ops.segment_sum(x.sum(axis=1), blk, num_segments=hb.shape[0]).reshape(-1)

    n_out = jnp.maximum(per_row(out_mask.astype(heat.dtype)), 1)
    nout_u = n_out.reshape(-1, r)[blk][:, None, :]
    nout_nb = n_out[cols].reshape(c, s // c, r)
    outflow = alpha / nout_u * w * jnp.where(out_mask, h_u - h_nb, 0.0)
    inflow = alpha / nout_nb * w * jnp.where(in_mask, h_nb - h_u, 0.0)
    return (1.0 - gamma) * (heat + per_row(inflow - outflow)) + beta * q


def route_expand_masks(
    bits: jnp.ndarray,  # [R, K] i32 per-item replica bitmask over DCs
    lens: jnp.ndarray,  # [R] i32 real item count per request
    origin: jnp.ndarray,  # [R] i32 origin DC
    comp: jnp.ndarray,  # [hier + 1, D] i32 layer component ids (layer 0 first)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Derived request masks shared by the oracle and the kernel wrapper:
    ``(valid [R, K], local [R, K], missing [R, K], allowed [R, L, D])``.
    ``allowed[r, l, d]`` is True when DC ``d`` sits in the origin's layer
    ``l + 1`` cluster (the origin itself is excluded, as in the greedy)."""
    R, K = bits.shape
    D = comp.shape[1]
    valid = jnp.arange(K, dtype=jnp.int32)[None, :] < lens[:, None]
    local = valid & (((bits >> origin[:, None]) & 1) > 0)
    comp_l = comp[1:]  # [L, D]
    comp_o = jnp.transpose(comp_l[:, origin])  # [R, L]
    allowed = (comp_l[None, :, :] == comp_o[:, :, None]) & (
        jnp.arange(D, dtype=jnp.int32)[None, None, :] != origin[:, None, None]
    )
    return valid, local, valid & ~local, allowed


def route_expand_ref(
    bits: jnp.ndarray,  # [R, K] i32 per-item replica bitmask (bit d = DC d)
    sizes: jnp.ndarray,  # [R, K] f32 item bytes (0 where padded)
    lens: jnp.ndarray,  # [R] i32 real item count per request
    origin: jnp.ndarray,  # [R] i32 origin DC per request
    comp: jnp.ndarray,  # [hier + 1, D] i32 layer component ids
    rtt: jnp.ndarray,  # [D, D] f32 env RTT matrix
    ibw: jnp.ndarray,  # [D, D] f32 elementwise 1 / bandwidth matrix
) -> Tuple[jnp.ndarray, ...]:
    """Fused stepwise layered expansion (paper §VI) + Eq. 1 latency fold.

    Ground truth for the ``route_expand`` Pallas kernel and the jitted CPU
    fast path behind :func:`repro.core.routing.route_online_batch`.  Per
    request the greedy picks match :func:`repro.core.routing.route_online`
    exactly: serve locally first, then per layer repeatedly pick the
    cluster DC covering the most still-missing items (``argmax`` = lowest-
    DC-id tie-break), assign its hits, escalate when no cluster DC covers
    anything.  The batch walks the layers in lockstep behind one early-exit
    ``while_loop``: a pass with zero progress anywhere escalates the shared
    layer pointer — extra passes are idempotent per request, so lockstep
    equals per-request greedy.  Coverage counts are 0/1 sums, exact in f32
    below 2^24 items; the iteration bound L * (D + 1) covers the worst case
    (at most D - 1 productive picks plus one no-progress pass per layer).

    Returns ``(served [R, K] i32 (-1 unresolved), bytes_rd [R, D] f32,
    layers_used [R] i32, miss_after [R, L+1] i32 (missing count after each
    layer, layer 0 first), straggler_s [R] f32, wan_bytes [R] f32)``.
    """
    R, K = bits.shape
    L = comp.shape[0] - 1
    D = comp.shape[1]
    valid, local, missing, allowed = route_expand_masks(bits, lens, origin, comp)
    served = jnp.where(local, origin[:, None].astype(jnp.int32), jnp.int32(-1))
    layers_used = jnp.zeros((R,), jnp.int32)
    miss_after = jnp.zeros((R, L + 1), jnp.int32)
    miss_after = miss_after.at[:, 0].set(missing.sum(axis=1))
    max_iters = L * (D + 1)

    def cond(c):
        _, missing, layer, _, _, it = c
        return (layer < L) & missing.any() & (it < max_iters)

    # Coverage popcounts: for narrow batches (item slots <= 512) the D
    # per-DC shift-and-mask reductions collapse into ceil(D / 3) "field
    # word" reductions — bit d of each item spread into a 10-bit field
    # (3 DCs per int32 word), so one sum per word accumulates 3 exact
    # per-DC counts at once (count <= 512 < 2^10, word sum < 2^31).
    use_fields = K <= 512
    if use_fields:
        words = []
        for w in range((D + 2) // 3):
            acc = jnp.zeros_like(bits)
            for j, d in enumerate(range(w * 3, min(w * 3 + 3, D))):
                acc = acc + (((bits >> d) & 1) << (10 * j))
            words.append(acc)

    def _coverage(missing):
        if use_fields:
            cols = []
            for w, word in enumerate(words):
                s = jnp.where(missing, word, 0).sum(axis=1)  # [R]
                for j in range(min(3, D - w * 3)):
                    cols.append((s >> (10 * j)) & 1023)
            return jnp.stack(cols, axis=1).astype(jnp.float32)
        masked = jnp.where(missing, bits, 0)
        return jnp.stack(
            [((masked >> d) & 1).sum(axis=1) for d in range(D)], axis=1
        ).astype(jnp.float32)

    def body(c):
        served, missing, layer, layers_used, miss_after, it = c
        a_l = jax.lax.dynamic_index_in_dim(allowed, layer, axis=1, keepdims=False)
        layers_used = jnp.where(
            missing.any(axis=1) & a_l.any(axis=1), layer + 1, layers_used
        )
        cover = jnp.where(a_l, _coverage(missing), 0.0)
        best = jnp.argmax(cover, axis=1).astype(jnp.int32)  # lowest-id ties
        gain = jnp.max(cover, axis=1)
        has = ((bits >> best[:, None]) & 1) > 0
        hit = missing & (gain > 0)[:, None] & has
        progressed = hit.any()
        new_missing = missing & ~hit
        miss_after = jnp.where(
            progressed,
            miss_after,
            miss_after.at[:, layer + 1].set(new_missing.sum(axis=1)),
        )
        return (
            jnp.where(hit, best[:, None], served),
            new_missing,
            jnp.where(progressed, layer, layer + 1),
            layers_used,
            miss_after,
            it + 1,
        )

    served, missing, _, layers_used, miss_after, _ = jax.lax.while_loop(
        cond, body, (served, missing, jnp.int32(0), layers_used, miss_after, jnp.int32(0))
    )

    # Eq. 1 fold: per-DC served bytes -> transfer latency, straggler = max
    # over serving DCs, WAN = bytes served away from the origin
    szv = jnp.where(valid, sizes, 0.0)
    bytes_rd = jnp.stack(
        [jnp.where(served == d, szv, 0.0).sum(axis=1) for d in range(D)], axis=1
    )
    served_d = jnp.stack([(served == d).any(axis=1) for d in range(D)], axis=1)
    at_origin = (
        jnp.arange(D, dtype=jnp.int32)[None, :] == origin[:, None]
    )  # [R, D]
    rtt_ro = jnp.transpose(rtt[:, origin])
    ibw_ro = jnp.transpose(ibw[:, origin])
    lat_rd = jnp.where(at_origin, 0.0, rtt_ro + bytes_rd * ibw_ro)
    straggler = jnp.max(jnp.where(served_d, lat_rd, 0.0), axis=1)
    wan = jnp.where(at_origin, 0.0, bytes_rd).sum(axis=1)
    return served, bytes_rd, layers_used, miss_after, straggler, wan


def embedding_bag_ref(
    table: jnp.ndarray,  # [V, D]
    indices: jnp.ndarray,  # [B, L] int32
    weights: Optional[jnp.ndarray] = None,  # [B, L]
    mode: str = "sum",
) -> jnp.ndarray:
    """EmbeddingBag: per-bag weighted gather-reduce (sum or mean).

    JAX has no native ``nn.EmbeddingBag``; this take+reduce *is* the system's
    reference lookup (kernel_taxonomy §B.6)."""
    rows = table[indices]  # [B, L, D]
    if weights is None:
        weights = jnp.ones(indices.shape, dtype=table.dtype)
    out = (rows * weights[..., None]).sum(axis=1)
    if mode == "mean":
        denom = jnp.maximum(weights.sum(axis=1, keepdims=True), 1e-9)
        out = out / denom
    return out
