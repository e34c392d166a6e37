"""Blocked online-softmax attention (FlashAttention) as a Pallas TPU kernel.

Grid: (batch, q_heads, q_blocks, kv_blocks) — kv innermost ("arbitrary"
semantics) so the running-softmax scratch (m, l, acc) carries across kv
iterations and the output is finalized at the last kv block.

BlockSpec tiling keeps one (block_q x d) Q tile and one (block_kv x d) K/V
tile in VMEM; the S = Q K^T tile (block_q x block_kv) is MXU-shaped
(multiples of 128 recommended).  Supports causal masking, sliding-window
(local) masking and GQA via an index_map that folds q-head -> kv-head.

Decode (Sq=1..8 with large Skv) runs the same kernel with block_q = Sq.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _attn_kernel(
    q_ref,  # [1, 1, bq, d]
    k_ref,  # [1, 1, bkv, d]
    v_ref,  # [1, 1, bkv, d]
    o_ref,  # [1, 1, bq, d]
    m_scr,  # [bq, 1] running max
    l_scr,  # [bq, 1] running denom
    acc_scr,  # [bq, d] running numerator
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_kv: int,
    seq_q: int,
    seq_kv: int,
):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)  # [bkv, d]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bkv]

    # absolute positions; suffix-aligned when seq_q < seq_kv (decode)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    q_pos = q_pos + (seq_kv - seq_q)
    k_pos = ikv * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1
    )
    mask = jnp.ones((block_q, block_kv), dtype=jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]  # [bq, 1]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # [bq, bkv]
    correction = jnp.exp(m_prev - m_new)  # [bq, 1]
    l_new = l_prev * correction + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_scr[...] = acc_scr[...] * correction + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, Hq, Sq, D]
    k: jnp.ndarray,  # [B, Hkv, Skv, D]
    v: jnp.ndarray,  # [B, Hkv, Skv, D]
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, "GQA requires Hq % Hkv == 0"
    group = hq // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0
    scale = d ** -0.5
    grid = (b, hq, sq // block_q, skv // block_kv)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        seq_q=sq,
        seq_kv=skv,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ikv: (b_, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda b_, h, iq, ikv: (b_, h // group, ikv, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda b_, h, iq, ikv: (b_, h // group, ikv, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h, iq, ikv: (b_, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),  # running numerator acc
        ],
        interpret=interpret,
    )(q, k, v)
