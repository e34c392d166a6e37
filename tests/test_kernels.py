"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dhd import dhd_step_edges
from repro.core.graph import build_csr, build_ell
from repro.kernels import ops, ref
from repro.kernels.dhd_spmv import dhd_ell_step, dhd_ell_step_batch, dhd_sliced_step
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention
from repro.streaming.delta_dhd import _cut_slabs, _k_bucket, _slab_index

ATTN_SWEEP = [
    # b, hq, hkv, sq, skv, d, causal, window, dtype
    (2, 4, 2, 128, 128, 64, True, None, jnp.float32),
    (1, 8, 8, 256, 256, 32, False, None, jnp.float32),
    (1, 4, 1, 128, 512, 64, True, 64, jnp.float32),
    (2, 4, 2, 8, 256, 64, True, None, jnp.float32),
    (1, 2, 2, 64, 64, 128, True, None, jnp.bfloat16),
]


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window,dtype", ATTN_SWEEP)
def test_flash_attention_matches_ref(b, hq, hkv, sq, skv, d, causal, window, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_kv=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("n,kmax,block_n", [(256, 8, 64), (512, 16, 128), (128, 4, 32)])
def test_dhd_kernel_matches_edge_oracle(n, kmax, block_n):
    rng = np.random.default_rng(1)
    m = n * kmax // 4
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    a, b = np.minimum(src, dst)[keep], np.maximum(src, dst)[keep]
    _, i = np.unique(a.astype(np.int64) * n + b, return_index=True)
    a, b = a[i], b[i]
    w = (rng.random(len(a)) + 0.1).astype(np.float32)
    csr = build_csr(n, a, b, weights=w, symmetrize=True)
    ell = build_ell(csr, max_degree=int(csr.degree().max()))
    assert len(ell.tail_src) == 0
    heat = jnp.asarray(rng.random(n), jnp.float32)
    q = jnp.asarray(rng.random(n) * 0.1, jnp.float32)
    out = dhd_ell_step(heat, jnp.asarray(ell.cols), jnp.asarray(ell.vals), q,
                       block_n=block_n, interpret=True)
    want = dhd_step_edges(heat, jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
                          jnp.asarray(w), q, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-4)


def test_dhd_kernel_pads_arbitrary_n():
    """Non-block-multiple row counts take the kernel path via internal
    self-loop padding instead of crashing (satellite of the batched engine)."""
    rng = np.random.default_rng(5)
    n = 37  # not a multiple of any block size
    src, dst = rng.integers(0, n, 120), rng.integers(0, n, 120)
    keep = src != dst
    a, b = np.minimum(src, dst)[keep], np.maximum(src, dst)[keep]
    _, i = np.unique(a.astype(np.int64) * n + b, return_index=True)
    a, b = a[i], b[i]
    w = (rng.random(len(a)) + 0.1).astype(np.float32)
    csr = build_csr(n, a, b, weights=w, symmetrize=True)
    ell = build_ell(csr, max_degree=int(csr.degree().max()))
    heat = jnp.asarray(rng.random(n), jnp.float32)
    q = jnp.asarray(rng.random(n) * 0.1, jnp.float32)
    out = dhd_ell_step(heat, jnp.asarray(ell.cols), jnp.asarray(ell.vals), q,
                       block_n=16, interpret=True)
    want = ref.dhd_ell_ref(heat, jnp.asarray(ell.cols), jnp.asarray(ell.vals), q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("n,kmax,block_n,B,batched_vals", [
    (64, 8, 32, 4, False),
    (57, 6, 16, 3, True),   # padding path + per-batch weights
    (128, 4, 64, 2, True),
])
def test_dhd_kernel_batch_matches_ref(n, kmax, block_n, B, batched_vals):
    rng = np.random.default_rng(6)
    m = n * kmax // 4
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = src != dst
    a, b = np.minimum(src, dst)[keep], np.maximum(src, dst)[keep]
    _, i = np.unique(a.astype(np.int64) * n + b, return_index=True)
    a, b = a[i], b[i]
    w = (rng.random(len(a)) + 0.1).astype(np.float32)
    csr = build_csr(n, a, b, weights=w, symmetrize=True)
    ell = build_ell(csr, max_degree=int(csr.degree().max()))
    heat = jnp.asarray(rng.random((B, n)), jnp.float32)
    q = jnp.asarray(rng.random((B, n)) * 0.1, jnp.float32)
    if batched_vals:
        vals = np.repeat(ell.vals[None], B, axis=0)
        vals *= (rng.random(vals.shape) > 0.2)  # drop edges per batch element
        vals = jnp.asarray(vals)
    else:
        vals = jnp.asarray(ell.vals)
    cols = jnp.asarray(ell.cols)
    out = dhd_ell_step_batch(heat, cols, vals, q, block_n=block_n, interpret=True)
    want = ref.dhd_ell_ref_batch(heat, cols, vals, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-4)
    # row b of the batch == the single-seed kernel on (heat[b], vals[b])
    for k in range(B):
        vk = vals[k] if batched_vals else vals
        single = dhd_ell_step(heat[k], cols, vk, q[k], block_n=block_n, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(single), atol=1e-5, rtol=1e-4
        )


def _ell_with_degrees(rng, deg, kmax):
    """A dense ELL whose row ``u`` holds ``deg[u]`` random edges (pad = self)."""
    n = len(deg)
    cols = np.repeat(np.arange(n, dtype=np.int32)[:, None], kmax, axis=1)
    vals = np.zeros((n, kmax), np.float32)
    for u, d in enumerate(deg):
        cols[u, :d] = rng.integers(0, n, d)
        vals[u, :d] = rng.random(d) + 0.05
    return cols, vals


@pytest.mark.parametrize("n_real,top,headroom,extra_rows", [
    (600, 23, 8, 0),  # pad rows fill the last block's tail
    (512, 23, 8, 8),  # the last block holds only pad rows: 8 slots wide
    (768, 24, 0, 24),  # the widest row exactly fills its block; capacity past the slabs
])
def test_dhd_sliced_kernel_matches_dense(n_real, top, headroom, extra_rows):
    """The sliced kernels (interpreted) give the dense kernels' and both
    references' step over the same rows, within 1e-6 relative."""
    rng = np.random.default_rng(7)
    n, kmax = 768, 32
    deg = np.zeros(n, np.int64)
    deg[:n_real] = np.sort(rng.integers(1, top + 1, n_real))[::-1]
    deg[0] = top
    widths = np.array([_k_bucket(d + headroom) for d in deg.reshape(-1, 256).max(axis=1)])
    assert (widths >= deg.reshape(-1, 256).max(axis=1)).all()
    if n_real == 512:
        assert widths[-1] == 8
    if headroom == 0:
        assert deg[0] == widths[0]
    cols, vals = _ell_with_degrees(rng, deg, kmax)
    src, blk = _slab_index(widths, kmax, int(widths.sum()) + extra_rows)
    s_cols, s_vals = _cut_slabs(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(src))
    heat = jnp.asarray(rng.random(n), jnp.float32)
    q = jnp.asarray(rng.random(n) * 0.1, jnp.float32)
    out = np.asarray(dhd_sliced_step(heat, s_cols, s_vals, blk, q, alpha=np.float32(0.05),
                                     interpret=True))
    wants = [
        dhd_ell_step(heat, cols, vals, q, alpha=np.float32(0.05), interpret=True),
        ref.dhd_ell_ref(heat, jnp.asarray(cols), jnp.asarray(vals), q, alpha=0.05),
        ref.dhd_sliced_ref(heat, s_cols, s_vals, jnp.asarray(blk), q, alpha=0.05),
    ]
    for want in map(np.asarray, wants):
        assert np.abs(out - want).max() <= 1e-6 * np.abs(want).max()


def test_dhd_tail_edge_cache_reused():
    """Repeated dhd_step calls with the same adjacency arrays must hit the
    deduped-edge cache instead of rebuilding the edge list host-side.

    Hit/miss counts live in the metrics registry now (no module-global
    leaking across runs), so the test enables a throwaway registry."""
    from repro.obs import MetricsRegistry, set_default_registry

    rng = np.random.default_rng(8)
    n = 48
    a = rng.integers(0, n, 140)
    b = (a + 1 + rng.integers(0, n - 1, 140)) % n
    w = (rng.random(140) + 0.1).astype(np.float32)
    csr = build_csr(n, a, b, weights=w, symmetrize=True)
    ell = build_ell(csr, max_degree=2)  # forces a tail
    assert len(ell.tail_src) > 0
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    ts, td, tv = (jnp.asarray(ell.tail_src), jnp.asarray(ell.tail_dst),
                  jnp.asarray(ell.tail_val))
    heat = jnp.asarray(rng.random(n), jnp.float32)
    q = jnp.asarray(rng.random(n) * 0.1, jnp.float32)
    old = set_default_registry(MetricsRegistry(enabled=True))
    try:
        r1 = ops.dhd_step(heat, cols, vals, q, ts, td, tv)
        hits0 = ops.edge_cache_stats()["hits"]
        r2 = ops.dhd_step(heat, cols, vals, q, ts, td, tv)
        rb = ops.dhd_step_batch(heat[None], cols, vals, q[None], ts, td, tv)
        stats = ops.edge_cache_stats()
        assert stats["hits"] >= hits0 + 2
        assert 0.0 < stats["hit_rate"] <= 1.0
    finally:
        reg = set_default_registry(old)
    # registry reset clears the counts (the old module-global never did)
    reg.reset()
    assert set_default_registry(reg) is old  # install to read, then restore
    assert ops.edge_cache_stats() == {"hits": 0, "misses": 0, "hit_rate": 0.0}
    set_default_registry(old)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=0)
    np.testing.assert_allclose(np.asarray(rb[0]), np.asarray(r1), atol=1e-6)


def test_dhd_tail_path_exact(small_setup):
    rng = np.random.default_rng(3)
    n, m = 64, 300
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = src != dst
    a, b = np.minimum(src, dst)[keep], np.maximum(src, dst)[keep]
    _, i = np.unique(a.astype(np.int64) * n + b, return_index=True)
    a, b = a[i], b[i]
    w = (rng.random(len(a)) + 0.1).astype(np.float32)
    csr = build_csr(n, a, b, weights=w, symmetrize=True)
    ell = build_ell(csr, max_degree=4)  # forces a big tail
    assert len(ell.tail_src) > 0
    heat = jnp.asarray(rng.random(n), jnp.float32)
    q = jnp.asarray(rng.random(n) * 0.1, jnp.float32)
    out = ops.dhd_step(heat, jnp.asarray(ell.cols), jnp.asarray(ell.vals), q,
                       jnp.asarray(ell.tail_src), jnp.asarray(ell.tail_dst),
                       jnp.asarray(ell.tail_val))
    want = dhd_step_edges(heat, jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
                          jnp.asarray(w), q, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


BAG_SWEEP = [
    (2048, 32, 256, 20, "sum", jnp.float32),
    (4096, 64, 128, 8, "mean", jnp.float32),
    (1024, 16, 64, 5, "sum", jnp.float32),
    (512, 8, 32, 3, "sum", jnp.bfloat16),
]


@pytest.mark.parametrize("V,D,B,L,mode,dtype", BAG_SWEEP)
def test_embedding_bag_matches_ref(V, D, B, L, mode, dtype):
    rng = np.random.default_rng(2)
    tab = jnp.asarray(rng.standard_normal((V, D)), dtype)
    idx = jnp.asarray(rng.integers(0, V, (B, L)), jnp.int32)
    w = jnp.asarray(rng.random((B, L)), dtype)
    out = embedding_bag(tab, idx, w, mode=mode, block_b=32, block_v=256, interpret=True)
    want = ref.embedding_bag_ref(tab, idx, w, mode=mode)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_chunked_attention_matches_ref():
    from repro.models.attention import chunked_attention

    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, 4, 256, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 32)), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, chunk_kv=64, chunk_q=128)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_kernel_attention_trainable():
    """The Pallas kernel path is differentiable (custom VJP, ref backward)."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 4, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    f_kern = lambda q_: ops.attention(
        q_, k, v, causal=True, use_kernel=True, block_q=64, block_kv=64
    ).sum()
    f_ref = lambda q_: ref.attention_ref(q_, k, v, causal=True).sum()
    g1 = jax.grad(f_kern)(q)
    g2 = jax.grad(f_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)
