"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
in interpret mode (``interpret=True``: the CPU runs the interpreter).  Per instructions: every kernel sweeps shapes and
dtypes and asserts allclose against ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep — deterministic fallback shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.kernels.flash_attention import (attention_ref, flash_attention,
                                           flash_attention_model_layout)
from repro.kernels.grouped_matmul import (grouped_matmul, grouped_matmul_ref,
                                          ragged_grouped_matmul,
                                          ragged_grouped_matmul_ref)
from repro.kernels.rg_lru import lru_scan, lru_scan_ref, rg_lru_pallas
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_model_layout, rmsnorm_ref
from repro.kernels.segment_fairshare import (segment_min, segment_min_ref,
                                             segment_sum, segment_sum_ref)


def tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ flash attn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,Sq,Skv,Dh", [
    (1, 4, 4, 64, 64, 64),        # MHA square
    (2, 4, 2, 100, 100, 32),      # GQA, non-multiple seq
    (1, 8, 1, 128, 128, 64),      # MQA
    (2, 4, 2, 1, 96, 64),         # decode: q len 1, right-aligned
    (1, 2, 2, 33, 77, 128),       # cross-ish ragged
])
def test_flash_attention_sweep(dtype, B, H, K, Sq, Skv, Dh):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, K, Skv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, K, Skv, Dh), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [1, 8, 64, None])
def test_flash_attention_windows(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 4, 80, 32))
    k = jax.random.normal(ks[1], (2, 2, 80, 32))
    v = jax.random.normal(ks[2], (2, 2, 80, 32))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=16, block_k=16, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bidirectional():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 48, 64))
    k = jax.random.normal(ks[1], (1, 2, 80, 64))
    v = jax.random.normal(ks[2], (1, 2, 80, 64))
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=32,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_model_layout_matches_layers():
    """Kernel == the model layer's attention math (same inputs)."""
    from repro.models import layers as L

    B, S, K, G, Dh = 2, 64, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, Dh))
    k = jax.random.normal(ks[1], (B, S, K, Dh))
    v = jax.random.normal(ks[2], (B, S, K, Dh))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    ref = L.attention_ref(q, k, v, pos, pos, causal=True)
    out = flash_attention_model_layout(q, k, v, causal=True, block_q=16,
                                       block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
@given(sq=st.integers(1, 80), skv=st.integers(1, 80),
       bq=st.sampled_from([8, 16, 32]), bk=st.sampled_from([8, 16, 32]))
@settings(max_examples=15, deadline=None)
def test_flash_attention_property_shapes(sq, skv, bq, bk):
    if sq > skv:
        sq = skv  # causal right-aligned requires Sq <= Skv
    ks = jax.random.split(jax.random.PRNGKey(sq * 81 + skv), 3)
    q = jax.random.normal(ks[0], (1, 2, sq, 32))
    k = jax.random.normal(ks[1], (1, 1, skv, 32))
    v = jax.random.normal(ks[2], (1, 1, skv, 32))
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


# -------------------------------------------------------- grouped matmul


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,M,K,N,bm", [
    (1, 32, 32, 32, 16),
    (4, 50, 40, 30, 16),      # non-multiples everywhere
    (8, 128, 64, 96, 64),
])
def test_grouped_matmul_sweep(dtype, E, M, K, N, bm):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(ks[0], (E, M, K), dtype)
    w = jax.random.normal(ks[1], (E, K, N), dtype)
    out = grouped_matmul(x, w, block_m=bm, block_n=16, block_k=16,
                         interpret=True)
    ref = grouped_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("sizes", [
    [64, 64, 64, 64],
    [128, 0, 64, 64],          # empty group
    [256, 0, 0, 0],            # all one group
    [32, 96, 64, 64],          # non-block-multiple boundaries -> masked
])
def test_ragged_grouped_matmul(sizes):
    gs = jnp.asarray(sizes)
    T = int(gs.sum())
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (T, 32))
    w = jax.random.normal(ks[1], (4, 32, 16))
    out = ragged_grouped_matmul(x, w, gs, block_m=32, block_k=16,
                                interpret=True)
    ref = ragged_grouped_matmul_ref(x, w, gs)
    # rows whose block straddles a group boundary are masked to 0 in the
    # kernel (callers pad groups to block multiples); compare only rows
    # whose block is fully owned.
    owned = np.ones(T, bool)
    start = 0
    for size in sizes:
        if start % 32 and size:
            blk0 = start - (start % 32)
            owned[blk0:start] &= False  # previous block spills into group
            owned[start:blk0 + 32] &= False
        start += size
    np.testing.assert_allclose(np.asarray(out)[owned],
                               np.asarray(ref)[owned], atol=2e-5, rtol=2e-5)


def test_ragged_block_aligned_exact():
    """With block-aligned group sizes the ragged kernel is exact."""
    gs = jnp.asarray([64, 128, 0, 64])
    T = 256
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (T, 48))
    w = jax.random.normal(ks[1], (4, 48, 24))
    out = ragged_grouped_matmul(x, w, gs, block_m=64, block_k=16,
                                interpret=True)
    ref = ragged_grouped_matmul_ref(x, w, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- rg-lru


@pytest.mark.parametrize("B,S,W,chunk,bw", [
    (1, 16, 32, 8, 16),
    (2, 75, 96, 16, 32),       # non-multiples
    (3, 128, 64, 128, 64),     # single chunk
    (1, 200, 48, 32, 48),
])
def test_lru_scan_sweep(B, S, W, chunk, bw):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    a = jax.random.uniform(ks[0], (B, S, W), minval=0.4, maxval=0.999)
    b = jax.random.normal(ks[1], (B, S, W))
    h0 = jax.random.normal(ks[2], (B, W))
    y, hl = lru_scan(a, b, h0, chunk=chunk, block_w=bw, interpret=True)
    yr, hr = lru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hr), atol=1e-5,
                               rtol=1e-5)


def test_rg_lru_pallas_matches_model_scan():
    """Full-block wrapper == the model's associative-scan implementation."""
    from repro.models.rglru import rg_lru_init, rg_lru_scan

    B, S, W = 2, 40, 64
    p = rg_lru_init(jax.random.PRNGKey(8), W)
    x = jax.random.normal(jax.random.PRNGKey(9), (B, S, W))
    h0 = jnp.zeros((B, W))
    y_ref, h_ref = rg_lru_scan(p, x, h0=h0)
    y_k, h_k = rg_lru_pallas(p, x, h0, chunk=16, block_w=32,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-4)


# --------------------------------------------------------------- rmsnorm


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,D,bn", [(7, 64, 8), (100, 256, 32),
                                    (256, 1024, 256)])
def test_rmsnorm_sweep(dtype, N, D, bn):
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    x = jax.random.normal(ks[0], (N, D), dtype)
    s = jax.random.normal(ks[1], (D,))
    out = rmsnorm(x, s, block_n=bn, interpret=True)
    ref = rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_rmsnorm_model_layout_matches_layers():
    from repro.models import layers as L

    x = jax.random.normal(jax.random.PRNGKey(11), (2, 10, 64))
    s = jax.random.normal(jax.random.PRNGKey(12), (64,))
    ref = L.rmsnorm({"scale": s}, x)
    out = rmsnorm_model_layout(x, s, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------- segment reduce


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("nnz,n_seg,block_rows,block_seg", [
    (0, 5, 8, 8),            # no entries: sums 0, mins +inf
    (1, 1, 8, 8),
    (37, 5, 8, 8),           # fewer entries than one 128-lane row
    (1000, 300, 8, 64),      # several segment and row blocks, ragged
    (5000, 1000, 16, 256),
    (3000, 9, 8, 8),         # many entries per segment
])
def test_segment_kernels_sweep(dtype, nnz, n_seg, block_rows, block_seg):
    rng = np.random.default_rng(nnz + n_seg)
    with jax.enable_x64(dtype == jnp.float64):
        vals = jnp.asarray(rng.uniform(0.1, 2.0, nnz), dtype)
        ids = jnp.asarray(rng.integers(0, n_seg, nnz), jnp.int32)
        kw = dict(interpret=True, block_rows=block_rows,
                  block_seg=block_seg)
        got_sum = segment_sum(vals, ids, n_seg, **kw)
        got_min = segment_min(vals, ids, n_seg, **kw)
        assert got_sum.dtype == dtype and got_sum.shape == (n_seg,)
        np.testing.assert_allclose(
            np.asarray(got_sum), np.asarray(segment_sum_ref(vals, ids, n_seg)),
            rtol=1e-5 if dtype == jnp.float32 else 1e-12)
        np.testing.assert_array_equal(
            np.asarray(got_min), np.asarray(segment_min_ref(vals, ids, n_seg)))
