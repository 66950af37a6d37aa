"""Pallas TPU flash-attention kernel (blockwise online softmax).

Grid: (B, H, n_q_blocks, n_kv_blocks) — the kv axis is the innermost
(sequential) dimension; running max / denominator / accumulator live in
VMEM scratch and persist across kv steps (the canonical TPU flash
schedule).  BlockSpecs tile q/k/v/o into VMEM with MXU-aligned
(block, head_dim) tiles; GQA is expressed in the k/v index_map
(query head h reads kv head h // G), so no repeated KV is ever
materialized.

Supports causal and sliding-window masks.  Fully-masked kv blocks are
skipped via ``pl.when`` (their compute is predicated off — on TPU this
saves the MXU issue; in interpret mode it just skips the branch).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int | None,
                 block_q: int, block_k: int, sq: int, skv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions (queries right-aligned to the kv tail: decode-safe)
    q_off = skv - sq
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_off
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # block-level skip test (static per (qi, ki) under causal/window)
    blk_q_min = qi * block_q + q_off
    blk_q_max = blk_q_min + block_q - 1
    blk_k_min = ki * block_k
    run = True
    if causal:
        run = blk_k_min <= blk_q_max
    if window is not None:
        run = jnp.logical_and(run,
                              (blk_q_min - (blk_k_min + block_k - 1))
                              < window)

    @pl.when(run if not isinstance(run, bool) else True)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, (q_pos - k_pos) < window)
        # out-of-range padding rows/cols
        mask = jnp.logical_and(mask, k_pos < skv)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jnp.ndarray:
    """q: (B, H, Sq, Dh); k, v: (B, K, Skv, Dh); GQA via H % K == 0."""
    B, H, Sq, Dh = q.shape
    K, Skv = k.shape[1], k.shape[2]
    if H % K:
        raise ValueError("H must be a multiple of K")
    G = H // K
    scale = 1.0 / math.sqrt(Dh)

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Skv, 8))
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = (Sq + pad_q) // block_q
    n_k = (Skv + pad_k) // block_k

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, sq=Sq, skv=Skv)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, Dh), q.dtype),
        scratch_shapes=[
            # (bq,) running max / denom, (bq, Dh) accumulator — fp32 VMEM
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
