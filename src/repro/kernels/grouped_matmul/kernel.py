"""Pallas TPU grouped matmul (MoE expert GEMMs).

Two variants:

* :func:`grouped_matmul` — dense-batched (E, M, K) x (E, K, N): grid
  (E, M/bm, N/bn, K/bk) with an fp32 VMEM accumulator tile; the K axis is
  innermost/sequential, M/N parallel.  This is the compute core of
  ``repro.models.moe._expert_ffn`` (capacity-padded buffers).
* :func:`ragged_grouped_matmul` — MegaBlocks-style: rows of x (T, K) sorted
  by expert with ``group_sizes`` (E,); each (row-block, expert) pair is
  mapped through a precomputed block->group table (scalar-prefetch
  analogue, computed on host side of the call); rows outside their group's
  range are masked.  Avoids compute on capacity padding entirely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc, *, n_k: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def grouped_matmul(x, w, *, block_m: int = 128, block_n: int = 128,
                   block_k: int = 128, interpret: bool):
    """x: (E, M, K); w: (E, K, N) -> (E, M, N)."""
    E, M, K = x.shape
    _, _, N = w.shape
    bm, bn, bk = (min(block_m, M), min(block_n, N), min(block_k, K))
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, 0), (0, pk), (0, pn)))
    gm, gn, gk = (M + pm) // bm, (N + pn) // bn, (K + pk) // bk

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=gk),
        grid=(E, gm, gn, gk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, mi, ni, ki: (e, mi, ki)),
            pl.BlockSpec((1, bk, bn), lambda e, mi, ni, ki: (e, ki, ni)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, mi, ni, ki: (e, mi, ni)),
        out_shape=jax.ShapeDtypeStruct((E, M + pm, N + pn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out[:, :M, :N]


def _ragged_kernel(gid_ref, start_ref, size_ref, x_ref, w_ref, o_ref, acc,
                   *, block_m: int, n_k: int):
    mi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        # mask rows that belong to a different group than this block's owner
        row = mi * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        g0 = start_ref[mi]
        g1 = g0 + size_ref[mi]
        ok = jnp.logical_and(row >= g0, row < g1)
        o_ref[...] = jnp.where(ok, acc[...], 0.0).astype(o_ref.dtype)


def ragged_grouped_matmul(x, w, group_sizes, *, block_m: int = 128,
                          block_k: int = 128, interpret: bool):
    """x: (T, K) rows sorted by group; w: (E, K, N); group_sizes: (E,).

    The block->group table is a scalar-prefetch operand: the w BlockSpec's
    index_map reads ``gid[mi]`` so each row block streams exactly its own
    expert's weights — no compute on other experts, no gather of w.
    Each row block is owned by the group of its FIRST row; foreign rows in
    the block are masked.  Callers that pad every group to a multiple of
    ``block_m`` (as the MoE capacity buffers do) get exact ownership.
    """
    T, K = x.shape
    E, _, N = w.shape
    bm = min(block_m, T)
    bk = min(block_k, K)
    pm, pk = (-T) % bm, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk:
        w = jnp.pad(w, ((0, 0), (0, pk), (0, 0)))
    gm, gk = (T + pm) // bm, (K + pk) // bk

    # host-side block->group table (scalar prefetch)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    block_first_row = jnp.arange(gm) * bm
    gid = jnp.sum(block_first_row[:, None] >= ends[None, :],
                  axis=1).astype(jnp.int32)              # (gm,)
    gid = jnp.minimum(gid, E - 1)
    blk_start = starts[gid].astype(jnp.int32)
    blk_size = group_sizes[gid].astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(gm, gk),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda mi, ki, gid, start, size: (mi, ki)),
            pl.BlockSpec((1, bk, N),
                         lambda mi, ki, gid, start, size: (gid[mi], ki, 0)),
        ],
        out_specs=pl.BlockSpec((bm, N),
                               lambda mi, ki, gid, start, size: (mi, 0)),
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, block_m=bm, n_k=gk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + pm, N), x.dtype),
        interpret=interpret,
    )(gid, blk_start, blk_size, x, w)
    return out[:T]
