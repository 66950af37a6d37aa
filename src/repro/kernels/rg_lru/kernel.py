"""Pallas TPU kernel for the RG-LRU linear recurrence (Griffin,
RecurrentGemma's temporal-mixing hot loop).

h_t = a_t * h_{t-1} + b_t, elementwise over the width dim.

Schedule: grid (B, W/bw, S/chunk) with the chunk axis innermost
(sequential); the running state h lives in a VMEM scratch tile (bw,) that
persists across chunk steps.  Inside a chunk we unroll a fori_loop over
time — each step is a fused multiply-add over the width tile (VPU work;
there is no MXU here, the kernel is bandwidth-bound, so the tiling goal is
purely to stream a/b through VMEM in large contiguous blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(a_ref, b_ref, h0_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        h_scr[...] = h0_ref[...][0]

    def step(t, h):
        h = a_ref[0, t] * h + b_ref[0, t]
        y_ref[0, t] = h
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h


def lru_scan(a, b, h0=None, *, chunk: int = 128, block_w: int = 512,
             interpret: bool):
    """a, b: (B, S, W) float32; h0 (B, W) -> (y (B,S,W), h_last (B,W)).

    The final state is returned by reading the last time row of y.
    """
    B, S, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    bw = min(block_w, W)
    ck = min(chunk, S)
    pw, ps = (-W) % bw, (-S) % ck
    if pw or ps:
        a = jnp.pad(a, ((0, 0), (0, ps), (0, pw)))
        # pad b with zeros and a with ones so padded steps keep state
        a = a.at[:, S:, :].set(1.0) if ps else a
        b = jnp.pad(b, ((0, 0), (0, ps), (0, pw)))
        h0 = jnp.pad(h0, ((0, 0), (0, pw)))
    gs = (S + ps) // ck
    gw = (W + pw) // bw

    y = pl.pallas_call(
        functools.partial(_lru_kernel, chunk=ck),
        grid=(B, gw, gs),
        in_specs=[
            pl.BlockSpec((1, ck, bw), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, ck, bw), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, bw), lambda bi, wi, ci: (bi, wi)),
        ],
        out_specs=pl.BlockSpec((1, ck, bw), lambda bi, wi, ci: (bi, ci, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S + ps, W + pw), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
    y = y[:, :S, :W]
    return y, y[:, -1, :]
