"""Pallas TPU fused RMSNorm kernel.

Bandwidth-bound: one pass over x per row block.  Grid (N/bn,); each step
loads a (bn, D) tile into VMEM, computes the row rms in fp32, scales, and
writes back — no HBM round-trip for the variance (what the unfused jnp
version pays).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float, d: int):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.sum(x * x, axis=-1, keepdims=True) / d
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6, block_n: int = 256,
            interpret: bool):
    """x: (N, D); scale: (D,)."""
    N, D = x.shape
    bn = min(block_n, N)
    pn = (-N) % bn
    if pn:
        x = jnp.pad(x, ((0, pn), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps, d=D),
        grid=((N + pn) // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N + pn, D), x.dtype),
        interpret=interpret,
    )(x, scale)
    return out[:N]
