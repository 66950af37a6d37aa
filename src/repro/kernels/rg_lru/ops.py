"""jit'd wrapper: full RG-LRU block (gates computed in jnp, scan in Pallas)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.rglru import _rg_lru_coeffs
from .kernel import lru_scan


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rg_lru_pallas(params, x, h0=None, *, chunk: int = 128,
                  block_w: int = 512, interpret: bool):
    """Drop-in replacement for repro.models.rglru.rg_lru_scan (fwd only)."""
    a, bcoef, _ = _rg_lru_coeffs(params, x)
    return lru_scan(a, bcoef, h0, chunk=chunk, block_w=block_w,
                    interpret=interpret)
