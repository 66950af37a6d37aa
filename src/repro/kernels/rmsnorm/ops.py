"""jit'd wrapper for the fused RMSNorm kernel (model layout (B,S,d))."""

from __future__ import annotations

import functools

import jax

from .kernel import rmsnorm


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm_model_layout(x, scale, *, eps: float = 1e-6,
                         interpret: bool):
    B, S, d = x.shape
    return rmsnorm(x.reshape(B * S, d), scale, eps=eps,
                   interpret=interpret).reshape(B, S, d)
