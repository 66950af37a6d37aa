"""jit'd public wrapper for the flash-attention kernel.

Accepts the model-layout tensors q (B, S, K, G, Dh), k/v (B, S, K, Dh)
(as produced by ``repro.models.layers.mha_project_qkv``) and handles the
transpose to kernel layout, dtype preservation, and block-size selection.
``interpret`` has no default: ``True`` on the CPU (the Pallas
interpreter), ``False`` on a TPU (Mosaic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_model_layout(q, k, v, *, causal: bool = True,
                                 window: int | None = None,
                                 block_q: int = 128, block_k: int = 128,
                                 interpret: bool):
    """q: (B, S, K, G, Dh); k, v: (B, S, K, Dh) -> (B, S, K, G, Dh)."""
    B, S, K, G, Dh = q.shape
    qk = q.transpose(0, 2, 3, 1, 4).reshape(B, K * G, S, Dh)
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    o = flash_attention(qk, kk, vv, causal=causal, window=window,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return o.reshape(B, K, G, S, Dh).transpose(0, 3, 1, 2, 4)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_kernel_layout(q, k, v, *, causal: bool = True,
                                  window: int | None = None,
                                  block_q: int = 128, block_k: int = 128,
                                  interpret: bool):
    """q: (B, H, Sq, Dh); k, v: (B, K, Skv, Dh)."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
