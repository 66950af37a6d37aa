"""jit'd wrappers for the grouped-matmul kernels (MoE expert GEMMs)."""

from __future__ import annotations

import functools

import jax

from .kernel import grouped_matmul, ragged_grouped_matmul


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_ffn_matmul(x, w, *, interpret: bool):
    """(E, C, d) x (E, d, f) -> (E, C, f); drop-in for the einsums in
    repro.models.moe._expert_ffn."""
    return grouped_matmul(x, w, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def megablocks_matmul(x, w, group_sizes, *, interpret: bool):
    """Ragged (T, K) x per-group (E, K, N) -> (T, N)."""
    return ragged_grouped_matmul(x, w, group_sizes, interpret=interpret)
