"""Multi-plane collectives in JAX — the compute-side realization of the
paper's plane spraying (DESIGN.md §3.1).

An MPHX NIC sprays each flow across n independent planes.  XLA collectives
are ordered, so the JAX-native analogue is *chunk decomposition*: split the
tensor into n chunks and issue n independent collectives the scheduler can
overlap (``multiplane_psum``), and *dimension decomposition*: express one
big all-reduce as reduce-scatter -> (recurse) -> all-gather across distinct
mesh axes (``hierarchical_psum``) the way an MPHX hierarchical all-reduce
walks the HyperX dimensions (netsim.hierarchical_allreduce_time).

Everything here runs inside ``shard_map``.  Each function has the same
semantics as a single ``lax.psum`` over the named axes — property-tested
against that oracle in tests/test_collectives.py.

``int8_psum`` is the wire-level compressed all-reduce (cross-pod/DCN axis):
quantize-per-chunk -> integer psum -> dequantize, with the scale reduced by
max.  Error feedback for it lives in train/trainer.py.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax


def plane_chunk_count(size: int, n_planes: int) -> int:
    """Number of per-plane chunks a sprayed collective splits into: the
    largest ``n <= n_planes`` dividing ``size`` evenly, or 1 (no split).
    Shared by :func:`multiplane_psum` / :func:`multiplane_all_gather` and by
    :mod:`repro.experiments.scenarios` to size collective chunk schedules."""
    n = min(n_planes, size)
    if size % n:
        return 1
    return n


def multiplane_psum(x, axis_name: str, n_planes: int = 8, split_axis: int = 0):
    """All-reduce as ``n_planes`` independent chunk all-reduces.

    Same result as ``lax.psum(x, axis_name)``; the chunks model the NIC
    spraying a flow over n planes (each chunk rides one plane).  On TPU the
    chunks pipeline through the ICI links and overlap with surrounding
    compute; XLA may also fuse them back together — the decomposition is a
    scheduling hint, not a semantic change.
    """
    n = plane_chunk_count(x.shape[split_axis], n_planes)
    if n == 1:
        return lax.psum(x, axis_name)
    chunks = jnp.split(x, n, axis=split_axis)
    return jnp.concatenate([lax.psum(c, axis_name) for c in chunks],
                           axis=split_axis)


def decomposed_psum(x, axis_name: str, split_axis: int = 0):
    """All-reduce as reduce-scatter + all-gather over the SAME axis.

    Equivalent bytes to a ring all-reduce but exposes the two phases to the
    scheduler separately (overlap the all-gather with downstream compute).
    Requires ``x.shape[split_axis]`` divisible by the axis size.
    """
    n = lax.axis_size(axis_name)
    if x.shape[split_axis] % n:
        return lax.psum(x, axis_name)
    scattered = lax.psum_scatter(x, axis_name, scatter_dimension=split_axis,
                                 tiled=True)
    return lax.all_gather(scattered, axis_name, axis=split_axis, tiled=True)


def hierarchical_psum(x, axis_names: Sequence[str], split_axis: int = 0):
    """All-reduce over multiple mesh axes as the MPHX dimension walk:
    reduce-scatter along axis 0, recurse over the remaining axes on the
    shard, then all-gather along axis 0.  Traffic per step matches the
    hierarchical schedule in core/netsim.hierarchical_allreduce_time."""
    axis_names = list(axis_names)
    if len(axis_names) == 0:
        return x
    if len(axis_names) == 1:
        return decomposed_psum(x, axis_names[0], split_axis)
    a0 = axis_names[0]
    n = lax.axis_size(a0)
    if x.shape[split_axis] % n:
        # fall back: reduce this axis whole, recurse on the rest
        return hierarchical_psum(lax.psum(x, a0), axis_names[1:], split_axis)
    scattered = lax.psum_scatter(x, a0, scatter_dimension=split_axis,
                                 tiled=True)
    reduced = hierarchical_psum(scattered, axis_names[1:], split_axis)
    return lax.all_gather(reduced, a0, axis=split_axis, tiled=True)


def multiplane_all_gather(x, axis_name: str, n_planes: int = 8,
                          gather_axis: int = 0, chunk_axis: int = -1):
    """All-gather with the payload chunk-split over planes."""
    ca = chunk_axis % x.ndim
    n = plane_chunk_count(x.shape[ca], n_planes)
    if n == 1:
        return lax.all_gather(x, axis_name, axis=gather_axis, tiled=True)
    chunks = jnp.split(x, n, axis=ca)
    outs = [lax.all_gather(c, axis_name, axis=gather_axis, tiled=True)
            for c in chunks]
    return jnp.concatenate(outs, axis=ca)


def int8_psum(x, axis_name: str):
    """Compressed all-reduce: int8 quantized payload + shared max-scale.

    Wire bytes: 1/4 of fp32 (plus one scalar).  Biased per call (quantization
    error does not cancel); pair with error feedback across steps
    (train/trainer.compress_grads_ef) for convergence.
    """
    amax = lax.pmax(jnp.max(jnp.abs(x)), axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    # accumulate in int32 (axis size < 2^24 safe)
    total = lax.psum(q.astype(jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale


def latency_optimal_psum(x, axis_name: str):
    """Small-payload all-reduce: a single psum (alpha-bound); provided so
    callers can dispatch on payload size like the netsim algo picker."""
    return lax.psum(x, axis_name)


def psum_auto(x, axis_name: str, n_planes: int = 8,
              small_cutoff_bytes: int = 1 << 14):
    """Dispatch between latency-optimal and plane-decomposed all-reduce by
    payload size (mirrors netsim.allreduce_time's algo choice)."""
    nbytes = x.size * x.dtype.itemsize
    if nbytes <= small_cutoff_bytes:
        return latency_optimal_psum(x, axis_name)
    return multiplane_psum(x, axis_name, n_planes)
