"""Pallas segment-reduce kernels for the COO flow-link incidence tensor.

The max-min water-filling solver (:mod:`repro.sim.fairshare`) spends its
rounds in two sparse reductions over the coalesced COO incidence arrays:

* ``segment_sum``  — per-edge live weight ``sum_f frac[f,e]`` (and the
  per-flow saturated-fraction sum on the freeze step);
* ``segment_min``  — per-flow bottleneck ``min_e cap[e]/frac[f,e]``.

Both are scatter reductions with data-dependent indices, which TPUs hate
in their natural form.  The kernels below recast them as **masked
compares on (8, 128) tiles**: the (NNZ,) entries are padded and laid out
as ``(rows, 128)`` lanes, the grid tiles (segment blocks x row blocks),
and each step sweeps its rows, comparing a row's 128 segment ids against
a ``(block_seg, 128)`` tile whose sublanes are this block's segments.
Matching entries fold into a lane-partial ``(block_seg, 128)`` output
tile that stays resident in VMEM across the row-block axis (the entry
axis is innermost, so each tile is initialized once at row-block 0); one
XLA reduction over the 128 lanes finishes the job.

Entries are padded with an out-of-range segment id, so padding never hits
a real bin.  ``interpret`` has no default: callers pick it from the
platform (``True`` on the CPU, where the Pallas interpreter runs the same
kernel at float64; ``False`` on a TPU, where Mosaic compiles it — float32
only, Mosaic has no float64).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _segment_kernel(ids_ref, val_ref, o_ref, *, combine, fill):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, fill, o_ref.dtype)

    block_seg = o_ref.shape[0]
    seg = (pl.program_id(0) * block_seg
           + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0))

    def row(r, acc):
        ids = ids_ref[pl.ds(r, 1), :]
        vals = val_ref[pl.ds(r, 1), :]
        return combine(acc, jnp.where(ids == seg, vals, fill))

    o_ref[...] = jax.lax.fori_loop(0, ids_ref.shape[0], row, o_ref[...])


def _pad_coo(values, segment_ids, num_segments: int, block_rows: int):
    """``(rows, 128)`` values and int32 ids, rows a multiple of the row
    block (itself a multiple of 8), padded with id ``num_segments``."""
    rows = _round_up(max(values.shape[0], 1), LANES) // LANES
    br = min(block_rows, _round_up(rows, 8))
    pad = _round_up(rows, br) * LANES - values.shape[0]
    values = jnp.pad(values, (0, pad)).reshape(-1, LANES)
    # out-of-range id: the padded entries miss every real segment
    ids = jnp.pad(segment_ids.astype(jnp.int32), (0, pad),
                  constant_values=num_segments).reshape(-1, LANES)
    return values, ids, br


def _segment_call(combine, fill, values, segment_ids, num_segments: int,
                  block_rows: int, block_seg: int, interpret: bool):
    values, ids, br = _pad_coo(values, segment_ids, num_segments,
                               block_rows)
    bs = min(block_seg, _round_up(num_segments, 8))
    n_seg_blocks = _round_up(num_segments, bs) // bs
    out = pl.pallas_call(
        functools.partial(_segment_kernel, combine=combine, fill=fill),
        grid=(n_seg_blocks, ids.shape[0] // br),
        in_specs=[
            pl.BlockSpec((br, LANES), lambda s, i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda s, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bs, LANES), lambda s, i: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((n_seg_blocks * bs, LANES),
                                       values.dtype),
        interpret=interpret,
    )(ids, values)
    return out[:num_segments]


def segment_sum(values, segment_ids, num_segments: int, *, interpret: bool,
                block_rows: int = 512, block_seg: int = 256):
    """Scatter-add ``values`` (NNZ,) into ``num_segments`` bins."""
    if num_segments == 0:
        return jnp.zeros((0,), dtype=values.dtype)
    return _segment_call(jnp.add, 0.0, values, segment_ids, num_segments,
                         block_rows, block_seg, interpret).sum(axis=1)


def segment_min(values, segment_ids, num_segments: int, *, interpret: bool,
                block_rows: int = 512, block_seg: int = 256):
    """Per-segment min of ``values`` (NNZ,); empty segments hold +inf."""
    if num_segments == 0:
        return jnp.zeros((0,), dtype=values.dtype)
    return _segment_call(jnp.minimum, jnp.inf, values, segment_ids,
                         num_segments, block_rows, block_seg,
                         interpret).min(axis=1)
