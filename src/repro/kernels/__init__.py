"""Pallas TPU kernels for the perf-critical compute layers.

Each subpackage: kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd wrapper), ref.py (pure-jnp oracle).  ``interpret`` has no
default anywhere: callers pass ``True`` on the CPU (the Pallas
interpreter, as tests/test_kernels.py does) and ``False`` on a TPU, so no
path interprets on a chip by accident.  Only ``segment_fairshare`` is
compiled for a TPU by the tests (tests/test_tpu_compile.py).  The dry-run / cost-analysis paths use the jnp reference
implementations so HLO FLOP counts stay visible (DESIGN.md §6).
"""
