"""Compiles of the simulator's device programs for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what the chip's compiler would
refuse (an unaligned Pallas block, a program that does not fit).  The
shapes are the 66K-NIC ``mphx-4p-86x9`` uniform flow set at 90% load.
Nothing here runs on a device, so these say nothing about results or
times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.segment_fairshare import segment_min, segment_sum
from repro.sim.events import _event_loop_jit
from repro.sim.fairshare import _waterfill_jit

F, E, NNZ = 598_302, 71_982, 2_177_262
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back from the
        # persistent cache, so keep it out of the cache
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _shapes(one_chip, **dims):
    return {name: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for name, (shape, dtype) in dims.items()}


def _solver_shapes(one_chip):
    return _shapes(one_chip,
                   flow=((NNZ,), jnp.int64), edge=((NNZ,), jnp.int64),
                   frac=((NNZ,), jnp.float64), cap_e=((E,), jnp.float64),
                   caps=((F,), jnp.float64), tol=((), jnp.float64))


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES // 4, used


def test_waterfill_compiles_for_v5e(one_chip):
    with jax.enable_x64(True):
        s = _solver_shapes(one_chip)
        active = _shapes(one_chip, a=((F,), jnp.bool_))["a"]
        compiled = _waterfill_jit().lower(
            s["flow"], s["edge"], s["frac"], s["cap_e"], s["caps"], active,
            s["tol"], E=E, use_pallas=False).compile()
    _assert_fits(compiled)


def test_event_loop_compiles_for_v5e(one_chip):
    with jax.enable_x64(True):
        s = _solver_shapes(one_chip)
        t = _shapes(one_chip, size=((F,), jnp.float64),
                    start=((F,), jnp.float64))
        compiled = _event_loop_jit().lower(
            s["flow"], s["edge"], s["frac"], s["cap_e"], t["size"],
            s["caps"], t["start"], s["tol"], E=E, use_pallas=False,
            record=False, max_j=0).compile()
    _assert_fits(compiled)


@pytest.mark.parametrize("kernel", [segment_sum, segment_min],
                         ids=["segment_sum", "segment_min"])
def test_segment_kernel_compiles_on_mosaic(one_chip, kernel):
    s = _shapes(one_chip, vals=((NNZ,), jnp.float32),
                ids=((NNZ,), jnp.int32))
    compiled = jax.jit(lambda v, i: kernel(v, i, E, interpret=False)).lower(
        s["vals"], s["ids"]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def test_chip_smoke_refuses_the_cpu(capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out
