"""Multi-plane collectives demo: runs the plane-decomposed / hierarchical /
compressed all-reduces on 8 forced host devices and compares against the
single-psum oracle; then models the same collectives on the paper's
topologies with the flow-level simulator.

Run:  PYTHONPATH=src python examples/multiplane_demo.py
(re-execs itself pinned to the CPU platform with 8 host devices; the
demo exists only on host devices and never asks for an accelerator)
"""

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

if os.environ.get("_MPHX_DEMO_CHILD") != "1":
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["_MPHX_DEMO_CHILD"] = "1"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    raise SystemExit(subprocess.call([sys.executable, __file__], env=env))

sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import MPHX, table2_topologies  # noqa: E402
from repro.core.collectives import (decomposed_psum,  # noqa: E402
                                    hierarchical_psum, int8_psum,
                                    multiplane_psum)
from repro.core.netsim import allreduce_time  # noqa: E402


def device_demo():
    print(f"devices: {jax.device_count()} x {jax.default_backend()} "
          "(host devices; JAX_PLATFORMS=cpu)")
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    x = jnp.linspace(-1, 1, 8 * 1024 * 4).reshape(8, 1024, 4)

    def run(fn, in_spec=P("data", None, None)):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                                     out_specs=in_spec, check_vma=False))(x)

    oracle = run(lambda v: jax.lax.psum(v, "model"))
    for name, fn in [
        ("multiplane_psum (4 plane-chunks)",
         lambda v: multiplane_psum(v, "model", 4, split_axis=1)),
        ("decomposed_psum (RS+AG)",
         lambda v: decomposed_psum(v, "model", split_axis=1)),
        ("int8_psum (compressed)", lambda v: int8_psum(v, "model")),
    ]:
        out = run(fn)
        err = float(jnp.abs(out - oracle).max())
        print(f"  {name:36s} max|err| = {err:.2e}")
    h = jax.jit(shard_map(
        lambda v: hierarchical_psum(v, ("data", "model"), split_axis=1),
        mesh=mesh, in_specs=P(None, None, None), out_specs=P(None, None, None),
        check_vma=False))(x)
    o2 = jax.jit(shard_map(
        lambda v: jax.lax.psum(v, ("data", "model")), mesh=mesh,
        in_specs=P(None, None, None), out_specs=P(None, None, None),
        check_vma=False))(x)
    print(f"  {'hierarchical_psum (dim walk)':36s} max|err| = "
          f"{float(jnp.abs(h - o2).max()):.2e}")


def fabric_model():
    print("\nModeled 256 MiB all-reduce on the paper's fabrics:")
    for t in table2_topologies():
        est = allreduce_time(t, 256 * 2**20)
        print(f"  {t.name:28s} {est.total_s * 1e3:9.3f} ms  ({est.algo})")


if __name__ == "__main__":
    device_demo()
    fabric_model()
