"""Production mesh construction.

Importing this module never touches jax device state; both helpers are
functions.  The dry-run (and ONLY the dry-run) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so these meshes can be built on one CPU host.
"""

from __future__ import annotations

import jax

from repro.models.sharding import MeshPlan, MULTI_POD, SINGLE_POD


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    The model code steers layouts with ``with_sharding_constraint``,
    which only asserts (and fails) on the explicit axes ``make_mesh``
    builds by default."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_mesh_plan(*, multi_pod: bool = False,
                   sequence_parallel: bool = False,
                   fsdp: bool = True, fsdp_pod: bool = False,
                   moe_ws: bool = False) -> MeshPlan:
    base = MULTI_POD if multi_pod else SINGLE_POD
    if not fsdp:
        fsdp_axes = None
    elif multi_pod and fsdp_pod:
        fsdp_axes = ("pod", "data")    # ZeRO over DCN too (1T config)
    else:
        fsdp_axes = "data"
    return MeshPlan(batch=base.batch, sp=sequence_parallel, fsdp=fsdp_axes,
                    moe_ws=moe_ws)

