"""Elastic-restart demo: train on an 8-device mesh, checkpoint, "lose" half
the machines, replan the mesh (model axis preserved), restore the sharded
checkpoint onto the smaller mesh, and continue training — loss continues
from where it left off.

Run:  PYTHONPATH=src python examples/elastic_restart.py
(re-execs itself pinned to the CPU platform with 8 host devices; the
demo exists only on host devices and never asks for an accelerator)
"""

import os
import subprocess
import sys
import tempfile

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

if os.environ.get("_MPHX_ELASTIC_CHILD") != "1":
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["_MPHX_ELASTIC_CHILD"] = "1"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    raise SystemExit(subprocess.call([sys.executable, __file__], env=env))

sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ModelConfig, RunConfig  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticDataset  # noqa: E402
from repro.launch.mesh import make_auto_mesh  # noqa: E402
from repro.models.sharding import MeshPlan  # noqa: E402
from repro.models.transformer import DecoderLM  # noqa: E402
from repro.train.checkpoint import Checkpointer  # noqa: E402
from repro.train.fault import plan_remesh  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402


def make(mesh, run):
    model = DecoderLM(CFG, run, mesh=mesh, plan=MeshPlan())
    return Trainer(model, run, mesh=mesh, plan=MeshPlan())


CFG = ModelConfig(arch_id="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  param_dtype="float32", activation_dtype="float32")


def main():
    print(f"devices: {jax.device_count()} x {jax.default_backend()} "
          "(host devices; JAX_PLATFORMS=cpu)")
    run = RunConfig(lr=3e-3, warmup_steps=5, total_steps=40)
    ds = SyntheticDataset(DataConfig(vocab_size=256, seq_len=32,
                                     global_batch=8, temperature=0.25))

    # phase 1: healthy cluster, 4x2 mesh
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    trainer = make(mesh, run)
    state = jax.device_put(trainer.init_state(jax.random.PRNGKey(0)),
                           trainer.state_shardings())
    step = trainer.make_train_step()
    for i in range(10):
        state, m = step(state, jax.tree.map(jnp.asarray, ds.batch(i)))
    print(f"[phase1 4x2] step 10 loss {float(m['loss']):.4f}")

    ckdir = tempfile.mkdtemp(prefix="elastic_")
    Checkpointer(ckdir).save(10, state)

    # disaster: 4 of 8 hosts die -> replan (model axis preserved)
    plan = plan_remesh((4, 2), ("data", "model"), available=4)
    print(f"[fault] 8 -> 4 hosts; remesh {plan.old_shape} -> "
          f"{plan.new_shape} (usable {plan.hosts_used})")

    # phase 2: restore the SAME checkpoint onto the smaller mesh
    mesh2 = make_auto_mesh(plan.new_shape, plan.axis_names)
    trainer2 = make(mesh2, run)
    template = jax.eval_shape(
        lambda: trainer2.init_state(jax.random.PRNGKey(0)))
    restored, at_step = Checkpointer(ckdir).restore(
        template, shardings=trainer2.state_shardings())
    step2 = trainer2.make_train_step()
    for i in range(at_step, at_step + 10):
        restored, m2 = step2(restored,
                             jax.tree.map(jnp.asarray, ds.batch(i)))
    print(f"[phase2 {plan.new_shape[0]}x{plan.new_shape[1]}] "
          f"resumed at {at_step}, step {at_step + 10} loss "
          f"{float(m2['loss']):.4f}")
    assert float(m2["loss"]) < float(m["loss"]) + 0.1, "loss regressed"
    print("elastic restart OK: training continued on the degraded mesh")


if __name__ == "__main__":
    main()
