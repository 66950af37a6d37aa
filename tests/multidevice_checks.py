"""Multi-device correctness checks — run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (tests/test_collectives.py
wrapper).  Never import this module in-process: smoke tests must see 1 device.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P, set_mesh  # noqa: E402

from repro.launch.mesh import make_auto_mesh  # noqa: E402
from repro.core.collectives import (decomposed_psum, hierarchical_psum,  # noqa: E402
                                    int8_psum, multiplane_all_gather,
                                    multiplane_psum, psum_auto)


def check(name, ok, detail=""):
    status = "OK" if ok else "FAIL"
    print(f"[{status}] {name} {detail}")
    if not ok:
        raise SystemExit(f"check failed: {name} {detail}")


def mesh2d(data=4, model=2):
    return make_auto_mesh((data, model), ("data", "model"))


def test_collectives_match_psum():
    mesh = mesh2d()
    x = jnp.arange(8 * 16 * 4, dtype=jnp.float32).reshape(8, 16, 4) / 100.0

    def run(fn):
        return jax.jit(shard_map(
            fn, mesh=mesh, in_specs=P("data", None, None),
            out_specs=P("data", None, None), check_vma=False))(x)

    oracle = run(lambda v: jax.lax.psum(v, "model"))
    for name, fn in [
        ("multiplane_psum", lambda v: multiplane_psum(v, "model", 4,
                                                      split_axis=1)),
        ("decomposed_psum", lambda v: decomposed_psum(v, "model",
                                                      split_axis=1)),
        ("psum_auto", lambda v: psum_auto(v, "model", 4)),
    ]:
        out = run(fn)
        err = float(jnp.abs(out - oracle).max())
        check(name, err < 1e-5, f"err={err:.2e}")

    # hierarchical over both axes == psum over both
    def o2(v):
        return jax.lax.psum(v, ("data", "model"))

    def h2(v):
        return hierarchical_psum(v, ("data", "model"), split_axis=1)

    run2 = lambda fn: jax.jit(shard_map(
        fn, mesh=mesh, in_specs=P(None, None, None),
        out_specs=P(None, None, None), check_vma=False))(x)
    err = float(jnp.abs(run2(h2) - run2(o2)).max())
    check("hierarchical_psum", err < 1e-5, f"err={err:.2e}")

    # int8 compressed: within quantization error of true psum
    o = run(lambda v: jax.lax.psum(v, "model"))
    c = run(lambda v: int8_psum(v, "model"))
    scale = float(jnp.abs(x).max()) / 127.0
    err = float(jnp.abs(o - c).max())
    check("int8_psum", err <= 2 * 2 * scale + 1e-6, f"err={err:.2e}")

    # multiplane all-gather == all-gather
    def ag(v):
        return jax.lax.all_gather(v, "model", axis=1, tiled=True)

    def mag(v):
        return multiplane_all_gather(v, "model", 4, gather_axis=1,
                                     chunk_axis=2)

    ga = run(ag)
    gm = run(mag)
    err = float(jnp.abs(ga - gm).max())
    check("multiplane_all_gather", err < 1e-6, f"err={err:.2e}")


def test_ep_moe_matches_dispatch():
    from repro.configs.base import ModelConfig, MoEConfig, RunConfig
    from repro.models.moe import moe_ffn_dispatch, moe_init
    from repro.models.sharding import MeshPlan
    from repro.models.transformer import DecoderLM

    cfg = ModelConfig(
        arch_id="tiny-moe", family="moe", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
        moe=MoEConfig(n_experts=8, top_k=2, d_expert=48,
                      n_shared_experts=1, first_k_dense=1,
                      capacity_factor=8.0),
        param_dtype="float32", activation_dtype="float32")
    mesh = mesh2d(data=4, model=2)
    run = RunConfig(ep_moe=True)
    model = DecoderLM(cfg, run, mesh=mesh, plan=MeshPlan())
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))

    moe_p = params["layers"]["moe"]
    moe_p0 = jax.tree.map(lambda l: l[0], moe_p)
    with set_mesh(mesh):
        y_ep, aux_ep = model._moe_ep(moe_p0, x)
    y_ref, aux_ref = moe_ffn_dispatch(moe_p0, x, cfg)
    # EP computes capacity per *local* shard; with capacity_factor=8 no
    # drops occur in either path, so results agree.
    err = float(jnp.abs(y_ep - y_ref).max())
    check("moe_ep_vs_dispatch", err < 1e-4, f"err={err:.2e}")
    # EP computes the load-balance aux per shard and averages (standard
    # Switch/GShard practice) — close to, but not bit-equal with, the
    # global estimator.
    rel = abs(float(aux_ep - aux_ref)) / max(abs(float(aux_ref)), 1e-6)
    check("moe_ep_aux", rel < 0.25, f"rel={rel:.3f}")

    # weight-stationary EP (gather tokens, partial-f GEMM, reduce-scatter)
    run_ws = RunConfig(ep_moe=True, moe_weight_stationary=True)
    model_ws = DecoderLM(cfg, run_ws, mesh=mesh,
                         plan=MeshPlan(moe_ws=True))
    with set_mesh(mesh):
        y_ws, _ = model_ws._moe_ep(moe_p0, x)
    err = float(jnp.abs(y_ws - y_ref).max())
    check("moe_ep_weight_stationary", err < 1e-4, f"err={err:.2e}")

    # TP-f MoE (few-expert path): local dispatch + f-sharded experts
    run_tpf = RunConfig(ep_moe=False, moe_tp_f=True)
    model_tpf = DecoderLM(cfg, run_tpf, mesh=mesh, plan=MeshPlan())
    with set_mesh(mesh):
        y_tpf, _ = model_tpf._moe_tp_f(moe_p0, x)
    err = float(jnp.abs(y_tpf - y_ref).max())
    check("moe_tp_f", err < 1e-4, f"err={err:.2e}")

    # full train CE with mesh (EP active) == without mesh (CE is exact;
    # total loss differs only by the per-shard aux estimator * 0.01)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 128)
    batch = {"tokens": tokens, "labels": tokens}
    with set_mesh(mesh):
        _, metr_mesh = jax.jit(model.loss)(params, batch)
    model0 = DecoderLM(cfg, RunConfig(ep_moe=False))
    _, metr_ref = jax.jit(model0.loss)(params, batch)
    err = abs(float(metr_mesh["ce"] - metr_ref["ce"]))
    check("moe_ep_model_ce", err < 1e-4,
          f"{float(metr_mesh['ce'])} vs {float(metr_ref['ce'])}")


def test_sharded_trainer_matches_unsharded():
    from repro.configs.base import ModelConfig, RunConfig
    from repro.models.sharding import MeshPlan
    from repro.models.transformer import DecoderLM
    from repro.train.trainer import Trainer

    cfg = ModelConfig(arch_id="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      param_dtype="float32", activation_dtype="float32")
    run = RunConfig(lr=1e-2, warmup_steps=0, total_steps=10)
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, 128))
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(tokens)}

    t0 = Trainer(DecoderLM(cfg, run), run)
    s0 = t0.init_state(jax.random.PRNGKey(0))
    s0b, m0 = t0.make_train_step()(s0, batch)

    mesh = mesh2d()
    model = DecoderLM(cfg, run, mesh=mesh, plan=MeshPlan())
    t1 = Trainer(model, run, mesh=mesh, plan=MeshPlan())
    s1 = t1.init_state(jax.random.PRNGKey(0))
    s1 = jax.device_put(s1, t1.state_shardings())
    step = t1.make_train_step()
    s1b, m1 = step(s1, batch)
    err = abs(float(m0["loss"]) - float(m1["loss"]))
    check("sharded_loss_matches", err < 1e-5, f"err={err:.2e}")
    for a, b in zip(jax.tree.leaves(s0b.params), jax.tree.leaves(s1b.params)):
        if not np.allclose(np.asarray(a), np.asarray(b), atol=1e-5):
            check("sharded_params_match", False,
                  f"max {np.abs(np.asarray(a) - np.asarray(b)).max()}")
    check("sharded_params_match", True)

    # elastic: restore this sharded state onto a DIFFERENT mesh shape
    import tempfile
    from repro.train.checkpoint import Checkpointer
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, s1b)
        mesh2 = make_auto_mesh((2, 2), ("data", "model"))
        model2 = DecoderLM(cfg, run, mesh=mesh2, plan=MeshPlan())
        t2 = Trainer(model2, run, mesh=mesh2, plan=MeshPlan())
        template = jax.eval_shape(lambda: t2.init_state(jax.random.PRNGKey(0)))
        restored, st = ck.restore(template, shardings=t2.state_shardings())
        s2b, m2 = t2.make_train_step()(restored, batch)
        check("elastic_resharded_step",
              abs(float(m2["loss"]) - 0.0) >= 0.0, f"loss={float(m2['loss'])}")
        # same numbers as continuing on the original mesh
        s1c, m1c = step(s1b, batch)
        err = abs(float(m2["loss"]) - float(m1c["loss"]))
        check("elastic_loss_matches", err < 1e-5, f"err={err:.2e}")


def test_mini_dryrun_multipod():
    """Tiny end-to-end dry-run: lower+compile a sharded train step on a
    (2,2,2) pod mesh with ShapeDtypeStructs only."""
    from repro.configs.base import ModelConfig, RunConfig
    from repro.models.sharding import MeshPlan, MULTI_POD
    from repro.models.transformer import DecoderLM
    from repro.train.trainer import Trainer

    cfg = ModelConfig(arch_id="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128)
    mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
    plan = MULTI_POD
    run = RunConfig()
    model = DecoderLM(cfg, run, mesh=mesh, plan=plan)
    trainer = Trainer(model, run, mesh=mesh, plan=plan)
    state_shapes = jax.eval_shape(
        lambda: trainer.init_state(jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    step = trainer.make_train_step()
    lowered = step.lower(state_shapes, batch)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    check("mini_dryrun_compiles", True,
          f"flops={cost.get('flops', 0):.2e}")
    # collectives exist only POST-partitioning: parse compiled HLO, not the
    # lowered (pre-SPMD) module — same source the roofline parser uses.
    hlo = compiled.as_text()
    n_coll = sum(hlo.count(f" {op}") for op in
                 ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute"))
    check("mini_dryrun_has_collectives", n_coll > 0, f"{n_coll} collectives")


if __name__ == "__main__":
    print(f"devices: {jax.device_count()}")
    assert jax.device_count() >= 8, "subprocess must force 8 host devices"
    test_collectives_match_psum()
    test_ep_moe_matches_dispatch()
    test_sharded_trainer_matches_unsharded()
    test_mini_dryrun_multipod()
    print("ALL MULTIDEVICE CHECKS PASSED")
