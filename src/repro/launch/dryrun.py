import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (debug override BEFORE jax import; production default is 512 placeholders)
if os.environ.get("DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["DRYRUN_DEVICES"])

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, on the single-pod 16x16 mesh
and the 2x16x16 multi-pod mesh: build the sharded step function
(train_step / prefill / decode serve_step), ``.lower().compile()`` it with
``ShapeDtypeStruct`` stand-ins (no real allocation), and record

  * ``compiled.memory_analysis()``  — proves the cell fits per-device HBM
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline
  * collective bytes parsed from the POST-PARTITIONING ``compiled.as_text()``
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute), per collective kind and group size

into ``results/dryrun/<cell>.json`` for the roofline benchmark.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import LM_SHAPES, RunConfig  # noqa: E402
from repro.launch.hloparse import analyze as hlo_analyze  # noqa: E402
from repro.launch.mesh import make_mesh_plan, make_production_mesh  # noqa: E402
from repro.models.registry import (ARCH_IDS, get_config, get_model,  # noqa: E402
                                   supported_shapes)
from repro.models.sharding import batch_spec, shardable  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


# --------------------------------------------------------------------------
# HLO collective parsing — lives in repro.launch.hloparse (shared with the
# co-sim traffic layer); re-exported here for back-compat.
# --------------------------------------------------------------------------

from repro.launch.hloparse import (COLLECTIVE_OPS, DTYPE_BYTES,  # noqa: E402,F401
                                   _group_size, _shape_bytes,
                                   parse_collectives)


# --------------------------------------------------------------------------
# cell construction
# --------------------------------------------------------------------------


def _with_sharding(tree_shapes, tree_specs, mesh):
    from repro.models.sharding import sanitize_specs

    tree_specs = sanitize_specs(tree_shapes, tree_specs, mesh)

    def attach(l, s):
        return jax.ShapeDtypeStruct(l.shape, l.dtype,
                                    sharding=NamedSharding(mesh, s))

    return jax.tree.map(attach, tree_shapes, tree_specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def default_run_config(arch: str, shape_id: str, multi_pod: bool,
                       **overrides) -> RunConfig:
    """Baseline distribution config (hillclimbs override).

    Training uses full remat + 8 microbatches — required to FIT 16 GB/chip
    HBM at global batch 256 x 4096 (EXPERIMENTS.md §Dry-run memory table);
    the 1T-param config additionally keeps Adam moments in bf16."""
    big = arch in ("kimi-k2-1t-a32b",)
    kw = dict(arch=arch, shape=shape_id, multi_pod=multi_pod,
              remat="full", microbatches=8,
              fsdp_params=True, fsdp_pod=big, ep_moe=True,
              adam_dtype="bfloat16" if big else "float32",
              sequence_parallel=False)
    kw.update(overrides)
    return RunConfig(**kw)


def build_cell(arch: str, shape_id: str, multi_pod: bool, run: RunConfig):
    """Returns (lowered, meta) for one cell."""
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_mesh_plan(multi_pod=multi_pod,
                          sequence_parallel=run.sequence_parallel,
                          fsdp=run.fsdp_params, fsdp_pod=run.fsdp_pod,
                          moe_ws=run.moe_weight_stationary)
    model = get_model(cfg, run, mesh, plan)
    shape = LM_SHAPES[shape_id]
    specs = model.input_specs(shape)
    meta = {"arch": arch, "shape": shape_id,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "params": model.param_count(),
            "active_params": model.active_param_count(),
            "seq_len": shape.seq_len, "global_batch": shape.global_batch,
            "kind": shape.kind}

    if shape.kind == "train":
        trainer = Trainer(model, run, mesh, plan)
        state_shapes = jax.eval_shape(
            lambda: trainer.init_state(jax.random.PRNGKey(0)))
        state_sds = _with_sharding(state_shapes, trainer.state_specs(), mesh)
        batch_sds = {
            k: jax.ShapeDtypeStruct(
                v.shape, v.dtype,
                sharding=NamedSharding(mesh, batch_spec(plan, v.ndim)))
            for k, v in specs.items()}
        step = trainer.make_train_step()
        lowered = step.lower(state_sds, batch_sds)
    elif shape.kind == "prefill":
        pspecs = model.param_specs()
        pshapes = model.param_shapes()
        p_sds = _with_sharding(pshapes, pspecs, mesh)
        in_sds = {k: jax.ShapeDtypeStruct(
            v.shape, v.dtype,
            sharding=NamedSharding(mesh, batch_spec(plan, v.ndim)))
            for k, v in specs.items()}
        args = [in_sds["tokens"]]
        if "img_embeds" in in_sds:
            args.append(in_sds["img_embeds"])
        if "frames" in in_sds:
            args.append(in_sds["frames"])
        fn = jax.jit(lambda p, *a: model.prefill(p, *a))
        lowered = fn.lower(p_sds, *args)
    else:  # decode
        pspecs = model.param_specs()
        pshapes = model.param_shapes()
        p_sds = _with_sharding(pshapes, pspecs, mesh)
        B, S = shape.global_batch, shape.seq_len
        cache_shapes = specs["caches"]
        cache_specs = model.cache_specs(B, S)
        cache_sds = _with_sharding(cache_shapes, cache_specs, mesh)
        b_ax = shardable(mesh, plan.batch_axes, B)
        tok_sds = jax.ShapeDtypeStruct(
            (B, 1), jnp.int32,
            sharding=NamedSharding(mesh, P(b_ax, None)))
        fn = jax.jit(model.decode_step, donate_argnums=(2,))
        lowered = fn.lower(p_sds, tok_sds, cache_sds)
    return lowered, meta


def run_cell(arch: str, shape_id: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, tag: str = "",
             verbose: bool = True, **run_overrides) -> dict:
    t0 = time.time()
    run = default_run_config(arch, shape_id, multi_pod, **run_overrides)
    lowered, meta = build_cell(arch, shape_id, multi_pod, run)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_rec = {attr: int(getattr(mem, attr)) for attr in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes") if hasattr(mem, attr)}
    cost = compiled.cost_analysis()
    cost_rec = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "transcendentals", "bytes accessed")}
    # scan-aware accounting (XLA cost_analysis counts while bodies once;
    # the hloparse walker expands trip counts — EXPERIMENTS.md §Dry-run)
    hlo = hlo_analyze(compiled.as_text())
    colls = hlo["collectives"]

    rec = {**meta,
           "run_config": {k: getattr(run, k) for k in
                          ("remat", "fsdp_params", "ep_moe", "adam_dtype",
                           "sequence_parallel", "microbatches",
                           "grad_compression")},
           "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
           "memory_analysis": mem_rec,
           "cost_analysis": cost_rec,
           "hlo_flops": hlo["flops"],
           "hlo_hbm_bytes": hlo["hbm_bytes"],
           "collectives": colls}
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape_id}__{rec['mesh'].replace('x', '_')}"
    if tag:
        name += f"__{tag}"
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        print(f"[dryrun] {name}: flops={hlo['flops']:.3e} "
              f"hbm={hlo['hbm_bytes']:.3e}B "
              f"mem_args={mem_rec.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
              f"temp={mem_rec.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"coll={colls['total_wire_bytes']/2**30:.3f}GiB/"
              f"{int(colls['total_count'])}ops "
              f"lower={t_lower:.0f}s compile={t_compile:.0f}s")
        print("  memory_analysis:", mem_rec)
    return rec


def all_cells(multi_pod: bool):
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape_id in supported_shapes(cfg):
            yield arch, shape_id, multi_pod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(LM_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every supported (arch x shape) cell")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    print(f"devices: {jax.device_count()} "
          f"({jax.devices()[0].platform})")
    cells = []
    if args.all:
        meshes = (False, True) if args.both_meshes else (args.multi_pod,)
        for mp in meshes:
            cells.extend(all_cells(mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        meshes = (False, True) if args.both_meshes else (args.multi_pod,)
        for mp in meshes:
            cells.append((args.arch, args.shape, mp))

    failed = []
    for arch, shape_id, mp in cells:
        try:
            run_cell(arch, shape_id, mp, out_dir=args.out, tag=args.tag)
        except Exception as e:
            traceback.print_exc()
            failed.append((arch, shape_id, mp, repr(e)[:200]))
    print(f"\n{len(cells) - len(failed)}/{len(cells)} cells passed")
    for f in failed:
        print("FAILED:", f)
    raise SystemExit(1 if failed else 0)


if __name__ == "__main__":
    main()
