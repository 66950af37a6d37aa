"""CLI for the experiment suites.

Examples::

    PYTHONPATH=src python -m repro.experiments.run --suite table2
    PYTHONPATH=src python -m repro.experiments.run --suite sweep \
        --topos mphx-2p-8x8 mphx-4p-86x9 --scenarios uniform neighbor_shift \
        --modes minimal adaptive --loads 0.25 0.5 1.0
    PYTHONPATH=src python -m repro.experiments.run --suite sim \
        --topos mphx-2p-8x8 --scenarios uniform --loads 0.5 0.9
    PYTHONPATH=src python -m repro.experiments.run --suite failures \
        --topos mphx-2p-8x8 dragonfly-small --failures link:0.01 plane:1
    PYTHONPATH=src python -m repro.experiments.run --suite cosim \
        --config kimi_k2_1t_a32b --ranks 64
    PYTHONPATH=src python -m repro.experiments.run --suite serving \
        --tenants chat burst train --seed 7
    PYTHONPATH=src python -m repro.experiments.run --suite all
    PYTHONPATH=src python -m repro.experiments.run --suite cosim \
        --topos mphx-2p-8x8 --trace step_trace.json

Artifacts land in ``--out`` (default ``results/experiments``):
``{table2,sweep,sim,failures,cosim,serving}.{json,md}``; the JSON schema
(v6) is documented in :mod:`repro.experiments.artifacts` and
``docs/experiments.md`` / ``docs/simulation.md``.  ``--trace OUT.json``
runs every selected suite under the fabric flight recorder
(:mod:`repro.telemetry`) and exports one Chrome/Perfetto ``trace_event``
JSON; suites with nothing to trace (analytic-only paths) leave explicit
skip records in the trace's ``otherData.skipped``, and the artifacts
gain the schema-v5 ``telemetry`` block.  ``--seed`` makes the serving
suite's artifacts byte-reproducible run to run.
"""

from __future__ import annotations

import argparse
import sys
from contextlib import nullcontext

from repro.compile_cache import enable_compile_cache
from repro.routing.protection import REROUTE_MODES
from repro.sim.failures import parse_failure_spec
from repro.sim.fairshare import resolve_sim_backend
from .cosuite import (DEFAULT_COSIM_CONFIGS, DEFAULT_COSIM_RANKS,
                      DEFAULT_COSIM_TOPOS, run_cosim_suite)
from .scenarios import SCENARIOS
from .servesuite import (DEFAULT_SERVING_TOPOS, DEFAULT_TENANTS,
                         TENANT_PRESETS, run_serving_suite)
from .simsuite import (DEFAULT_FAILURE_SPECS, run_failures_suite,
                       run_sim_suite)
from .sweep import (DEFAULT_OUTDIR, DEFAULT_SWEEP_TOPOS, SWEEP_TOPOLOGIES,
                    run_sweep_suite, run_table2_suite)

SUITES = ["table2", "sweep", "sim", "failures", "cosim", "serving", "all"]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.experiments.run",
        description="MPHX experiment sweeps (paper §6 evaluation)")
    p.add_argument("--suite", choices=SUITES, default="all")
    p.add_argument("--out", default=DEFAULT_OUTDIR,
                   help="artifact directory (default results/experiments)")
    p.add_argument("--topos", nargs="+", choices=sorted(SWEEP_TOPOLOGIES),
                   default=None, help="sweep topologies (default: "
                   f"{' '.join(DEFAULT_SWEEP_TOPOS)})")
    p.add_argument("--scenarios", nargs="+", choices=sorted(SCENARIOS),
                   default=None, help="scenarios (default: all; inapplicable "
                   "ones are recorded as skipped)")
    p.add_argument("--modes", nargs="+",
                   choices=["minimal", "valiant", "adaptive"], default=None,
                   help="routing modes (default: all three; the sim suite "
                   "always routes minimal — the static path spread both "
                   "engines share)")
    p.add_argument("--engine", choices=["auto", "array", "graph"],
                   default="auto",
                   help="routing engine (auto: array for MPHX, graph "
                   "for baseline topologies; failures always re-route on "
                   "graph — forcing array yields skip records)")
    p.add_argument("--loads", nargs="+", type=float,
                   default=None,
                   help="offered load fractions of NIC bandwidth "
                   "(default: 0.1..1.0 for sweep, 0.5 0.9 for sim)")
    p.add_argument("--msg-bytes", type=float, default=4096)
    p.add_argument("--backend", choices=["auto", "numpy", "jax"],
                   default="auto")
    p.add_argument("--collective-mb", type=float, default=256.0,
                   help="all-reduce payload for the table2 suite")
    p.add_argument("--simulate", action="store_true",
                   help="sweep suite: add measured-FCT columns from the "
                   "flow simulator (minimal mode only)")
    p.add_argument("--flow-time-us", type=float, default=200.0,
                   help="sim: flow size as transfer seconds at the "
                   "offered rate (default 200us)")
    p.add_argument("--sim-collective-mb", type=float, default=16.0,
                   help="sim suite: measured-collective payload per NIC")
    p.add_argument("--sim-backend",
                   choices=["numpy", "jax", "pallas", "auto"],
                   default="auto",
                   help="sim/sweep/serving suites: fair-share solver path "
                   "— numpy reference loop, jax in-jit while_loop, pallas "
                   "segment kernels (CPU only), or auto: jax on a TPU, "
                   "else numpy unless x64 is on (repro.sim.fairshare)")
    p.add_argument("--failures", nargs="+", default=None,
                   metavar="SPEC",
                   help="failure specs for the failures suite, e.g. "
                   "'link:0.01' 'link:0.01,plane:1' 'switch:0.02,seed:3' "
                   f"(default: {' '.join(DEFAULT_FAILURE_SPECS)}); "
                   "topologies whose engine lacks re-route support get "
                   "explicit skip records")
    p.add_argument("--failure-load", type=float, default=0.5,
                   help="offered load fraction for the failures suite")
    p.add_argument("--failure-mode",
                   choices=["minimal", "valiant", "adaptive"],
                   default="adaptive",
                   help="routing mode for degraded-fabric re-routing")
    p.add_argument("--reroute-modes", nargs="+", default=None,
                   choices=list(REROUTE_MODES), metavar="MODE",
                   help="recovery-curve reroute modes for the failures "
                   "suite: none (global recompute), local (precomputed "
                   "backup paths, no BFS), global (local bridge + full "
                   "reconvergence); default: all three")
    p.add_argument("--protection-layers", type=int, default=4,
                   help="FatPaths/MRC protection layers for "
                   "local/global reroute modes (default 4)")
    p.add_argument("--config", nargs="+", default=None, metavar="ARCH",
                   help="cosim suite: model configs to co-simulate "
                   "(underscores normalize to the registry's hyphenated "
                   f"arch ids; default: {' '.join(DEFAULT_COSIM_CONFIGS)})")
    p.add_argument("--ranks", type=int, default=DEFAULT_COSIM_RANKS,
                   help="cosim suite: training job size in ranks "
                   f"(default {DEFAULT_COSIM_RANKS})")
    p.add_argument("--device-tflops", type=float, default=989.0,
                   help="cosim suite: per-device peak for the overlapped "
                   "compute term (default 989, H100 bf16 dense)")
    p.add_argument("--cosim-method", choices=["steady", "batches"],
                   default="steady",
                   help="cosim phase execution: steady-state step scaling "
                   "or the fully serialized batch schedule")
    p.add_argument("--tenants", nargs="+", choices=sorted(TENANT_PRESETS),
                   default=None,
                   help="serving suite: tenant presets to mix on each "
                   f"fabric (default: {' '.join(DEFAULT_TENANTS)})")
    p.add_argument("--seed", type=int, default=0,
                   help="root seed for workload RNG (one SeedSequence "
                   "spawning a child per tenant) — same seed, same "
                   "artifact, byte for byte")
    p.add_argument("--serving-duration-ms", type=float, default=None,
                   help="serving suite: override every open-loop "
                   "tenant's window (CI smokes shrink it)")
    p.add_argument("--serving-rate-scale", type=float, default=1.0,
                   help="serving suite: scale every open-loop tenant's "
                   "arrival rate")
    p.add_argument("--trace", default=None, metavar="OUT.json",
                   help="run the suites under the fabric flight recorder "
                   "and export a Chrome/Perfetto trace_event JSON "
                   "(docs/observability.md); artifacts gain the "
                   "schema-v5 telemetry block")
    return p


def _note_if_untraced(rec, suite: str, n_before: int, reason: str) -> None:
    """Explicit skip record when a suite path crossed no traced layer."""
    if rec is not None and rec.n_events == n_before:
        rec.note_skip(suite, reason)


def main(argv: "list[str] | None" = None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    try:
        args.sim_backend = resolve_sim_backend(args.sim_backend)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.failures is not None:
        try:
            specs = [parse_failure_spec(s) for s in args.failures]
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    else:
        specs = None
    rec, ctx = None, nullcontext()
    if args.trace:
        from repro.telemetry import TraceRecorder, recording
        rec = TraceRecorder()
        ctx = recording(rec)
    with ctx:
        rc = _run_suites(args, specs, rec)
    if rec is not None:
        rec.export(args.trace)
        print(f"trace: {rec.n_events} events, "
              f"{len(rec.notes)} untraced suites -> {args.trace}")
    return rc


def _run_suites(args, specs, rec=None) -> int:
    rc = 0
    if args.suite in ("table2", "all"):
        n0 = rec.n_events if rec else 0
        payload = run_table2_suite(args.out, args.collective_mb,
                                   args.msg_bytes)
        print(f"table2: {len(payload['rows'])} topologies -> "
              f"{args.out}/table2.json, {args.out}/table2.md")
        _note_if_untraced(rec, "table2", n0,
                          "analytic cost/diameter table — nothing "
                          "crosses the simulator")
    if args.suite in ("sweep", "all"):
        n0 = rec.n_events if rec else 0
        payload = run_sweep_suite(
            args.out, topo_names=args.topos, scenario_names=args.scenarios,
            modes=args.modes,
            load_fractions=tuple(args.loads) if args.loads
            else (0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
            msg_bytes=args.msg_bytes, backend=args.backend,
            engine=args.engine, simulate=args.simulate,
            flow_time_s=args.flow_time_us * 1e-6,
            sim_backend=args.sim_backend)
        print(f"sweep: {payload['params']['n_routed_rows']} routed rows, "
              f"{payload['params']['n_skipped']} skipped -> "
              f"{args.out}/sweep.json, {args.out}/sweep.md")
        _note_if_untraced(rec, "sweep", n0,
                          "analytic routing sweep without --simulate — "
                          "nothing crosses the simulator")
    if args.suite in ("sim", "all"):
        n0 = rec.n_events if rec else 0
        payload = run_sim_suite(
            args.out, topo_names=args.topos, scenario_names=args.scenarios,
            load_fractions=tuple(args.loads) if args.loads else (0.5, 0.9),
            flow_time_s=args.flow_time_us * 1e-6,
            msg_bytes=args.msg_bytes,
            collective_mb=args.sim_collective_mb,
            backend=args.backend, engine=args.engine,
            sim_backend=args.sim_backend)
        agree = payload["params"]["all_steady_checks_agree_1e-6"]
        print(f"sim: {len(payload['rows'])} rows "
              f"(steady-state agreement: {agree}) -> "
              f"{args.out}/sim.json, {args.out}/sim.md")
        if agree is False:
            # remember the failure but keep going — the failures suite
            # below is independent and its artifacts must still land
            print("sim: FAIL — simulator steady-state loads diverge from "
                  "the analytic engine (>1e-6)", file=sys.stderr)
            rc = 1
        _note_if_untraced(rec, "sim", n0,
                          "suite produced no trace events (all cells "
                          "skipped)")
    if args.suite in ("cosim", "all"):
        n0 = rec.n_events if rec else 0
        # the sim suites interpret --topos as sweep topologies; the cosim
        # default trims to fabrics big enough for the default job
        cosim_topos = args.topos if args.topos else list(DEFAULT_COSIM_TOPOS)
        payload = run_cosim_suite(
            args.out, config_names=args.config, topo_names=cosim_topos,
            n_ranks=args.ranks, device_tflops=args.device_tflops,
            method=args.cosim_method,
            backend=args.backend if args.backend != "auto" else "numpy")
        print(f"cosim: {payload['params']['n_rows']} cells, "
              f"{payload['params']['n_skipped']} skipped -> "
              f"{args.out}/cosim.json, {args.out}/cosim.md")
        _note_if_untraced(rec, "cosim", n0,
                          "suite produced no trace events (all cells "
                          "skipped)")
    if args.suite in ("serving", "all"):
        n0 = rec.n_events if rec else 0
        # serving defaults to its own small-MPHX + baseline trio
        serving_topos = args.topos if args.topos \
            else list(DEFAULT_SERVING_TOPOS)
        payload = run_serving_suite(
            args.out, topo_names=serving_topos,
            tenant_names=args.tenants, seed=args.seed,
            engine=args.engine, backend=args.backend,
            sim_backend=args.sim_backend,
            duration_ms=args.serving_duration_ms,
            rate_scale=args.serving_rate_scale)
        print(f"serving: {payload['params']['n_rows']} tenant rows, "
              f"{payload['params']['n_skipped']} skipped -> "
              f"{args.out}/serving.json, {args.out}/serving.md")
        _note_if_untraced(rec, "serving", n0,
                          "suite produced no trace events (all cells "
                          "skipped)")
    if args.suite in ("failures", "all"):
        n0 = rec.n_events if rec else 0
        payload = run_failures_suite(
            args.out, topo_names=args.topos,
            scenario_names=args.scenarios, failure_specs=specs,
            offered_fraction=args.failure_load, mode=args.failure_mode,
            backend=args.backend, engine=args.engine,
            reroute_modes=args.reroute_modes,
            protection_layers=args.protection_layers)
        print(f"failures: {payload['params']['n_rows']} rows, "
              f"{payload['params']['n_skipped']} skipped -> "
              f"{args.out}/failures.json, {args.out}/failures.md")
        _note_if_untraced(rec, "failures", n0,
                          "suite produced no trace events (all cells "
                          "skipped)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
