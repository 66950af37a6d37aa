"""Smoke run of the flow simulator's main path on one TPU chip.

    python chip_smoke.py

Everything runs in this one process, from seeds; nothing is downloaded.
It refuses any platform but ``tpu`` and then runs three phases at the
66K-NIC Table-2 presets:

A. the experiments CLI (``repro.experiments.run.main``), ``--suite sim``
   on ``mphx-4p-86x9`` and ``mphx-8p-256``, once with ``--sim-backend
   auto`` (which must resolve to the jax solver on the chip) and once
   with the numpy reference; both must pass their steady-state checks,
   and every FCT row must match in epochs and in FCT percentiles;
B. the jitted event loop under churn: the staggered neighbor-shift
   workload of ``benchmarks/run.py sim-scale`` on ``mphx-4p-86x9``, on
   the jax path and on the numpy reference;
C. the Pallas segment kernels compiled by Mosaic (float32) on the
   uniform incidence, against ``jax.ops.segment_sum`` /
   ``segment_min``.

Walls printed on the way are smoke timings, not measurements.  Any
failure exits non-zero before the last line, which on success is one
JSON object: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.netsim import make_router  # noqa: E402
from repro.core.routing_vec import (neighbor_shift_demands,  # noqa: E402
                                    uniform_demands)
from repro.experiments import run as experiments_run  # noqa: E402
from repro.experiments.sweep import SWEEP_TOPOLOGIES  # noqa: E402
from repro.kernels.segment_fairshare import (segment_min,  # noqa: E402
                                             segment_min_ref, segment_sum,
                                             segment_sum_ref)
from repro.sim.events import simulate_incidence  # noqa: E402
from repro.sim.fairshare import _compress_edges, flow_incidence  # noqa: E402

FULL_TOPOS = ("mphx-4p-86x9", "mphx-8p-256")
CHURN_TOPO = "mphx-4p-86x9"
SCENARIOS = ("uniform", "hotspot", "neighbor_shift")
LOADS = ("0.5", "0.9")
# jax vs numpy reference: both solve in float64 with the same freeze
# tolerances, so they agree far inside 1e-6 relative.  The CLI artifact
# rounds FCT percentiles to 0.001 us, so a percentile may also differ by
# that one rounding quantum.
REL_TOL = 1e-6
FCT_QUANTUM_US = 1e-3
# float32 segment sums in a different order than XLA's scatter: about
# sqrt(k) * 6e-8 relative for k entries per segment; min is exact
F32_SUM_RTOL = 1e-5
FCT_KEYS = ("fct_p50_us", "fct_p95_us", "fct_p99_us")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _run_cli(outdir: str, topos, sim_backend: str) -> dict:
    argv = ["--suite", "sim", "--topos", *topos,
            "--scenarios", *SCENARIOS, "--loads", *LOADS,
            "--sim-backend", sim_backend, "--out", outdir]
    t0 = time.perf_counter()
    rc = experiments_run.main(argv)
    wall = time.perf_counter() - t0
    check(rc == 0, f"experiments CLI --sim-backend {sim_backend}: rc={rc}")
    with open(os.path.join(outdir, "sim.json")) as f:
        payload = json.load(f)
    check(payload["params"]["all_steady_checks_agree_1e-6"] is True,
          f"--sim-backend {sim_backend}: steady-state checks disagree")
    print(f"phase A: CLI --sim-backend {sim_backend} -> "
          f"{payload['params']['sim_backend']}, {len(payload['rows'])} "
          f"rows, smoke wall {wall:.1f} s")
    return payload


def phase_a(topos=FULL_TOPOS, expect_backend: str = "jax") -> dict:
    """The experiments CLI on the device solver (``auto``) and on the
    numpy reference; every FCT row must agree."""
    with tempfile.TemporaryDirectory() as tmp:
        dev = _run_cli(os.path.join(tmp, "auto"), topos, "auto")
        ref = _run_cli(os.path.join(tmp, "numpy"), topos, "numpy")
    check(dev["params"]["sim_backend"] == expect_backend,
          f"--sim-backend auto resolved to {dev['params']['sim_backend']}, "
          f"not {expect_backend}")

    def fct_rows(payload):
        return {(r["topology"], r["scenario"], r["offered_fraction"]): r
                for r in payload["rows"] if r.get("kind") == "fct"}

    got, want = fct_rows(dev), fct_rows(ref)
    n_cells = len(topos) * len(SCENARIOS) * len(LOADS)
    check(len(want) == n_cells and got.keys() == want.keys(),
          f"expected {n_cells} fct rows on both: {sorted(got)} vs "
          f"{sorted(want)}")
    worst = 0.0
    for key, w in want.items():
        g = got[key]
        check(g["sim_epochs"] == w["sim_epochs"],
              f"{key}: epochs {g['sim_epochs']} vs {w['sim_epochs']}")
        for k in FCT_KEYS:
            if w[k] is None or g[k] is None:
                check(w[k] is g[k], f"{key} {k}: {g[k]} vs {w[k]}")
                continue
            diff = abs(g[k] - w[k])
            check(diff <= REL_TOL * abs(w[k]) + FCT_QUANTUM_US,
                  f"{key} {k}: {g[k]} vs {w[k]}")
            worst = max(worst, diff / abs(w[k]))
        print(f"phase A: {key[0]} {key[1]} @{key[2]}: flows "
              f"{w['sim_flows']}, epochs {w['sim_epochs']}, fct p99 "
              f"{g['fct_p99_us']} us vs reference {w['fct_p99_us']} us")
    print(f"phase A: {len(want)} fct rows agree; worst FCT percentile "
          f"rel diff {worst:.3e} (limit {REL_TOL} + {FCT_QUANTUM_US} us)")
    return {"rows": len(want), "worst_rel": worst}


def phase_b(preset: str = CHURN_TOPO) -> dict:
    """The jitted event loop on the staggered workload of
    ``benchmarks/run.py sim-scale`` against the numpy event loop."""
    topo = SWEEP_TOPOLOGIES[preset]
    router = make_router(topo, backend="numpy")
    dem = neighbor_shift_demands(topo, 0.9 * topo.nic_bw_gbps)
    inc = flow_incidence(router, dem, "minimal")
    rng = np.random.default_rng(7)
    size = rng.uniform(0.2, 1.0, inc.n_flows) * (1 << 24)
    start = rng.uniform(0.0, 200e-6, inc.n_flows)
    caps = np.asarray(dem.gbps)
    walls = {}
    res = {}
    for backend in ("jax", "jax", "numpy"):
        t0 = time.perf_counter()
        res[backend] = simulate_incidence(inc, size, caps, start_s=start,
                                          backend=backend)
        walls.setdefault(backend, []).append(time.perf_counter() - t0)
    dev, ref = res["jax"], res["numpy"]
    check(dev.n_epochs == ref.n_epochs,
          f"epochs {dev.n_epochs} vs reference {ref.n_epochs}")
    fin = np.isfinite(ref.finish_s)
    check(bool(np.array_equal(fin, np.isfinite(dev.finish_s))),
          "stalled flows differ from the reference")
    rel = float(np.max(np.abs(dev.finish_s[fin] - ref.finish_s[fin])
                       / np.abs(ref.finish_s[fin]), initial=0.0))
    check(rel <= REL_TOL, f"finish_s rel diff {rel:.3e} > {REL_TOL}")
    print(f"phase B: {preset} staggered neighbor_shift: {inc.n_flows} "
          f"flows, {ref.n_epochs} epochs on both; max finish_s rel diff "
          f"{rel:.3e} (limit {REL_TOL}); smoke walls jax first "
          f"{walls['jax'][0]:.2f} s, warm {walls['jax'][1]:.2f} s, numpy "
          f"{walls['numpy'][0]:.2f} s")
    return {"flows": inc.n_flows, "epochs": ref.n_epochs, "max_rel": rel}


def phase_c(preset: str = CHURN_TOPO, interpret: bool = False) -> dict:
    """The Pallas segment kernels at float32 on the uniform incidence
    (entries into used edges), against XLA's segment reductions."""
    import jax
    import jax.numpy as jnp

    topo = SWEEP_TOPOLOGIES[preset]
    router = make_router(topo, backend="numpy")
    inc = flow_incidence(router,
                         uniform_demands(topo, 0.9 * topo.nic_bw_gbps),
                         "minimal")
    used, edge_c, _ = _compress_edges(inc)
    n_seg = int(used.size)
    vals = jnp.asarray(inc.frac, dtype=jnp.float32)
    ids = jnp.asarray(edge_c, dtype=jnp.int32)
    out = {"entries": int(vals.shape[0]), "segments": n_seg}
    for name, kernel, ref in (("segment_sum", segment_sum, segment_sum_ref),
                              ("segment_min", segment_min, segment_min_ref)):
        fn = jax.jit(lambda v, i, k=kernel: k(v, i, n_seg,
                                              interpret=interpret))
        t0 = time.perf_counter()
        compiled = fn.lower(vals, ids).compile()
        t_compile = time.perf_counter() - t0
        if not interpret:
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name}: no Mosaic kernel (tpu_custom_call) in the "
                  "compiled program")
        t0 = time.perf_counter()
        got = np.asarray(compiled(vals, ids).block_until_ready())
        t_run = time.perf_counter() - t0
        want = np.asarray(jax.jit(lambda v, i, r=ref: r(v, i, n_seg))(
            vals, ids))
        if name == "segment_sum":
            err = float(np.max(np.abs(got - want) / np.maximum(
                np.abs(want), np.finfo(np.float32).tiny)))
            check(err <= F32_SUM_RTOL,
                  f"{name}: max rel err {err:.3e} > {F32_SUM_RTOL}")
            limit = f"rel, limit {F32_SUM_RTOL}"
        else:
            err = float(np.max(np.abs(got - want)))
            check(err == 0.0, f"{name}: max abs err {err:.3e}, not exact")
            limit = "abs, must be exact"
        out[name] = err
        print(f"phase C: {name} float32, {vals.shape[0]} entries into "
              f"{n_seg} segments: max err {err:.3e} ({limit}) vs XLA; "
              f"smoke walls compile {t_compile:.2f} s, run {t_run:.3f} s")
    return out


def main() -> int:
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}")
    if info["platform"] != "tpu":
        print("chip_smoke: needs a TPU; JAX found "
              f"{info['platform']!r} only", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    try:
        phase_a()
        phase_b()
        phase_c()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
