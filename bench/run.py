"""Benchmark harness of the flow simulator on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout root.  ``BENCHMARK.json`` names the cell; the cell
names a configuration (``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); each metric is read by
``bench/metrics/<name>.py``.  Nothing here knows a cell by name.  The
configuration's ``routing`` is the mode that the program and the
reference both route with.

One run: refuse any platform but a TPU with enough chips, build the
router, make the mix's pool of inputs, warm up its shapes, then run
whole simulations back to back for ``--seconds`` (ending on a whole
cycle of the mix's load levels).  One simulation is the program's
spec-to-result path: routing incidence (``flow_incidence`` in the
configuration's routing mode), the solver
(``simulate_incidence`` on the ``auto`` backend, which must resolve to
``jax``) and the FCT summary.  After the window, a sample of the
simulations drawn from the seed is compared with the float64 reference
in ``bench/reference.py``.  The last line of standard output is one
JSON object; the numbers compared, each beside its limit, are the last
lines of standard error and the last key of that object.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
# the TPU runtime would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import compare  # noqa: E402
import reference as ref  # noqa: E402
from gen import CheckSample, Plane, Traffic  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_SECONDS = 1.0   # traced part of a --trace 1 window: whole cycles
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """The platform is not a TPU, or has fewer chips than the cell."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> dict:
    """The cell of ``workload`` with its configuration, mix and metrics."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    routing_of(config)
    return {
        "cell": cell,
        "config": config,
        "mix": load_json(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json")),
        "end_to_end": spec["end_to_end"],
        "per_layer": spec["per_layer"],
    }


def routing_of(config: dict) -> str:
    """The configuration's routing mode; SystemExit for a mode that the
    benchmark cannot check."""
    mode = config.get("routing")
    if mode not in ref.ROUTINGS:
        raise SystemExit(
            f"configuration {config.get('name')!r} routes {mode!r}: the "
            f"benchmark runs only {', '.join(ref.ROUTINGS)}, the modes with "
            "a fixed per-flow spread of paths that the float64 reference "
            "knows (adaptive re-routes under load and has no static "
            "incidence)")
    return mode


def reader(name: str):
    """The ``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCount:
    """Counts XLA executables the process builds or loads from the
    persistent cache (JAX's backend-compile events), and cache hits and
    misses, while it is open."""

    def __init__(self):
        self.compiles = self.hits = self.misses = 0

    def _dur(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _ev(self, event, **kw):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._ev)


def chips(n: int):
    """The first ``n`` TPU devices; NoChip otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not tpu")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def build_router(config: dict):
    """The program's router for the configuration's preset, after
    checking that the preset is the plane the configuration states."""
    from repro.core.netsim import make_router
    from repro.experiments.sweep import SWEEP_TOPOLOGIES

    topo = SWEEP_TOPOLOGIES[config["preset"]]
    want = Plane.from_config(config)
    got = Plane(topo.n, topo.p, tuple(topo.dims), tuple(topo.links_per_dim),
                float(topo.nic_bw_gbps))
    if got != want:
        raise ValueError(f"preset {config['preset']!r} is {got}, the "
                         f"configuration states {want}")
    return make_router(topo)


class Simulator:
    """One simulation through the program's public entry points."""

    def __init__(self, router, routing: str):
        from jax.profiler import TraceAnnotation

        from repro.core.routing_vec import DemandArrays
        from repro.sim.events import simulate_incidence
        from repro.sim.fairshare import flow_incidence, resolve_sim_backend

        got = resolve_sim_backend("auto")
        if got != "jax":
            raise RuntimeError(f"solver backend 'auto' resolved to {got!r}, "
                               "the benchmark runs 'jax'")
        self.router = router
        self.routing = routing
        self._ann = TraceAnnotation
        self._dem = DemandArrays
        self._inc = flow_incidence
        self._sim = simulate_incidence

    def __call__(self, inp) -> "tuple[dict, tuple]":
        ann, clock = self._ann, time.perf_counter
        with ann("bench.sim"):
            t0 = clock()
            with ann("bench.incidence"):
                inc = self._inc(self.router,
                                self._dem(inp.src, inp.dst, inp.gbps),
                                self.routing)
            t1 = clock()
            with ann("bench.solve"):
                res = self._sim(inc, inp.size_bytes, inp.gbps, inp.start_s,
                                backend="auto")
            t2 = clock()
            with ann("bench.summary"):
                summ = summarize(inc, res, inp.gbps)
            t3 = clock()
        times = {"incidence_s": t1 - t0, "solve_s": t2 - t1,
                 "summary_s": t3 - t2}
        return times, (inc, res, summ)

    def warm(self, pool) -> int:
        """Load or compile the solver's programs for each distinct shape
        (flows, incidence entries, edges used) of the pool's inputs: one
        solve of each, with every size zero, so that each flow is done
        at its start and the device loop ends at once.  Returns the
        number of shapes."""
        shapes = set()
        for inp in pool:
            inc = self._inc(self.router,
                            self._dem(inp.src, inp.dst, inp.gbps),
                            self.routing)
            shape = (inc.n_flows, inc.flow.size, np.unique(inc.edge).size)
            if shape not in shapes:
                shapes.add(shape)
                self._sim(inc, np.zeros_like(inp.size_bytes), inp.gbps,
                          inp.start_s, backend="auto")
        return len(shapes)


def summarize(inc, res, gbps) -> dict:
    """The FCT summary of one result (the benchmark's own arithmetic)."""
    bneck = ref.bottleneck_gbps(inc.flow, inc.edge, inc.frac, inc.capacity,
                                inc.n_flows)
    return ref.fct_summary(res.fct_s, res.finish_s, res.size_bytes, gbps,
                           res.latency_s, bneck, res.makespan_s,
                           float(gbps.sum()))


def observed_view(kept: tuple, inp, plane: Plane) -> dict:
    """What the timed path produced, in the comparison's terms: the
    program's edge slots are keyed by the switch pair they join."""
    inc, res, summ = kept
    pairs = plane.slot_pairs()
    if inc.n_edges != pairs.size:
        # not the documented slot layout: keys that match no pair
        pairs = plane.S ** 2 + np.arange(inc.n_edges, dtype=np.int64)

    def per_edge(per_flow):
        return np.bincount(inc.edge, weights=per_flow[inc.flow] * inc.frac,
                           minlength=inc.n_edges)

    return compare.view(pairs, per_edge(inp.gbps), per_edge(inp.size_bytes),
                        res.edge_bytes,
                        (inc.flow, pairs[inc.edge], inc.frac),
                        res.finish_s, res.n_epochs, summ)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(resolved: dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float = T_PROCESS) -> dict:
    """One run of one cell; returns the result object.  ``require_tpu``
    off lets the tests drive it on the CPU."""
    with CompileCount() as counter:
        return _run_cell(resolved, seed, seconds, trace, require_tpu,
                         t_start, counter)


def _run_cell(resolved, seed, seconds, trace, require_tpu, t_start,
              counter) -> dict:
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.telemetry import collecting

    laps = [t_start]

    def lap() -> float:
        laps.append(time.perf_counter())
        return laps[-1] - laps[-2]

    parts = {"imports": lap()}
    n_chips = int(resolved["cell"]["chips"])
    devices = chips(n_chips) if require_tpu else jax.devices()[:n_chips]
    parts["chips"] = lap()
    cache_dir = enable_compile_cache()
    # cache every program, however quick to compile, so that set-up after
    # a checkout's first run loads them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    config, mix = resolved["config"], resolved["mix"]
    plane = Plane.from_config(config)
    traffic = Traffic(mix, plane, seed)
    sim = Simulator(build_router(config), routing_of(config))
    parts["router"] = lap()
    log(f"routing: {sim.routing} (configuration {config['name']})")
    pool = [traffic.inputs(j) for j in range(traffic.pool)]
    parts["pool"] = lap()
    n_shapes = sim.warm(pool)
    parts["warmup"] = lap()
    setup_compiles = counter.compiles
    setup_hits, setup_misses = counter.hits, counter.misses

    sims, sample, failed = [], CheckSample(traffic), 0
    tracing = False
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        tracing = True
    k = 0
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t_start
    with collecting() as registry:
        while True:
            try:
                times, out = sim(pool[k % traffic.pool])
            except (RuntimeError, ValueError, FloatingPointError):
                failed += 1
                log(traceback.format_exc())
                break
            times["k"] = k
            sims.append(times)
            sample.offer(k, out)
            k += 1
            if not traffic.cycle_done(len(sims)):
                continue
            elapsed = time.perf_counter() - t_w0
            if tracing and elapsed >= TRACE_SECONDS:
                jax.profiler.stop_trace()
                tracing = False
            if elapsed >= seconds:
                break
    t_w1 = time.perf_counter()
    if tracing:
        jax.profiler.stop_trace()
    window_compiles = counter.compiles - setup_compiles
    n = len(sims)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"setup: {setup_s:.6f} s; compiles {setup_compiles} (persistent "
        f"cache hits {setup_hits}, misses {setup_misses}) for {n_shapes} "
        f"shapes; cache dir {cache_dir}")
    log("setup parts, s: " + json.dumps(parts))
    log(f"window: {n} simulations in {t_w1 - t_w0:.6f} s; "
        f"window_compiles={window_compiles}; failed {failed}")
    log("counters: " + json.dumps(registry.snapshot()["counters"],
                                  sort_keys=True))
    span_means = {key: float(np.mean([s[key] for s in sims])) if sims
                  else None
                  for key in ("incidence_s", "solve_s", "summary_s")}
    log("host spans, mean per simulation: " + json.dumps(span_means))
    per_sim_s = [s["incidence_s"] + s["solve_s"] + s["summary_s"]
                 for s in sims]
    if len(per_sim_s) >= 4:
        half = len(per_sim_s) // 2
        log("simulation seconds: first " + repr(per_sim_s[0]) +
            ", quartiles " + json.dumps(
            statistics.quantiles(per_sim_s, n=4)) + ", first half mean "
            f"{np.mean(per_sim_s[:half])!r}, second half mean "
            f"{np.mean(per_sim_s[half:])!r}")

    # correctness: after the window and the memory reading, on the host
    t_c0 = time.perf_counter()
    per_sim = []
    for k, out in sample.items():
        inp = traffic.inputs(k)
        got = observed_view(out, inp, plane)
        want = compare.reference_view(plane, config, inp)
        nums = compare.numbers(got, want)
        per_sim.append(nums)
        log(f"check k={k} load={inp.load} epochs "
            f"{got['n_epochs']}/{want['n_epochs']}: " + json.dumps(nums))
    del sample
    worst = compare.worst(per_sim)
    correct = failed == 0 and n > 0 and compare.verdict(worst)
    log(f"reference: {len(per_sim)} simulations compared in "
        f"{time.perf_counter() - t_c0:.3f} s")

    ctx = {"sims": sims, "n_sims": n, "window_s": t_w1 - t_w0,
           "setup_s": setup_s, "trace": None}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": n + failed,
              "failed": failed}
    if trace:
        import trace_reduce

        t_r0 = time.perf_counter()
        try:
            red = trace_reduce.reduce(trace_reduce.load(TRACE_DIR))
        except FileNotFoundError as e:     # the profiler wrote nothing
            log(f"trace: {e}")
            red = None
        log(f"trace reduced in {time.perf_counter() - t_r0:.3f} s")
        ctx["trace"] = red
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
        metrics = resolved["per_layer"]
    else:
        metrics = resolved["end_to_end"]
    values = {}
    for m in metrics:
        v = reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = values
    result["device"] = device
    if ctx["trace"] is not None:
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["checks"] = {name: {"value": worst[name], "limit": lim}
                        for name, (lim, _) in compare.LIMITS.items()}
    for name, (lim, _) in compare.LIMITS.items():
        log(f"check {name} {worst[name]!r} limit {lim!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    resolved = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                       args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        result = run_cell(resolved, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
