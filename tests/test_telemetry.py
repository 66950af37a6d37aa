"""Fabric flight recorder: metrics, traces, and the perf dashboard.

Pins the observability layer's core contracts:

  * the ambient registry defaults to the no-op ``NullRegistry`` and
    disabled telemetry does not move the jitted solver/event-loop
    outputs off ``tests/golden/fairshare_golden.json`` (record is a
    static jit argument — off compiles the identical graph);
  * the numpy reference loop and the jitted ``lax.while_loop`` journal
    the SAME trace (event count, ordering, epoch rows);
  * the Perfetto ``trace_event`` export round-trips and validates;
  * cosim phase spans tile the step clock — their durations sum to the
    reported communication time (1e-6 relative);
  * ``span`` feeds the ambient registry and the profiler's host plane;
    the solver call and both engines' incidence report their phase
    spans once per run, the children inside the parent;
  * the jit event loop counts the same water-filling rounds as the
    numpy loop, and its HLO carries every named scope;
  * both routing engines count walks and cache hits/misses uniformly;
  * ``benchmarks/report.py --check`` passes on the committed BENCH
    history and fails on a synthetic 2x slowdown;
  * a 65K-NIC run's link series stays bounded by ``LinkSeriesPolicy``
    (slow-marked), with drops counted, never silent.
"""

import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

from repro.experiments.artifacts import SCHEMA_VERSION
from repro.core.dragonfly import Dragonfly
from repro.core.hyperx import MPHX
from repro.core.netsim import make_router
from repro.core.routing_graph import graph_uniform_demands
from repro.core.routing_vec import neighbor_shift_demands, uniform_demands
from repro.sim.events import simulate_incidence
from repro.sim.fairshare import flow_incidence
from repro.telemetry import (NULL_METRICS, LinkSeriesPolicy,
                             MetricsRegistry, NullRegistry, TraceRecorder,
                             collecting, get_metrics, get_recorder,
                             recording, span, validate_trace)

REPO = os.path.join(os.path.dirname(__file__), "..")
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "fairshare_golden.json")


def _load_report_module():
    path = os.path.join(REPO, "benchmarks", "report.py")
    spec = importlib.util.spec_from_file_location("bench_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ registry ----


def test_registry_counters_gauges_timers():
    mx = MetricsRegistry()
    assert mx.enabled is True
    mx.inc("a")
    mx.inc("a", 2)
    assert mx.value("a") == 3
    assert mx.value("never") == 0
    mx.set_counter("a", 7)
    assert mx.value("a") == 7
    mx.gauge("g", "jax")
    mx.observe("t", 0.25)
    with mx.timer("t"):
        pass
    snap = mx.snapshot()
    assert snap["counters"]["a"] == 7
    assert snap["gauges"]["g"] == "jax"
    assert snap["timers"]["t"]["count"] == 2
    assert snap["timers"]["t"]["total_s"] >= 0.25
    json.dumps(snap)                      # JSON-ready


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("x", 2)
    b.inc("x", 3)
    b.observe("w", 0.5)
    a.merge(b, prefix="sub.")
    assert a.value("x") == 2
    assert a.value("sub.x") == 3
    assert a.snapshot()["timers"]["sub.w"]["count"] == 1


def test_null_registry_is_noop_and_ambient_default():
    assert get_metrics() is NULL_METRICS
    assert isinstance(NULL_METRICS, NullRegistry)
    assert NULL_METRICS.enabled is False
    NULL_METRICS.inc("x", 5)
    NULL_METRICS.gauge("g", 1)
    NULL_METRICS.observe("t", 1.0)
    with NULL_METRICS.timer("t"):
        pass
    assert NULL_METRICS.value("x") == 0
    assert NULL_METRICS.snapshot() == {"counters": {}, "gauges": {},
                                       "timers": {}}


def test_collecting_swaps_ambient_and_restores():
    assert get_metrics() is NULL_METRICS
    with collecting() as outer:
        assert get_metrics() is outer
        inner = MetricsRegistry()
        with collecting(inner):
            assert get_metrics() is inner
            get_metrics().inc("seen")
        assert get_metrics() is outer
        assert inner.value("seen") == 1
    assert get_metrics() is NULL_METRICS
    assert get_recorder() is None


# ------------------------------------------- routing-engine counters ----


def _engines():
    mphx = MPHX(n=2, p=8, dims=(8, 8))
    dfly = Dragonfly(p=2, a=4, h=2, groups=9, name="Dragonfly (small)")
    return {
        "array": (make_router(mphx, backend="numpy"),
                  uniform_demands(mphx, 400.0)),
        "graph": (make_router(dfly, backend="numpy"),
                  graph_uniform_demands(dfly, 400.0)),
    }


@pytest.mark.parametrize("engine", ["array", "graph"])
def test_engine_walks_and_incidence_spans_count_once(engine):
    router, dem = _engines()[engine]
    assert router.metrics.value("incidence.walks") == 0
    with collecting() as mx:
        router.incidence(dem, "minimal")
    assert router.metrics.value("incidence.walks") == 1
    assert mx.value("incidence.walks") == 1
    timers = mx.snapshot()["timers"]
    assert {k for k in timers if k.startswith("incidence.")} == \
        {"incidence.walk", "incidence.coalesce"}
    assert all(t["count"] == 1 for t in timers.values())


def test_cache_hit_miss_uniform_on_both_engines():
    for name, (router, dem) in _engines().items():
        with collecting() as mx:
            router.incidence_cached(dem, "minimal")
            misses = mx.value("incidence.cache_misses")
            assert misses > 0, name
            assert mx.value("incidence.cache_hits") == 0, name
            router.incidence_cached(dem, "minimal")
            assert mx.value("incidence.cache_hits") == misses, name
            assert mx.value("incidence.cache_misses") == misses, name
        # the router's own registry mirrors the ambient counts
        assert router.metrics.value("incidence.cache_hits") == misses, name


def test_solver_and_sim_counters_flow():
    router, build = _engines()["array"]
    dem = neighbor_shift_demands(router.topo, 800.0)
    inc = flow_incidence(router, dem, "minimal")
    caps = np.asarray(dem.gbps, dtype=np.float64)
    with collecting() as mx:
        simulate_incidence(inc, np.full(inc.n_flows, 1 << 20), caps,
                           backend="numpy")
    snap = mx.snapshot()
    assert snap["counters"]["sim.runs"] == 1
    assert snap["counters"]["sim.flows"] == inc.n_flows
    assert snap["counters"]["sim.epochs"] >= 1
    assert snap["counters"]["waterfill.solves"] >= 1
    assert snap["counters"]["waterfill.rounds"] >= \
        snap["counters"]["waterfill.solves"]
    assert snap["timers"]["sim.simulate"]["count"] == 1


SIM_PHASES = ("sim.compress", "sim.transfer", "sim.loop", "sim.readback",
              "sim.finalize")
SCOPES = ("waterfill.edge_load", "waterfill.step", "waterfill.freeze",
          "epoch.admit", "epoch.advance", "epoch.edge_bytes",
          "epoch.journal", "loop.edge_bytes")


def test_span_feeds_the_registry_and_nests():
    with span("outer"):            # nothing collecting: a no-op
        pass
    with collecting() as mx:
        with span("outer", run=3):
            for _ in range(2):
                with span("inner"):
                    pass
        with pytest.raises(KeyError):
            with span("outer"):
                raise KeyError("an error still closes the span")
    timers = mx.snapshot()["timers"]
    assert timers["outer"]["count"] == 2
    assert timers["inner"]["count"] == 2
    assert mx._timers["inner"]["total_s"] <= mx._timers["outer"]["total_s"]
    assert get_metrics() is NULL_METRICS


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("sim.simulate", run=5):
            with span("sim.loop"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("sim."):
                        found[ev.name] = (ev.start_ns, ev.duration_ns,
                                          dict(ev.stats))
    assert found["sim.simulate"][2] == {"run": 5}
    (s0, d0, _), (s1, d1, _) = found["sim.simulate"], found["sim.loop"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0


@pytest.mark.parametrize("engine", ["array", "graph"])
def test_jax_run_reports_each_phase_span_once(engine):
    pytest.importorskip("jax")
    router, dem = _engines()[engine]
    caps = np.asarray(dem.gbps, dtype=np.float64)
    with collecting() as mx:
        inc = flow_incidence(router, dem, "minimal")
        simulate_incidence(inc, np.full(inc.n_flows, 1 << 20), caps,
                           backend="jax")
    timers = mx.snapshot()["timers"]
    names = ("sim.simulate", "incidence.walk", "incidence.coalesce") + \
        SIM_PHASES
    assert set(timers) == set(names)
    assert all(timers[n]["count"] == 1 for n in names)
    raw = mx._timers               # unrounded totals
    assert sum(raw[n]["total_s"] for n in SIM_PHASES) <= \
        raw["sim.simulate"]["total_s"]


def test_segment_scan_counts_each_jax_simulation():
    pytest.importorskip("jax")
    inc, size, caps, start = _staggered_case()
    with collecting() as mx:
        for backend in ("jax", "numpy", "jax"):
            simulate_incidence(inc, size, caps, start_s=start,
                               backend=backend)
    assert mx.value("sim.runs") == 3
    assert mx.value("sim.segment_scan") == 2


def test_jit_rounds_match_numpy_on_staggered_case():
    pytest.importorskip("jax")
    inc, size, caps, start = _staggered_case()
    counts = {}
    for backend in ("numpy", "jax"):
        with collecting() as mx:
            res = simulate_incidence(inc, size, caps, start_s=start,
                                     backend=backend)
        counts[backend] = (mx.value("waterfill.rounds"),
                           mx.value("waterfill.solves"), res.n_epochs)
    assert counts["jax"] == counts["numpy"]
    rounds, solves, epochs = counts["jax"]
    assert solves == epochs and rounds >= epochs > 1


def test_event_loop_hlo_carries_every_scope():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.sim.events import _event_loop_jit
    from repro.sim.fairshare import SegmentLayout, _compress_edges

    inc, size, caps, start = _staggered_case()
    used, inc_c, layout = _compress_edges(inc)
    with jax.enable_x64(True):
        args = [jnp.asarray(a) for a in (inc_c.flow, inc_c.edge, inc_c.frac,
                                         inc_c.capacity, size, caps, start,
                                         1e-9)]
        lowered = _event_loop_jit().lower(
            *args, jnp.arange(4),
            layout=SegmentLayout(*map(jnp.asarray, layout)), E=used.size,
            use_pallas=False, record=True, max_j=8)
    text = lowered.as_text(debug_info=True)
    missing = [s for s in SCOPES if f"/{s}/" not in text]
    assert not missing


# ------------------------------------------------- trace determinism ----


def _staggered_case():
    topo = MPHX(n=2, p=8, dims=(8, 8))
    router = make_router(topo, backend="numpy")
    dem = neighbor_shift_demands(topo, 800.0)
    inc = flow_incidence(router, dem, "minimal")
    caps = np.asarray(dem.gbps, dtype=np.float64)
    rng = np.random.default_rng(11)
    size = rng.uniform(0.2, 1.0, inc.n_flows) * (1 << 22)
    start = rng.uniform(0.0, 200e-6, inc.n_flows)
    return inc, size, caps, start


def _traced_run(backend):
    inc, size, caps, start = _staggered_case()
    rec = TraceRecorder()
    with recording(rec):
        res = simulate_incidence(inc, size, caps, start_s=start,
                                 backend=backend)
    return rec, res


def test_numpy_and_jax_journal_the_same_trace():
    pytest.importorskip("jax")
    rec_np, res_np = _traced_run("numpy")
    rec_jx, res_jx = _traced_run("jax")
    assert res_np.n_epochs == res_jx.n_epochs
    # same events in the same order — the jit loop replays the reference
    # loop's journaling semantics, not just its totals
    assert [(e["ph"], e["name"]) for e in rec_np.events] == \
        [(e["ph"], e["name"]) for e in rec_jx.events]
    jn, jj = rec_np.journals[0], rec_jx.journals[0]
    assert jn["edge_ids"] == jj["edge_ids"]
    assert jn["active_flows"] == jj["active_flows"]
    assert jn["dropped_epochs"] == jj["dropped_epochs"] == 0
    scale = max(res_np.makespan_s, 1e-30)
    np.testing.assert_allclose(jn["t_s"], jj["t_s"], rtol=0,
                               atol=1e-9 * scale)
    np.testing.assert_allclose(jn["dt_s"], jj["dt_s"], rtol=0,
                               atol=1e-9 * scale)
    np.testing.assert_allclose(jn["util"], jj["util"], rtol=0, atol=1e-9)


def test_epoch_journal_rows_match_epoch_count():
    rec, res = _traced_run("numpy")
    j = rec.journals[0]
    assert len(j["t_s"]) == res.n_epochs
    assert len(j["util"]) == res.n_epochs
    k = len(j["edge_ids"])
    pol = LinkSeriesPolicy()
    assert 0 < k <= pol.top_k + pol.reservoir
    assert all(len(row) == k for row in j["util"])


def test_link_policy_selection_is_deterministic_and_bounded():
    inc, size, caps, start = _staggered_case()
    pol = LinkSeriesPolicy(top_k=4, reservoir=2, seed=3)
    a = pol.select(inc, caps)
    b = pol.select(inc, caps)
    assert np.array_equal(a, b)
    assert a.size <= 6
    assert np.array_equal(a, np.sort(a))
    load = inc.loads(np.broadcast_to(caps, (inc.n_flows,)))
    assert (load[a] > 0).all()            # only used edges qualify


# -------------------------------------------------- golden pinning ----


def test_disabled_telemetry_pins_jit_outputs_to_golden():
    pytest.importorskip("jax")
    with open(GOLDEN) as f:
        rec = json.load(f)["staggered"]
    topo = MPHX(n=2, p=8, dims=(8, 8))
    router = make_router(topo, backend="numpy")
    dem = neighbor_shift_demands(topo, 800.0)
    inc = flow_incidence(router, dem, "minimal")
    size = np.asarray(rec["size_bytes"])
    caps = np.asarray(rec["rate_caps_gbps"])
    start = np.asarray(rec["start_s"])
    assert get_metrics() is NULL_METRICS   # telemetry is OFF
    res = simulate_incidence(inc, size, caps, start_s=start,
                             backend="jax")
    tol = 1e-9 * rec["makespan_s"]
    np.testing.assert_allclose(res.finish_s, np.asarray(rec["finish_s"]),
                               rtol=0, atol=tol)
    assert res.n_epochs == rec["n_epochs"]
    # and recording must not move the outputs either (the journal is
    # numerically inert — it never feeds back into the solver state)
    with recording():
        res2 = simulate_incidence(inc, size, caps, start_s=start,
                                  backend="jax")
    np.testing.assert_allclose(res2.finish_s, res.finish_s, rtol=0,
                               atol=1e-12 * rec["makespan_s"])
    assert res2.n_epochs == res.n_epochs


# -------------------------------------------------- perfetto export ----


def test_perfetto_round_trip(tmp_path):
    rec = TraceRecorder()
    rec.span("phase_a", 0.0, 1e-3, process="cosim:t", thread="step",
             args={"kind": "allreduce"})
    rec.span("plane busy", 0.0, 5e-4, process="cosim:t", thread="plane 0")
    rec.instant("failure", 2e-3, process="failures")
    rec.counter("active_flows", 0.0, {"epochs": 4})
    rec.note_skip("table2", "analytic only")
    rec.metrics.inc("sim.runs")
    path = tmp_path / "trace.json"
    rec.export(str(path))
    payload = json.loads(path.read_text())
    assert validate_trace(payload) == []
    assert payload["displayTimeUnit"] == "ms"
    evs = payload["traceEvents"]
    # metadata tracks precede the data events and name every track
    metas = [e for e in evs if e["ph"] == "M"]
    assert {m["name"] for m in metas} == {"process_name", "thread_name"}
    span = next(e for e in evs if e["ph"] == "X" and
                e["name"] == "phase_a")
    assert span["ts"] == 0.0 and span["dur"] == pytest.approx(1e3)
    other = payload["otherData"]
    assert other["skipped"] == [{"name": "table2", "traced": False,
                                 "reason": "analytic only"}]
    assert other["metrics"]["counters"]["sim.runs"] == 1


def test_validate_trace_flags_malformed_events():
    bad = {"traceEvents": [{"ph": "X", "name": "x", "ts": -1.0},
                           {"ph": "?"}, "nope"]}
    problems = validate_trace(bad)
    assert any("missing" in p for p in problems)
    assert any("unknown ph" in p for p in problems)
    assert any("not an object" in p for p in problems)
    assert validate_trace({}) == ["traceEvents missing or not a list"]


# -------------------------------------------------- cosim span sums ----


def test_cosim_phase_spans_sum_to_comm_time():
    from repro.cosim import CollectivePhase, TrainJob
    from repro.cosim.stepsim import simulate_step

    topo = MPHX(n=2, p=8, dims=(8, 8))
    phases = (
        CollectivePhase("tp_ag", "allgather", 4, 1, 1 << 22, calls=4),
        CollectivePhase("ep_a2a", "alltoall", 4, 4, 1 << 22, calls=2),
        CollectivePhase("dp_ar", "allreduce", 8, 4, 1 << 26),
    )
    job = TrainJob("toy", 32, {"dp": 8, "tp": 4, "ep": 4},
                   tokens_per_step=4096, active_params=int(1e9),
                   phases=phases)
    rec = TraceRecorder()
    with recording(rec):
        res = simulate_step(topo, job)
    spans = [e for e in rec.events
             if e["ph"] == "X" and e.get("cat") == "phase"]
    assert len(spans) == len(phases)
    total_s = sum(e["dur"] for e in spans) / 1e6
    assert abs(total_s - res.comm_s) <= 1e-6 * res.comm_s
    # the spans tile the step clock back to back
    spans.sort(key=lambda e: e["ts"])
    assert spans[0]["ts"] == 0.0
    for prev, nxt in zip(spans, spans[1:]):
        assert nxt["ts"] == pytest.approx(prev["ts"] + prev["dur"],
                                          rel=1e-9)
    # per-plane busy windows ride their own tracks under the phase
    assert any(e.get("cat") == "plane" for e in rec.events)
    assert rec.metrics.value("cosim.phases") == len(phases)
    assert validate_trace(rec.to_json()) == []


# --------------------------------------------- failures phase spans ----


def test_recovery_curve_emits_phase_walls_and_spans():
    from repro.experiments.scenarios import SCENARIOS
    from repro.sim.failures import parse_failure_spec, recovery_curve

    topo = MPHX(n=2, p=8, dims=(8, 8))
    spec = parse_failure_spec("link:0.05")
    rec = TraceRecorder()
    with recording(rec):
        rows = recovery_curve(topo, SCENARIOS["uniform"].build, spec,
                              0.5 * topo.nic_bw_gbps)
    assert [r["phase"] for r in rows] == ["healthy", "failed", "rerouted"]
    offset = 0.0
    for r in rows:
        assert r["phase_wall_s"] >= 0.0
        # both columns are rounded to 6dp independently, so the
        # re-accumulated offset can drift a few ulps of the rounding
        assert r["t_offset_s"] == pytest.approx(offset, abs=5e-6)
        offset += r["phase_wall_s"]
    spans = [e for e in rec.events
             if e["ph"] == "X" and e.get("cat") == "recovery"]
    assert len(spans) == 3
    assert rec.metrics.value("failures.reroute_recomputes") >= 1
    assert rec.metrics.snapshot()["timers"][
        "failures.reroute_wall_s"]["count"] == 1


# --------------------------------------------------- CLI --trace ----


def test_experiments_cli_trace_records_skips(tmp_path):
    from repro.experiments.run import main

    out = str(tmp_path / "arts")
    trace = str(tmp_path / "trace.json")
    rc = main(["--suite", "table2", "--out", out, "--trace", trace])
    assert rc == 0
    payload = json.loads(open(trace).read())
    assert validate_trace(payload) == []
    skips = {n["name"]: n for n in payload["otherData"]["skipped"]}
    assert skips["table2"]["traced"] is False
    # analytic-only suite: explicit skip, not silence


def test_experiments_cli_trace_cosim_has_spans(tmp_path):
    from repro.experiments.run import main

    out = str(tmp_path / "arts")
    trace = str(tmp_path / "trace.json")
    rc = main(["--suite", "cosim", "--config", "mixtral_8x22b",
               "--ranks", "16", "--topos", "mphx-2p-8x8",
               "--out", out, "--trace", trace])
    assert rc == 0
    payload = json.loads(open(trace).read())
    assert validate_trace(payload) == []
    assert any(e.get("cat") == "phase"
               for e in payload["traceEvents"])
    # the artifacts written inside the recording scope carry the v5
    # telemetry block
    disk = json.loads(open(os.path.join(out, "cosim.json")).read())
    assert disk["schema_version"] == SCHEMA_VERSION
    assert disk["telemetry"]["counters"]["cosim.phases"] > 0


def test_bench_cli_trace_records_skips(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import run as bench_run
    finally:
        sys.path.pop(0)
    trace = str(tmp_path / "bench_trace.json")
    rc = bench_run.main(["flattening", "--trace", trace])
    assert rc == 0
    payload = json.loads(open(trace).read())
    assert validate_trace(payload) == []
    names = [n["name"] for n in payload["otherData"]["skipped"]]
    assert "bench:flattening" in names


def test_artifact_payload_telemetry_block():
    from repro.experiments.artifacts import SCHEMA_VERSION, artifact_payload

    off = artifact_payload("table2", {}, [])
    assert "telemetry" not in off
    with collecting() as mx:
        mx.inc("incidence.walks", 3)
        on = artifact_payload("table2", {}, [])
    assert on["telemetry"]["counters"]["incidence.walks"] == 3


# ----------------------------------------------- report dashboard ----


def test_report_check_passes_on_committed_history():
    report = _load_report_module()
    rc = report.main(["--check", "--results-dir",
                      os.path.join(REPO, "results")])
    assert rc == 0


def test_report_check_fails_on_synthetic_slowdown(tmp_path):
    report = _load_report_module()
    results = os.path.join(REPO, "results")
    for f in os.listdir(results):
        if f.startswith("BENCH_") and f.endswith(".json") \
                and f != "BENCH_report.json":
            shutil.copy(os.path.join(results, f), tmp_path / f)
    p = tmp_path / "BENCH_vectorized_routing.json"
    d = json.loads(p.read_text())
    d["scale"]["vectorized_s"] *= 2.0
    p.write_text(json.dumps(d))
    rc = report.main(["--check", "--results-dir", str(tmp_path),
                      "--baseline",
                      os.path.join(results, "BENCH_report.json")])
    assert rc == 1


def test_report_check_fails_on_false_flag(tmp_path):
    report = _load_report_module()
    results = os.path.join(REPO, "results")
    shutil.copy(os.path.join(results, "BENCH_vectorized_routing.json"),
                tmp_path / "BENCH_vectorized_routing.json")
    p = tmp_path / "BENCH_vectorized_routing.json"
    d = json.loads(p.read_text())
    d["scale"]["meets_target"] = False
    p.write_text(json.dumps(d))
    rc = report.main(["--check", "--results-dir", str(tmp_path),
                      "--baseline",
                      os.path.join(results, "BENCH_report.json")])
    assert rc == 1


def test_report_write_mode_builds_history_and_removes_stale_csv(tmp_path):
    report = _load_report_module()
    results = os.path.join(REPO, "results")
    shutil.copy(os.path.join(results, "BENCH_vectorized_routing.json"),
                tmp_path / "BENCH_vectorized_routing.json")
    (tmp_path / "bench_results.csv").write_text("stale\n")
    for label in ("one", "two"):
        rc = report.main(["--results-dir", str(tmp_path),
                          "--label", label])
        assert rc == 0
    assert not (tmp_path / "bench_results.csv").exists()
    hist = json.loads((tmp_path / "BENCH_report.json").read_text())
    assert [s["label"] for s in hist["snapshots"]] == ["one", "two"]
    md = (tmp_path / "BENCH_report.md").read_text()
    assert "vectorized_routing.scale.speedup" in md
    # and the freshly written history passes its own gate
    assert report.main(["--check", "--results-dir", str(tmp_path),
                        "--baseline",
                        str(tmp_path / "BENCH_report.json")]) == 0


# --------------------------------------------- 65K bounded series ----


@pytest.mark.slow
def test_65k_link_series_stays_bounded():
    pytest.importorskip("jax")
    from repro.experiments.sweep import SWEEP_TOPOLOGIES

    topo = SWEEP_TOPOLOGIES["mphx-8p-256"]
    assert topo.n_nics == 65536
    router = make_router(topo, backend="numpy")
    dem = neighbor_shift_demands(topo, 0.9 * topo.nic_bw_gbps)
    inc = flow_incidence(router, dem, "minimal")
    caps = np.asarray(dem.gbps)
    rng = np.random.default_rng(7)
    size = rng.uniform(0.2, 1.0, inc.n_flows) * (1 << 24)
    start = rng.uniform(0.0, 200e-6, inc.n_flows)
    pol = LinkSeriesPolicy(top_k=8, reservoir=4, max_epochs=64)
    rec = TraceRecorder(link_policy=pol, max_flow_events=32)
    with recording(rec):
        res = simulate_incidence(inc, size, caps, start_s=start,
                                 backend="jax")
    assert res.n_epochs > pol.max_epochs   # the cap actually bit
    j = rec.journals[0]
    assert len(j["t_s"]) == pol.max_epochs
    assert len(j["edge_ids"]) <= pol.top_k + pol.reservoir
    assert j["dropped_epochs"] == res.n_epochs - pol.max_epochs
    assert rec.metrics.value("trace.dropped_epochs") == \
        j["dropped_epochs"]
    assert rec.metrics.value("trace.dropped_flow_events") == \
        inc.n_flows - 32
    assert validate_trace(rec.to_json()) == []
