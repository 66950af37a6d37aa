"""Flow-level simulator (repro.sim): cross-validation and semantics.

The acceptance contract of PR 3:

* steady-state simulator loads match the analytic engines to 1e-6 on
  small MPHX (array engine) AND a graph-engine baseline;
* a single uncontended flow's FCT matches the closed-form
  bytes/bandwidth + latency bound;
* spraying reproduces ``planes.spray_completion_time``;
* failure injection masks edges/switches, re-routes survivors, and the
  CLI produces schema-v3 artifacts with explicit skip records.
"""

import json
import math

import numpy as np
import pytest

from repro.experiments.artifacts import SCHEMA_VERSION
from repro.core.dragonfly import Dragonfly
from repro.core.fattree import ThreeTierFatTree
from repro.core.hyperx import MPHX
from repro.core.netsim import (DEFAULT_NET, gbps_to_Bps, latency_under_load,
                               load_sweep, make_router, pattern_throughput)
from repro.core.planes import SprayConfig, spray_completion_time, split_chunks
from repro.core.routing_graph import GraphRouter, graph_uniform_demands
from repro.core.routing_vec import (VectorizedHyperXRouter, hotspot_demands,
                                    neighbor_shift_demands, uniform_demands)
from repro.sim import (FailureSpec, FlowIncidence, FlowSpec, degrade_graph,
                       degraded_router, failure_throughput, flow_incidence,
                       max_min_rates, parse_failure_spec,
                       plane_capacity_factor, recovery_curve,
                       simulate_collective, simulate_demands, simulate_flows,
                       simulate_sprayed)
from repro.sim.events import path_latency, simulate_incidence
from repro.sim.spray import _per_plane_bytes

MPHX_SMALL = MPHX(n=2, p=8, dims=(8, 8))
DF_SMALL = Dragonfly(p=2, a=4, h=2, groups=9, name="Dragonfly (small)")


# ------------------------------------------------- steady-state agreement ----


@pytest.mark.parametrize("mode", ["minimal", "valiant"])
@pytest.mark.parametrize("builder", [uniform_demands, neighbor_shift_demands,
                                     hotspot_demands])
def test_steady_state_matches_array_engine(mode, builder):
    """Sim load accounting == array-engine loads (utilizations to 1e-6)."""
    router = VectorizedHyperXRouter(MPHX_SMALL, backend="numpy")
    dem = builder(MPHX_SMALL, 1600.0)
    ll = router.route(dem, mode)
    inc = flow_incidence(router, dem, mode)
    diff = np.abs(inc.utilization(dem.gbps) - ll.utilization_array()).max()
    assert diff < 1e-6


@pytest.mark.parametrize("topo", [DF_SMALL,
                                  ThreeTierFatTree(radix=8, nics=128,
                                                   name="FT3 (small)")])
def test_steady_state_matches_graph_engine(topo):
    router = GraphRouter(topo, backend="numpy")
    dem = graph_uniform_demands(topo, 1600.0)
    ll = router.route(dem, "minimal")
    inc = flow_incidence(router, dem, "minimal")
    diff = np.abs(inc.utilization(dem.gbps) - ll.utilization_array()).max()
    assert diff < 1e-6


def test_pattern_throughput_simulate_cross_check():
    rep = pattern_throughput(MPHX_SMALL,
                             uniform_demands(MPHX_SMALL, 1600.0),
                             mode="minimal", backend="numpy", simulate=True)
    assert rep["sim_max_abs_util_diff"] < 1e-6
    assert rep["max_util_sim"] == pytest.approx(rep["max_util"], abs=1e-6)


def test_simulate_flags_reject_adaptive_up_front():
    """simulate=True with the (default) adaptive mode fails with a clear
    error instead of deep inside incidence extraction."""
    dem = uniform_demands(MPHX_SMALL, 100.0)
    with pytest.raises(ValueError, match="static path spread"):
        pattern_throughput(MPHX_SMALL, dem, simulate=True)
    with pytest.raises(ValueError, match="static path spread"):
        load_sweep(MPHX_SMALL, uniform_demands, mode="adaptive",
                   load_fractions=(0.5,), simulate=True)


def test_incidence_rejects_adaptive():
    router = make_router(MPHX_SMALL, backend="numpy")
    with pytest.raises(ValueError, match="adaptive"):
        flow_incidence(router, uniform_demands(MPHX_SMALL, 100.0),
                       "adaptive")
    groute = GraphRouter(DF_SMALL, backend="numpy")
    with pytest.raises(ValueError, match="minimal"):
        flow_incidence(groute, graph_uniform_demands(DF_SMALL, 100.0),
                       "valiant")


def test_incidence_hop_counts():
    """sum of fracs per flow == expected switch hops (minimal ECMP)."""
    router = VectorizedHyperXRouter(MPHX_SMALL, backend="numpy")
    dem = neighbor_shift_demands(MPHX_SMALL, 100.0)   # 1 mismatched dim
    inc = flow_incidence(router, dem, "minimal")
    assert np.allclose(inc.switch_hops(), 1.0)
    dem2 = uniform_demands(MPHX_SMALL, 100.0)
    inc2 = flow_incidence(router, dem2, "minimal")
    # mean over all (distinct) pairs = avg_hops - 2 rescaled to exclude
    # same-switch pairs: S/(S-1) * sum (d-1)/d
    S = MPHX_SMALL.switches_per_plane
    expect = S / (S - 1) * sum((d - 1) / d for d in MPHX_SMALL.dims)
    assert inc2.switch_hops().mean() == pytest.approx(expect, rel=1e-12)


# ----------------------------------------------------------- water-filling ----


def _toy_incidence(entries, n_flows, capacity):
    flow = np.array([e[0] for e in entries], dtype=np.int64)
    edge = np.array([e[1] for e in entries], dtype=np.int64)
    frac = np.array([e[2] for e in entries], dtype=np.float64)
    return FlowIncidence(flow, edge, frac, n_flows,
                         np.asarray(capacity, dtype=np.float64))


def test_max_min_two_flows_share_one_link():
    inc = _toy_incidence([(0, 0, 1.0), (1, 0, 1.0)], 2, [10.0])
    rates = max_min_rates(inc, np.array([100.0, 100.0]))
    assert rates == pytest.approx([5.0, 5.0])


def test_max_min_progressive_filling():
    """Classic 3-flow example: flows A,B share link 1 (cap 10); B,C also
    cross link 2 (cap 16).  A=B=5 on the first bottleneck, C fills the
    rest of link 2 -> 11."""
    inc = _toy_incidence([(0, 0, 1.0), (1, 0, 1.0),
                          (1, 1, 1.0), (2, 1, 1.0)], 3, [10.0, 16.0])
    rates = max_min_rates(inc, np.full(3, 100.0))
    assert rates == pytest.approx([5.0, 5.0, 11.0])


def test_max_min_respects_demand_caps():
    inc = _toy_incidence([(0, 0, 1.0), (1, 0, 1.0)], 2, [10.0])
    rates = max_min_rates(inc, np.array([2.0, 100.0]))
    # flow 0 capped at 2, flow 1 takes the remaining 8
    assert rates == pytest.approx([2.0, 8.0])


def test_max_min_feasible_caps_returned_exactly():
    router = make_router(MPHX_SMALL, backend="numpy")
    dem = uniform_demands(MPHX_SMALL, 1600.0)
    inc = flow_incidence(router, dem, "minimal")
    caps = np.asarray(dem.gbps) * 0.5     # comfortably below saturation
    rates = max_min_rates(inc, caps)
    assert np.abs(rates - caps).max() < 1e-9


def test_max_min_fractional_incidence():
    """ECMP split: a flow crossing an edge with frac 0.5 consumes half
    its rate there."""
    inc = _toy_incidence([(0, 0, 0.5), (0, 1, 0.5)], 1, [10.0, 10.0])
    rates = max_min_rates(inc, np.array([100.0]))
    assert rates == pytest.approx([20.0])


def test_max_min_jax_backend_matches_numpy():
    # the jax solver runs in its own float64 scope, whatever the global flag
    router = make_router(MPHX_SMALL, backend="numpy")
    dem = neighbor_shift_demands(MPHX_SMALL, 1600.0)
    inc = flow_incidence(router, dem, "minimal")
    caps = np.full(inc.n_flows, 2000.0)
    r_np = max_min_rates(inc, caps, backend="numpy")
    r_jx = max_min_rates(inc, caps, backend="jax")
    assert np.abs(r_np - r_jx).max() < 1e-9


def test_sim_backend_follows_the_platform(monkeypatch):
    import jax
    from repro.sim.fairshare import resolve_sim_backend

    # CPU: auto keeps the router engines' contract, pallas interprets
    assert resolve_sim_backend("auto") == (
        "jax" if jax.config.jax_enable_x64 else "numpy")
    assert resolve_sim_backend("pallas") == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_sim_backend("auto") == "jax"
    assert resolve_sim_backend("numpy") == "numpy"
    with pytest.raises(ValueError, match="float64"):
        resolve_sim_backend("pallas")


def test_cli_refuses_pallas_on_a_tpu(monkeypatch, tmp_path, capsys):
    import jax
    from repro.experiments.run import main

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rc = main(["--suite", "sim", "--sim-backend", "pallas",
               "--out", str(tmp_path)])
    assert rc == 2
    assert "Mosaic" in capsys.readouterr().err
    assert not (tmp_path / "sim.json").exists()


# ------------------------------------------------------------- event loop ----


def test_single_flow_fct_closed_form():
    """Uncontended FCT == bytes / min(cap, bottleneck) + path alpha."""
    router = make_router(MPHX_SMALL, backend="numpy")
    res = simulate_flows(router, [FlowSpec(0, 5, 1 << 24)])
    inc = res.incidence
    rate = min(MPHX_SMALL.port_gbps, float(inc.bottleneck_gbps()[0]))
    closed = (1 << 24) / gbps_to_Bps(rate) + float(path_latency(inc)[0])
    assert res.fct_s[0] == pytest.approx(closed, rel=1e-12)
    assert not res.stalled.any()


def test_fair_sharing_doubles_fct():
    """Two identical flows forced over the same single-path route finish
    in twice the solo time (minus nothing: serial fair share)."""
    router = make_router(MPHX_SMALL, backend="numpy")
    solo = simulate_flows(router, [FlowSpec(0, 1, 1 << 24)],
                          rate_cap_gbps=1600.0)
    both = simulate_flows(router, [FlowSpec(0, 1, 1 << 24),
                                   FlowSpec(0, 1, 1 << 24)],
                          rate_cap_gbps=1600.0)
    t_solo = float(solo.transfer_s()[0])
    assert both.transfer_s() == pytest.approx([2 * t_solo, 2 * t_solo],
                                              rel=1e-9)


def test_staggered_arrivals():
    """A flow arriving halfway through another gets the leftover share;
    total bytes conserve on every edge."""
    router = make_router(MPHX_SMALL, backend="numpy")
    size = 1 << 24
    t_half = size / gbps_to_Bps(800.0) / 2
    res = simulate_flows(router, [FlowSpec(0, 1, size),
                                  FlowSpec(0, 1, size, start_s=t_half)],
                         rate_cap_gbps=800.0)
    assert res.finish_s[1] > res.finish_s[0]
    # conservation: edge bytes == sum over flows of bytes * frac
    expect = res.incidence.loads(np.full(2, size))  # "rate"=bytes trick
    assert np.allclose(res.edge_bytes, expect, rtol=1e-9)


def test_simulate_demands_row_keys_and_delivered():
    router = make_router(MPHX_SMALL, backend="numpy")
    row = simulate_demands(router, neighbor_shift_demands(MPHX_SMALL, 800.0),
                           200e-6)
    assert {"sim_flows", "sim_delivered_fraction", "fct_p50_us",
            "fct_p99_us", "slowdown_mean", "sim_stalled"} <= set(row)
    # shift @ 0.5 load saturates the single minimal path 4x over
    assert row["sim_delivered_fraction"] == pytest.approx(0.25, rel=1e-6)
    assert row["sim_stalled"] == 0


def test_load_sweep_simulate_columns():
    rows = load_sweep(MPHX_SMALL, uniform_demands, mode="minimal",
                      load_fractions=(0.5, 1.0), backend="numpy",
                      simulate=True, flow_time_s=100e-6)
    for r in rows:
        assert "fct_p50_us" in r and "sim_delivered_fraction" in r
        assert r["sim_delivered_fraction"] <= 1.0 + 1e-9
    # uncontended level: slowdown exactly 1
    assert rows[0]["slowdown_mean"] == pytest.approx(1.0, abs=1e-6)


# ------------------------------------------------- latency satellite fix ----


def test_latency_under_load_uses_router_hops():
    """Graph-engine router supplies measured mean hops: on a fat-tree the
    heuristic avg_hops-2 over-counts (it was tuned for MPHX)."""
    ft = ThreeTierFatTree(radix=8, nics=128, name="FT3 (small)")
    router = GraphRouter(ft, backend="numpy")
    with_router = latency_under_load(ft, 0.5, router=router)
    heuristic = latency_under_load(ft, 0.5)
    assert with_router != heuristic
    measured = router.mean_switch_hops()
    base = latency_under_load(ft, 0.0, router=router)
    expect = base + measured * DEFAULT_NET.t_switch * 0.5 / 0.5
    assert with_router == pytest.approx(expect, rel=1e-12)


def test_mean_switch_hops_consistent_across_engines():
    """On untrunked MPHX the graph engine's NIC-weighted measured mean
    equals the array engine's closed form."""
    arr = VectorizedHyperXRouter(MPHX_SMALL)
    gr = GraphRouter(MPHX_SMALL, backend="numpy")
    assert gr.mean_switch_hops() == pytest.approx(arr.mean_switch_hops(),
                                                  rel=1e-12)
    assert arr.mean_switch_hops() == pytest.approx(
        MPHX_SMALL.avg_hops() - 2.0, rel=1e-12)


# ---------------------------------------------------------------- spraying ----


def test_per_plane_bytes_matches_split_chunks():
    cfg = SprayConfig(n_planes=4)
    sizes = [0, 1, cfg.chunk_bytes, cfg.chunk_bytes + 1,
             5 * cfg.chunk_bytes + 17, 1 << 24]
    got = _per_plane_bytes(np.array(sizes, dtype=np.float64), cfg)
    for i, s in enumerate(sizes):
        assert got[i].tolist() == pytest.approx(split_chunks(s, cfg))


def test_spray_sim_matches_planes_closed_form():
    cfg = SprayConfig(n_planes=2)
    size = 10 << 20
    res = simulate_sprayed(MPHX_SMALL, [FlowSpec(0, 5, size)], cfg=cfg)
    expect = spray_completion_time(size, MPHX_SMALL.nic_bw_gbps, cfg)
    assert (res.completion_s[0] - res.latency_s[0]
            == pytest.approx(expect, rel=1e-12))


def test_spray_sim_skewed_plane():
    cfg = SprayConfig(n_planes=2)
    size = 10 << 20
    skew = [1.0, 1.5]
    res = simulate_sprayed(MPHX_SMALL, [FlowSpec(0, 5, size)], cfg=cfg,
                           plane_skew=skew)
    expect = spray_completion_time(size, MPHX_SMALL.nic_bw_gbps, cfg, skew)
    assert (res.completion_s[0] - res.latency_s[0]
            == pytest.approx(expect, rel=1e-12))


def test_spray_sim_dead_plane_resprays():
    """One dead plane: bytes re-spray over survivors (chunk overhead off
    so the re-spray accounting matches planes.py exactly)."""
    cfg = SprayConfig(n_planes=2, per_chunk_overhead_s=0.0)
    size = 10 << 20
    skew = [1.0, math.inf]
    res = simulate_sprayed(MPHX_SMALL, [FlowSpec(0, 5, size)], cfg=cfg,
                           plane_skew=skew)
    expect = spray_completion_time(size, MPHX_SMALL.nic_bw_gbps, cfg, skew)
    assert (res.completion_s[0] - res.latency_s[0]
            == pytest.approx(expect, rel=1e-12))
    # dead plane carried nothing
    assert res.per_plane_bytes[0, 1] == 0.0
    assert res.per_plane_bytes[0, 0] == size


# ------------------------------------------------------------- collectives ----


def test_collective_sim_brackets_analytic():
    """Measured collectives land within a small factor of the alpha-beta
    closed forms (>= 1x: the fabric cannot beat wire speed + rounding)."""
    router = make_router(MPHX_SMALL, backend="numpy")
    for kind in ("allreduce_ring", "allgather_ring", "alltoall"):
        row = simulate_collective(MPHX_SMALL, kind, 1 << 24, router=router)
        assert row["measured_us"] > 0
        ratio = row["measured_over_analytic"]
        assert 0.9 <= ratio <= 5.0, (kind, ratio)


def test_collective_sim_unknown_kind():
    with pytest.raises(ValueError, match="unknown collective"):
        simulate_collective(MPHX_SMALL, "bcast", 1 << 20)


# ----------------------------------------------------------------- failures ----


def test_parse_failure_spec():
    s = parse_failure_spec("link:0.05,plane:1,seed:3")
    assert s == FailureSpec(link_fraction=0.05, planes_down=1, seed=3)
    assert s.label() == "link:0.05,plane:1"
    assert parse_failure_spec("switch:0.1").switch_fraction == 0.1
    with pytest.raises(ValueError, match="unknown failure key"):
        parse_failure_spec("nic:0.5")
    with pytest.raises(ValueError, match="key:value"):
        parse_failure_spec("link=0.5")
    with pytest.raises(ValueError):
        FailureSpec(link_fraction=1.5)


def test_degrade_graph_removes_links_deterministically():
    g = DF_SMALL.build_graph()
    spec = FailureSpec(link_fraction=0.2, seed=7)
    d1 = degrade_graph(g, spec)
    d2 = degrade_graph(g, spec)
    assert d1.failed_links == d2.failed_links > 0
    assert d1.graph.total_links() == pytest.approx(
        g.total_links() - d1.failed_links)
    # node ids preserved under link-only failures
    assert np.array_equal(d1.node_map, np.arange(g.n_switches))


def test_degrade_graph_switch_failures_compact():
    g = DF_SMALL.build_graph()
    d = degrade_graph(g, FailureSpec(switch_fraction=0.2, seed=1))
    assert len(d.failed_switches) > 0
    assert d.graph.n_switches == g.n_switches - len(d.failed_switches)
    assert len(d.graph.nic_nodes) < len(g.nic_nodes)
    # surviving ids are a clean renumbering
    alive = d.node_map[d.node_map >= 0]
    assert np.array_equal(np.sort(alive), np.arange(d.graph.n_switches))


def test_degraded_router_reroutes():
    spec = FailureSpec(link_fraction=0.1, seed=0)
    router, dg = degraded_router(DF_SMALL, spec)
    dem = graph_uniform_demands(DF_SMALL, 800.0, graph=dg.graph)
    ll = router.route(dem, "adaptive")
    assert np.isfinite(ll.max_utilization())
    # fewer links, same demand -> at least as hot
    healthy = GraphRouter(DF_SMALL, backend="numpy").route(
        graph_uniform_demands(DF_SMALL, 800.0), "adaptive")
    assert ll.max_utilization() >= healthy.max_utilization() - 1e-9


def test_failure_throughput_and_recovery_curve():
    spec = parse_failure_spec("link:0.05,seed:1")
    build = lambda t, o, g: graph_uniform_demands(t, o, graph=g)
    ft = failure_throughput(MPHX_SMALL, build, spec, 800.0, mode="minimal")
    assert 0 < ft["throughput_retained"] <= 1.0
    assert ft["degraded_max_util"] >= ft["healthy_max_util"] - 1e-9
    phases = recovery_curve(MPHX_SMALL, build, spec, 800.0, mode="minimal")
    names = [p["phase"] for p in phases]
    assert names == ["healthy", "failed", "rerouted"]
    # pre-reroute stall cuts delivery below (or at) healthy
    assert phases[1]["delivered_fraction"] <= phases[0]["delivered_fraction"]
    assert phases[1]["stalled_share"] > 0


def test_plane_capacity_factor():
    assert plane_capacity_factor(MPHX_SMALL, FailureSpec(planes_down=1)) \
        == pytest.approx(0.5)
    with pytest.raises(ValueError):
        plane_capacity_factor(MPHX_SMALL, FailureSpec(planes_down=2))


def test_stalled_flows_marked_not_spun():
    """A flow whose only path crosses a fully-failed edge stalls with
    finish = inf instead of looping."""
    inc = FlowIncidence(np.array([0], dtype=np.int64),
                        np.array([0], dtype=np.int64),
                        np.array([1.0]), 1, np.array([0.0]))
    res = simulate_incidence(inc, np.array([1e9]), np.array([100.0]))
    assert res.stalled[0]
    assert not np.isfinite(res.finish_s[0])


# ----------------------------------------------------- suites / CLI / docs ----


def test_sim_suite_artifact(tmp_path):
    from repro.experiments.simsuite import run_sim_suite

    payload = run_sim_suite(outdir=str(tmp_path),
                            topo_names=["mphx-2p-8x8"],
                            scenario_names=["uniform"],
                            load_fractions=(0.5,))
    disk = json.loads((tmp_path / "sim.json").read_text())
    assert disk == payload
    assert disk["schema_version"] == SCHEMA_VERSION
    assert disk["suite"] == "sim"
    assert disk["params"]["all_steady_checks_agree_1e-6"] is True
    kinds = {r.get("kind") for r in disk["rows"]}
    assert {"steady_check", "fct", "collective"} <= kinds
    checks = [r for r in disk["rows"] if r.get("kind") == "steady_check"]
    assert all(r["max_abs_util_diff"] < 1e-6 for r in checks)
    assert (tmp_path / "sim.md").read_text().startswith("# Flow-level")


def test_failures_suite_artifact_and_cli(tmp_path):
    from repro.experiments.run import main

    rc = main(["--suite", "failures", "--out", str(tmp_path),
               "--topos", "mphx-2p-8x8", "--scenarios", "uniform",
               "--failures", "link:0.1", "--failure-mode", "minimal"])
    assert rc == 0
    disk = json.loads((tmp_path / "failures.json").read_text())
    assert disk["schema_version"] == SCHEMA_VERSION
    assert disk["suite"] == "failures"
    assert disk["params"]["failure_specs"] == ["link:0.1"]
    kinds = [r.get("kind") for r in disk["rows"]]
    assert "throughput" in kinds and "recovery" in kinds


def test_failures_suite_array_engine_skips(tmp_path, capsys):
    from repro.experiments.simsuite import run_failures_suite

    payload = run_failures_suite(outdir=str(tmp_path),
                                 topo_names=["mphx-2p-8x8"],
                                 engine="array")
    assert payload["params"]["n_rows"] == 0
    skipped = [r for r in payload["rows"] if r.get("skipped")]
    assert skipped and "re-route" in skipped[0]["reason"]
    assert "re-route" in capsys.readouterr().err


def test_failures_cli_bad_spec(tmp_path):
    from repro.experiments.run import main

    rc = main(["--suite", "failures", "--out", str(tmp_path),
               "--failures", "bogus:1"])
    assert rc == 2


def test_sweep_suite_simulate_flag(tmp_path):
    from repro.experiments.sweep import run_sweep_suite

    payload = run_sweep_suite(outdir=str(tmp_path),
                              topo_names=["mphx-2p-8x8"],
                              scenario_names=["uniform"],
                              modes=["minimal", "adaptive"],
                              load_fractions=(0.5,), simulate=True)
    routed = [r for r in payload["rows"] if not r.get("skipped")]
    minimal = [r for r in routed if r["mode"] == "minimal"]
    adaptive = [r for r in routed if r["mode"] == "adaptive"]
    assert all("fct_p50_us" in r for r in minimal)
    assert all("fct_p50_us" not in r for r in adaptive)


def test_docs_smoke_registers_simulation_doc():
    """CI's docs smoke must cover docs/simulation.md (and the doc must
    actually quote runnable bash blocks)."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    smoke = open(os.path.join(repo, "scripts", "docs_smoke.py")).read()
    assert "simulation.md" in smoke
    doc = open(os.path.join(repo, "docs", "simulation.md")).read()
    assert "```bash" in doc
    assert "--suite sim" in doc and "--suite failures" in doc


def test_bench_flow_sim_writes_artifact():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(repo, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.bench_flow_sim()
    path = os.path.join(repo, "results", "BENCH_flow_sim.json")
    rec = json.load(open(path))
    assert all(v["within_1e-6"]
               for v in rec["steady_state_agreement"].values())
    assert rec["single_flow_fct"]["matches_closed_form"]
    assert rec["failure_sweep"]
