"""65K-scale sim path: BENCH artifact schema, incidence caching, smoke.

Three layers:

  * schema smoke on ``results/BENCH_sim_scale.json`` — the committed
    artifact must pin the >=10x jit speedup at the largest
    all-backends-timed rung (a 65K-NIC Table-2 preset) and three-way
    1e-6 agreement at every rung,
  * the pair-level incidence cache (``IncidenceCacheMixin``): cached
    extraction is byte-identical to the engine walk, repeated flow sets
    walk the engine exactly once (counted by ``incidence.walks``), and
    the batch simulator rides the cache,
  * a slow-marked smoke that actually routes + simulates a 65,536-NIC
    preset through the jit path and cross-checks numpy at 1e-6.
"""

import json
import os

import numpy as np
import pytest

from repro.core.hyperx import MPHX
from repro.core.netsim import make_router
from repro.core.routing_vec import neighbor_shift_demands, uniform_demands
from repro.sim.events import FlowSpec, simulate_flow_batches
from repro.sim.fairshare import flow_incidence, max_min_rates

BENCH = os.path.join(os.path.dirname(__file__), "..", "results",
                     "BENCH_sim_scale.json")

ROW_KEYS = {"preset", "topology", "n_nics", "n_flows", "n_edges", "nnz",
            "n_epochs", "fct_p50_us", "fct_p99_us", "reference_timed",
            "wall_s", "wall_reps_s", "agreement"}


@pytest.fixture(scope="module")
def bench():
    with open(BENCH) as f:
        return json.load(f)


def test_bench_artifact_schema(bench):
    assert bench["schema_version"] == 1
    assert bench["bench"] == "sim_scale"
    assert set(bench["backends"]) == {"numpy", "jax", "pallas"}
    assert bench["workload"]["scenario"] == "neighbor_shift"
    for row in bench["scales"]:
        assert ROW_KEYS <= set(row)
        for b in bench["backends"]:
            assert row["wall_s"][b] > 0
            assert len(row["wall_reps_s"][b]) >= 1
        for agree in row["agreement"].values():
            assert agree["within_1e-6"] is True
            assert agree["max_rel_link_load_err"] < 1e-6
            assert agree["max_rel_fct_pct_err"] < 1e-6
        if row["reference_timed"]:
            assert row["speedup_jax"] > 0
    assert bench["all_within_1e-6"] is True


def test_bench_pins_10x_at_65k(bench):
    largest = {r["preset"]: r for r in bench["scales"]}[
        bench["largest_common_scale"]]
    assert largest["reference_timed"] is True
    assert largest["n_nics"] >= 65536          # a Table-2 65K-NIC fabric
    assert largest["speedup_jax"] >= 10.0
    assert bench["speedup_at_largest_common_scale"] == \
        largest["speedup_jax"]
    assert bench["meets_10x"] is True
    # the 65K sweep rows ran through the jit path and delivered
    for preset, row in bench["sweep_65k"].items():
        assert row["n_nics"] >= 65536, preset
        assert row["sim_delivered_fraction"] == 1.0


# ------------------------------------------------- incidence caching ----


def _small_router():
    return make_router(MPHX(n=2, p=8, dims=(8, 8)), backend="numpy")


def test_cached_incidence_identical_to_engine_walk():
    router = _small_router()
    dem = neighbor_shift_demands(router.topo, 800.0)
    flow, edge, frac = router.incidence(dem, "minimal")
    cf, ce, cfr = router.incidence_cached(dem, "minimal")
    assert np.array_equal(cf, flow)
    assert np.array_equal(ce, edge)
    assert np.array_equal(cfr, frac)


def test_repeated_flow_sets_walk_engine_once():
    router = _small_router()
    dem = uniform_demands(router.topo, 400.0)
    assert router.metrics.value("incidence.walks") == 0
    for _ in range(3):
        flow_incidence(router, dem, "minimal", cached=True)
    # one walk covered all three extractions: every (src, dst) pair was
    # cached on the first pass
    assert router.metrics.value("incidence.walks") == 1
    # a new mode is a different path spread: exactly one more walk
    flow_incidence(router, dem, "valiant", cached=True)
    flow_incidence(router, dem, "valiant", cached=True)
    assert router.metrics.value("incidence.walks") == 2
    router.reset_incidence_cache()
    flow_incidence(router, dem, "minimal", cached=True)
    assert router.metrics.value("incidence.walks") == 3


def test_partial_overlap_walks_only_new_pairs():
    router = _small_router()
    a = neighbor_shift_demands(router.topo, 800.0)
    flow_incidence(router, a, "minimal", cached=True)
    calls = router.metrics.value("incidence.walks")
    # a flow set whose pairs are a subset of what's cached: no new walk
    sub = neighbor_shift_demands(router.topo, 800.0)
    flow_incidence(router, sub, "minimal", cached=True)
    assert router.metrics.value("incidence.walks") == calls


def test_batch_simulator_rides_the_cache():
    router = _small_router()
    batches = [[FlowSpec(src=0, dst=1, size_bytes=1 << 20),
                FlowSpec(src=1, dst=2, size_bytes=1 << 20)]
               for _ in range(4)]
    res = simulate_flow_batches(router, batches, rate_cap_gbps=200.0)
    assert len(res.results) == 4
    # 4 identical phases, 1 engine walk
    assert router.metrics.value("incidence.walks") == 1


# ----------------------------------------------------- 65K sim smoke ----


@pytest.mark.slow
def test_65k_preset_sim_smoke():
    from repro.experiments.sweep import SWEEP_TOPOLOGIES

    topo = SWEEP_TOPOLOGIES["mphx-8p-256"]
    assert topo.n_nics == 65536
    router = make_router(topo, backend="numpy")
    dem = neighbor_shift_demands(topo, 0.9 * topo.nic_bw_gbps)
    inc = flow_incidence(router, dem, "minimal")
    caps = np.asarray(dem.gbps)
    ref = max_min_rates(inc, caps, backend="numpy")
    jit = max_min_rates(inc, caps, backend="jax")
    scale = max(float(caps.max()), 1.0)
    assert np.abs(jit - ref).max() <= 1e-6 * scale

    from repro.sim.events import simulate_incidence
    rng = np.random.default_rng(7)
    size = rng.uniform(0.2, 1.0, inc.n_flows) * (1 << 24)
    start = rng.uniform(0.0, 200e-6, inc.n_flows)
    res = simulate_incidence(inc, size, caps, start_s=start, backend="jax")
    assert np.isfinite(res.finish_s).all()
    assert res.n_epochs > inc.n_flows      # staggered arrivals re-solve
    np.testing.assert_allclose(
        res.edge_bytes.sum(), (size * inc.switch_hops()).sum(), rtol=1e-9)
