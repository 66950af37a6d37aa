"""Vectorized routing engine + experiment sweep subsystem.

The load-bearing guarantee: the batched array engine reproduces the legacy
dict-based router's link loads bit-for-bit (well, to 1e-9 — float summation
order differs) on small MPHX instances, for every traffic pattern and for
both deterministic modes.  Plus smoke tests of the sweep runner's JSON and
markdown artifacts.
"""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from repro.experiments.artifacts import SCHEMA_VERSION
from repro.core import MPHX
from repro.core.routing import (HyperXRouter, bit_complement_traffic,
                                neighbor_shift_traffic, route_demands,
                                uniform_traffic)
from repro.core.routing_vec import (EdgeIndex, VectorizedHyperXRouter,
                                    bit_complement_demands, demands_from_dict,
                                    get_backend, neighbor_shift_demands,
                                    ring_demands, transpose_demands,
                                    uniform_demands)
from repro.core.netsim import load_sweep, pattern_throughput
from repro.experiments import (SCENARIOS, available_scenarios, get_scenario,
                               markdown_table, run_sweep_suite,
                               run_table2_suite)

# small instances where the legacy router never subsamples paths
# (m! <= 24 orderings, deroutes <= 16), so equivalence is exact
SMALL_TOPOS = [
    MPHX(n=2, p=4, dims=(4, 4)),
    MPHX(n=2, p=8, dims=(8, 8)),
    MPHX(n=1, p=4, dims=(4, 3)),      # asymmetric dims
    MPHX(n=2, p=3, dims=(3, 3, 3)),   # 3D: 6 orderings
]

PATTERNS = [
    ("uniform", uniform_traffic, uniform_demands),
    ("neighbor_shift", neighbor_shift_traffic, neighbor_shift_demands),
    ("bit_complement", bit_complement_traffic, bit_complement_demands),
]


def _edge_diff(legacy_ll, array_ll) -> float:
    ld = {k: v for k, v in legacy_ll.loads.items() if v > 0}
    vd = array_ll.to_dict()
    keys = set(ld) | set(vd)
    return max(abs(ld.get(k, 0.0) - vd.get(k, 0.0)) for k in keys)


# ---------------------------------------------------------------- engine ----


@pytest.mark.parametrize("topo", SMALL_TOPOS, ids=lambda t: t.name)
@pytest.mark.parametrize("pattern", [p[0] for p in PATTERNS])
@pytest.mark.parametrize("mode", ["minimal", "valiant"])
def test_vectorized_matches_legacy(topo, pattern, mode):
    _, dict_fn, arr_fn = next(p for p in PATTERNS if p[0] == pattern)
    demands = dict_fn(topo, 1600.0)
    legacy = HyperXRouter(topo).route(demands, mode=mode)
    vec = VectorizedHyperXRouter(topo).route(arr_fn(topo, 1600.0), mode=mode)
    assert _edge_diff(legacy, vec) < 1e-9
    assert vec.max_utilization() == pytest.approx(
        legacy.max_utilization(), abs=1e-9)
    assert vec.saturation_throughput() == pytest.approx(
        legacy.saturation_throughput(1600.0), abs=1e-9)


def test_demand_builders_match_dict_generators():
    topo = MPHX(n=2, p=4, dims=(4, 4))
    for _, dict_fn, arr_fn in PATTERNS:
        assert arr_fn(topo, 800.0).to_dict() == pytest.approx(
            dict_fn(topo, 800.0))


def test_route_demands_dispatcher_equivalence():
    topo = MPHX(n=2, p=4, dims=(4, 4))
    demands = neighbor_shift_traffic(topo, 1600.0)
    a = route_demands(topo, demands, mode="minimal", engine="dict")
    b = route_demands(topo, demands, mode="minimal", engine="array")
    assert b.max_utilization() == pytest.approx(a.max_utilization(), abs=1e-9)
    with pytest.raises(ValueError):
        route_demands(topo, demands, engine="quantum")


def test_edge_index_roundtrips():
    topo = MPHX(n=4, p=86, dims=(86, 9), links_per_dim=(85, 85))
    idx = EdgeIndex(topo)
    ids = np.arange(topo.switches_per_plane, dtype=np.int64)
    coords = idx.ids_to_coords(ids)
    assert np.array_equal(idx.coords_to_ids(coords), ids)
    # spot-check slot -> edge against topo coordinates
    u, v = idx.slot_to_edge(idx.n_slots - 1)
    cu, cv = topo.id_to_coord(u), topo.id_to_coord(v)
    assert sum(a != b for a, b in zip(cu, cv)) <= 1


def test_edge_slots_match_switch_graph():
    """Every loaded edge slot must be a real link of the built multigraph,
    with the same trunking multiplicity the capacity model assumes."""
    topo = MPHX(n=1, p=4, dims=(4, 3))
    us, vs, mult = topo.build_graph().directed_edge_arrays()
    graph_edges = {(u, v): m for u, v, m in zip(us, vs, mult)}
    ll = VectorizedHyperXRouter(topo).route(
        uniform_demands(topo, 1600.0), "valiant")
    idx = ll.index
    for slot in np.nonzero(np.asarray(ll.loads))[0]:
        u, v = idx.slot_to_edge(int(slot))
        assert (u, v) in graph_edges
        assert idx.capacity[slot] == pytest.approx(
            graph_edges[(u, v)] * topo.port_gbps)


def test_hotspot_to_dict_accumulates_duplicates():
    """hotspot lists (s, hot) twice (uniform + incast part); to_dict must
    sum them, not drop one."""
    from repro.core.routing_vec import hotspot_demands

    topo = MPHX(n=2, p=4, dims=(4, 4))
    d = hotspot_demands(topo, 800.0)
    assert sum(d.to_dict().values()) == pytest.approx(d.total_gbps())


def test_adaptive_improves_adversarial():
    """Parallel UGAL must beat minimal on the §5.2 neighbor-shift pattern."""
    topo = MPHX(n=2, p=8, dims=(8, 8))
    d = neighbor_shift_demands(topo, 1600.0)
    router = VectorizedHyperXRouter(topo)
    mn = router.route(d, "minimal").max_utilization()
    ad = router.route(d, "adaptive").max_utilization()
    assert ad < mn / 2


def test_adaptive_conserves_demand():
    topo = MPHX(n=2, p=4, dims=(4, 4))
    d = neighbor_shift_demands(topo, 1600.0)
    ll = VectorizedHyperXRouter(topo).route(d, "adaptive")
    # every quantum lands on a path of >= 1 hops: total load >= total demand
    assert ll.total_load() >= d.total_gbps() - 1e-6


def test_jax_backend_matches_numpy():
    jax = pytest.importorskip("jax")
    topo = MPHX(n=2, p=4, dims=(4, 4))
    d = uniform_demands(topo, 1600.0)
    ref = VectorizedHyperXRouter(topo, backend="numpy").route(d, "minimal")
    old = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", True)
        jx = VectorizedHyperXRouter(topo, backend="jax").route(d, "minimal")
        assert np.allclose(np.asarray(jx.loads), ref.loads, atol=1e-9)
    finally:
        jax.config.update("jax_enable_x64", old)
    assert get_backend("numpy")[0] == "numpy"


# ------------------------------------------------------------- scenarios ----


def test_scenario_registry_complete():
    expected = {"uniform", "neighbor_shift", "bit_complement", "transpose",
                "hotspot", "allreduce_ring", "allgather_ring", "alltoall"}
    assert expected <= set(SCENARIOS)
    with pytest.raises(KeyError):
        get_scenario("nope")


def test_scenarios_applicability_and_sanity():
    square = MPHX(n=2, p=4, dims=(4, 4))
    skewed = MPHX(n=1, p=4, dims=(4, 3))
    assert "transpose" in available_scenarios(square)
    assert "transpose" not in available_scenarios(skewed)
    for name in available_scenarios(square):
        d = SCENARIOS[name].builder(square, 1600.0)
        assert d.n > 0
        assert np.all(d.src != d.dst)
        assert np.all(d.gbps > 0)


def test_transpose_requires_square():
    with pytest.raises(ValueError):
        transpose_demands(MPHX(n=1, p=4, dims=(4, 3)), 1600.0)


def test_collective_scenarios_scale_with_spray():
    """Collective schedules charge the plane fabric at >= the perfect-spray
    rate (whole-chunk rounding can only concentrate load)."""
    topo = MPHX(n=4, p=4, dims=(4, 4))
    plain = ring_demands(topo, 1600.0)
    coll = SCENARIOS["allreduce_ring"].builder(topo, 1600.0)
    assert np.all(coll.gbps >= plain.gbps - 1e-9)


def test_ring_collective_scenarios_differ():
    """allreduce_ring charges the spray schedule on payload/m per-step
    chunks; allgather_ring moves the full payload per step — on a topology
    where the small chunk sprays onto one plane they must differ."""
    topo = MPHX(n=4, p=4, dims=(4, 4))
    ar = SCENARIOS["allreduce_ring"].builder(topo, 1600.0)
    ag = SCENARIOS["allgather_ring"].builder(topo, 1600.0)
    assert ar.gbps.sum() > ag.gbps.sum()


# ----------------------------------------------------------------- sweeps ----


def test_load_sweep_zero_first_load():
    """A sweep starting at 0 offered load must not divide by zero."""
    topo = MPHX(n=2, p=8, dims=(8, 8))
    rows = load_sweep(topo, neighbor_shift_demands, mode="minimal",
                      load_fractions=(0.0, 0.5, 1.0))
    assert rows[0]["max_util"] == 0.0
    assert rows[0]["throughput_fraction"] == 1.0
    assert rows[0]["latency_us"] > 0
    assert rows[2]["max_util"] == pytest.approx(2 * rows[1]["max_util"])


def test_load_sweep_monotone_and_linear():
    topo = MPHX(n=2, p=8, dims=(8, 8))
    rows = load_sweep(topo, neighbor_shift_demands, mode="minimal",
                      load_fractions=(0.25, 0.5, 1.0))
    utils = [r["max_util"] for r in rows]
    assert utils == sorted(utils)
    # fixed path spread -> utilization linear in offered load
    assert utils[1] == pytest.approx(2 * utils[0], rel=1e-9)
    sat = [r for r in rows if r["max_util"] >= 1.0]
    assert all(r["latency_us"] is None for r in sat)
    ok = [r for r in rows if r["max_util"] < 1.0]
    assert all(r["latency_us"] > 0 for r in ok)


def test_pattern_throughput_keys():
    topo = MPHX(n=2, p=4, dims=(4, 4))
    rep = pattern_throughput(topo, uniform_demands(topo, 1600.0), "minimal")
    assert {"max_util", "mean_util", "throughput_fraction",
            "total_load_gbps"} <= set(rep)


def test_table2_suite_artifact(tmp_path):
    payload = run_table2_suite(outdir=str(tmp_path))
    assert (tmp_path / "table2.json").exists()
    assert (tmp_path / "table2.md").exists()
    disk = json.loads((tmp_path / "table2.json").read_text())
    assert disk["schema_version"] == SCHEMA_VERSION
    assert disk["suite"] == "table2"
    assert len(disk["rows"]) == 8
    by_name = {r["topology"]: r for r in disk["rows"]}
    assert by_name["8-Plane 1D HyperX"]["diameter"] == 3
    # the reproduction matches the paper's published cost column
    assert all(r["cost_matches_paper"] for r in disk["rows"]
               if "cost_matches_paper" in r)
    assert payload["rows"] == disk["rows"]


def test_sweep_suite_artifact(tmp_path):
    payload = run_sweep_suite(
        outdir=str(tmp_path), topo_names=["mphx-2p-8x8"],
        scenario_names=["uniform", "neighbor_shift"],
        modes=["minimal"], load_fractions=(0.5, 1.0))
    disk = json.loads((tmp_path / "sweep.json").read_text())
    assert disk["suite"] == "sweep"
    assert disk["schema_version"] == SCHEMA_VERSION
    assert len(disk["rows"]) == 2 * 2  # 2 scenarios x 2 load levels
    for r in disk["rows"]:
        assert {"topology", "scenario", "mode", "engine", "offered_fraction",
                "max_util", "throughput_fraction"} <= set(r)
    assert (tmp_path / "sweep.md").read_text().startswith("# Latency")
    assert payload["rows"] == disk["rows"]


def test_cli_main(tmp_path):
    from repro.experiments.run import main

    rc = main(["--suite", "sweep", "--out", str(tmp_path),
               "--topos", "mphx-2p-8x8", "--scenarios", "uniform",
               "--modes", "minimal", "--loads", "1.0"])
    assert rc == 0
    assert (tmp_path / "sweep.json").exists()


def test_compile_cache_placement(monkeypatch, tmp_path):
    import jax
    from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: JAX reads the variable itself, and the
        # helper sets no other directory
        placed = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        jax.config.update("jax_compilation_cache_dir", placed)
        assert enable_compile_cache() == placed
        assert jax.config.jax_compilation_cache_dir == placed
        # otherwise: a fixed .jax_cache at the checkout root
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert CHECKOUT_CACHE_DIR == Path(root) / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_markdown_table_formatting():
    md = markdown_table([{"a": 1, "b": None}, {"a": 2.5, "c": True}],
                        columns=["a", "b", "c"])
    lines = md.strip().splitlines()
    assert lines[0] == "| a | b | c |"
    assert "—" in lines[2] and "yes" in lines[3]
