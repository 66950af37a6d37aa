"""The benchmark's float64 reference and generators against the repo's
golden fixture and the program's own generators, at ``mphx-2p-8x8``,
and its DAL incidence against DAL's paths enumerated one by one."""

import itertools
import json
import math
import os

import numpy as np
import pytest

import compare
import reference as ref
from gen import (Plane, Traffic, hotspot_demands, neighbor_shift_demands,
                 uniform_demands)

GOLDEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tests", "golden",
    "fairshare_golden.json")
NET = {"t_nic": 6e-07, "t_switch": 3e-07, "t_prop_per_hop": 5e-08}


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def plane(small_config):
    return Plane.from_config(small_config)


def _sorted(x):
    return np.sort(np.asarray(x, dtype=np.float64))[::-1]


@pytest.mark.parametrize("cell,build", [
    ("array/mphx-2p-8x8/uniform", uniform_demands),
    ("array/mphx-2p-8x8/neighbor_shift", neighbor_shift_demands),
])
@pytest.mark.parametrize("load", ["0.5", "1.2"])
def test_reference_matches_golden_cells(golden, plane, cell, build, load):
    want = golden["cells"][cell]["loads"][load]
    offered = float(load) * plane.nic_bw_gbps
    src, dst, gbps = build(plane, offered)
    inc = ref.incidence(plane, src, dst)
    assert inc.n_flows == want["n_flows"]
    assert inc.flow.size == want["nnz"]
    rates = ref.max_min_rates(inc, gbps, np.ones(inc.n_flows, dtype=bool),
                              1e-12 * max(inc.max_capacity, gbps.max()))
    np.testing.assert_allclose(rates, want["rates_gbps"], rtol=1e-12)
    loads = ref.edge_loads(inc, rates)
    np.testing.assert_allclose(
        _sorted(loads[loads > 0]),
        _sorted(list(want["link_loads_gbps_nonzero"].values())), rtol=1e-12)

    size = gbps * 1e9 / 8.0 * golden["flow_time_s"]
    res = ref.simulate(inc, size, gbps, None, NET)
    summ = ref.fct_summary(
        res.fct_s, res.finish_s, size, gbps, res.latency_s,
        ref.bottleneck_gbps(inc.flow, inc.edge, inc.frac, inc.capacity,
                            inc.n_flows),
        res.makespan_s, float(gbps.sum()))
    fct = want["fct"]
    assert res.n_epochs == fct["sim_epochs"]
    assert summ["stalled"] == fct["sim_stalled"]
    for q in (50, 95, 99):
        assert round(summ[f"fct_p{q}_s"] * 1e6, 3) == fct[f"fct_p{q}_us"]
    assert round(summ["slowdown_mean"], 4) == fct["slowdown_mean"]
    assert round(summ["slowdown_p99"], 4) == fct["slowdown_p99"]
    assert round(summ["delivered_fraction"], 6) == \
        fct["sim_delivered_fraction"]


def test_reference_matches_golden_staggered_trace(golden, plane):
    want = golden["staggered"]
    src, dst, _ = neighbor_shift_demands(plane, 800.0)
    inc = ref.incidence(plane, src, dst)
    res = ref.simulate(inc, np.array(want["size_bytes"]),
                       np.array(want["rate_caps_gbps"]),
                       np.array(want["start_s"]), NET)
    assert res.n_epochs == want["n_epochs"]
    np.testing.assert_allclose(res.finish_s, want["finish_s"], rtol=1e-12)
    np.testing.assert_allclose(res.fct_s, want["fct_s"], rtol=1e-12)
    assert res.makespan_s == pytest.approx(want["makespan_s"], rel=1e-12)
    eb = res.edge_bytes
    np.testing.assert_allclose(
        _sorted(eb[eb > 0]),
        _sorted(list(want["edge_bytes_nonzero"].values())), rtol=1e-12)


def test_generators_match_the_program(plane):
    """The copies in ``bench/gen.py`` make the demand rows the program's
    generators make (so the yardstick starts where the program is)."""
    from repro.core.hyperx import MPHX
    from repro.core import routing_vec as rv

    topo = MPHX(n=plane.n, p=plane.p, dims=plane.dims)
    offered = 0.9 * plane.nic_bw_gbps
    pairs = [
        (uniform_demands(plane, offered), rv.uniform_demands(topo, offered)),
        (neighbor_shift_demands(plane, offered),
         rv.neighbor_shift_demands(topo, offered)),
        (hotspot_demands(plane, offered, 5, 0.5),
         rv.hotspot_demands(topo, offered, hot=5, hot_fraction=0.5)),
    ]
    for (src, dst, gbps), dem in pairs:
        np.testing.assert_array_equal(src, dem.src)
        np.testing.assert_array_equal(dst, dem.dst)
        np.testing.assert_array_equal(gbps, dem.gbps)


@pytest.mark.parametrize("preset", ["mphx-2p-8x8", "mphx-4p-86x9"])
def test_slot_pairs_decode_the_program_slots(preset):
    """The benchmark's copy of the slot layout names the switch pair that
    the program's own decoder names, for every slot."""
    from repro.core.routing_vec import EdgeIndex
    from repro.experiments.sweep import SWEEP_TOPOLOGIES

    topo = SWEEP_TOPOLOGIES[preset]
    idx = EdgeIndex(topo)
    plane = Plane(topo.n, topo.p, tuple(topo.dims),
                  tuple(topo.links_per_dim), float(topo.nic_bw_gbps))
    pairs = plane.slot_pairs()
    assert pairs.size == idx.n_slots
    step = max(1, idx.n_slots // 4096)
    for slot in range(0, idx.n_slots, step):
        u, v = idx.slot_to_edge(slot)
        assert pairs[slot] == u * plane.S + v, slot


def test_reference_incidence_matches_the_program(plane):
    """The same (flow, edge) entries and edge loads as the program's array
    engine, its edge slots decoded to switch pairs."""
    from repro.core.hyperx import MPHX
    from repro.core.netsim import make_router
    from repro.core.routing_vec import DemandArrays
    from repro.sim.fairshare import flow_incidence

    topo = MPHX(n=plane.n, p=plane.p, dims=plane.dims)
    router = make_router(topo, backend="numpy")
    src, dst, gbps = hotspot_demands(plane, 0.9 * plane.nic_bw_gbps, 3, 0.5)
    got = flow_incidence(router, DemandArrays(src, dst, gbps), "minimal")
    want = ref.incidence(plane, src, dst)
    pairs = plane.slot_pairs()
    assert compare._entry_gap((got.flow, pairs[got.edge], got.frac),
                              (want.flow, want.pair[want.edge],
                               want.frac)) == 0
    assert compare._keyed_gap(pairs, got.loads(gbps), want.pair,
                              ref.edge_loads(want, gbps)) <= 1e-14


@pytest.mark.parametrize("mix", ["hotspot", "uniform", "churn"])
def test_traffic_is_a_function_of_the_seed(plane, mix):
    import run

    spec = run.load_json(os.path.join(run.BENCH, "traffic", mix + ".json"))
    seed = 2**31 + 977          # past 32 signed bits
    a, b = Traffic(spec, plane, seed), Traffic(spec, plane, seed)
    c = Traffic(spec, plane, -seed)
    for k in range(a.pool + 1):
        x, y = a.inputs(k), b.inputs(k)
        for f in ("src", "dst", "gbps", "size_bytes"):
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))
        assert x.load == a.loads[k % a.period]
    # every seed gives the same work in another order or place
    x, z = a.inputs(0), c.inputs(0)
    assert x.src.size == z.src.size
    assert np.isclose(x.gbps.sum(), z.gbps.sum(), rtol=1e-12)


def test_pool_must_hold_whole_cycles(plane):
    with pytest.raises(ValueError, match="pool"):
        Traffic({"pattern": "uniform", "loads": [0.5, 0.9], "pool": 3,
                 "sizes": {"flow_time_s": 1e-4}}, plane, 1)


def _dal_paths(dims, cs, cd) -> "list[list[tuple]]":
    """Every DAL path of one flow, as its switches' coordinates, from the
    documented semantics: each order of the mismatched dimensions, then
    for each mismatched dimension ``i`` and each coordinate ``v`` of it
    that is neither end's, ``v`` along ``i`` first and then every
    mismatched dimension in index order."""
    mism = [i for i in range(len(dims)) if cs[i] != cd[i]]
    paths = []
    for order in itertools.permutations(mism):
        cur = list(cs)
        path = [tuple(cur)]
        for i in order:
            cur[i] = cd[i]
            path.append(tuple(cur))
        paths.append(path)
    for i in mism:
        for v in range(dims[i]):
            if v in (cs[i], cd[i]):
                continue
            cur = list(cs)
            cur[i] = v
            path = [tuple(cs), tuple(cur)]
            for j in range(len(dims)):
                if cur[j] != cd[j]:
                    cur[j] = cd[j]
                    path.append(tuple(cur))
            paths.append(path)
    return paths


@pytest.mark.parametrize("dims,links", [
    ((6,), (5,)),
    ((4, 3), (9, 4)),           # trunked: 3 and 2 links to each neighbour
    ((3, 3, 2), (2, 2, 1)),
])
def test_dal_reference_matches_path_enumeration(dims, links):
    plane = Plane(2, 4, dims, links, 1600.0)
    S = plane.S
    src, dst, _ = uniform_demands(plane, 800.0)
    inc = ref.incidence(plane, src, dst, routing="valiant")
    cs, cd = plane.coords(src), plane.coords(dst)
    want = {}
    for f in range(src.size):
        paths = _dal_paths(dims, tuple(cs[f]), tuple(cd[f]))
        mism = [i for i in range(len(dims)) if cs[f, i] != cd[f, i]]
        assert len(paths) == (math.factorial(len(mism))
                              + sum(dims[i] - 2 for i in mism))
        for path in paths:
            for a, b in zip(path, path[1:]):
                u, v = (int(plane.ids(np.array([c]))[0]) for c in (a, b))
                key = (f, u * S + v)
                want[key] = want.get(key, 0.0) + 1.0 / len(paths)
    got = {(int(f), int(inc.pair[e])): float(x)
           for f, e, x in zip(inc.flow, inc.edge, inc.frac)}
    assert got.keys() == want.keys()
    assert max(abs(got[k] - want[k]) for k in want) <= 1e-15
    # hop capacities as for minimal routing: links / (dims - 1) ports
    for pair, cap in zip(inc.pair, inc.capacity):
        a, b = plane.coords(np.array([pair // S, pair % S]))
        (i,) = np.flatnonzero(a != b)
        assert cap == links[i] / (dims[i] - 1) * plane.port_gbps
    assert inc.n_flows == src.size


@pytest.mark.parametrize("build", [uniform_demands, neighbor_shift_demands])
def test_reference_dal_incidence_matches_the_program(plane, build):
    """The same (flow, edge) entries as the program's array engine in its
    ``valiant`` mode, and shares within rounding."""
    from repro.core.hyperx import MPHX
    from repro.core.netsim import make_router
    from repro.core.routing_vec import DemandArrays
    from repro.sim.fairshare import flow_incidence

    topo = MPHX(n=plane.n, p=plane.p, dims=plane.dims)
    router = make_router(topo, backend="numpy")
    src, dst, gbps = build(plane, 0.9 * plane.nic_bw_gbps)
    got = flow_incidence(router, DemandArrays(src, dst, gbps), "valiant")
    want = ref.incidence(plane, src, dst, routing="valiant")
    pairs = plane.slot_pairs()
    assert compare._entry_gap((got.flow, pairs[got.edge], got.frac),
                              (want.flow, want.pair[want.edge],
                               want.frac)) <= 1e-15


@pytest.mark.parametrize("routing", ["adaptive", "ecmp"])
def test_reference_refuses_routing_it_does_not_know(plane, routing):
    src, dst, _ = uniform_demands(plane, 800.0)
    with pytest.raises(ValueError, match="no static incidence"):
        ref.incidence(plane, src, dst, routing=routing)
