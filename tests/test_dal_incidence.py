"""DAL (``valiant``) incidence of the MPHX array engine.

On a 1D plane no two DAL paths of a flow share an edge, so the engine
writes each entry straight into its place (span ``incidence.place``)
instead of coalescing duplicates; the result must be the coalesce's,
array for array and bit for bit.  With more dimensions the paths meet
and the coalesce stays.
"""

import itertools
import math

import numpy as np
import pytest

from repro.core.hyperx import MPHX
from repro.core.routing_vec import (DemandArrays, VectorizedHyperXRouter,
                                    hotspot_demands, neighbor_shift_demands,
                                    uniform_demands)
from repro.sim.events import simulate_incidence
from repro.sim.fairshare import flow_incidence
from repro.telemetry import collecting

PLANES_1D = {
    "6": MPHX(n=2, p=6, dims=(6,)),
    "16": MPHX(n=2, p=16, dims=(16,)),
    "6-trunked": MPHX(n=2, p=6, dims=(6,), links_per_dim=(10,)),
}
PLANES_MD = {
    "4x3": MPHX(n=2, p=4, dims=(4, 3), links_per_dim=(3, 4)),
    "3x3x2": MPHX(n=2, p=3, dims=(3, 3, 2)),
}
DEMANDS = [uniform_demands, neighbor_shift_demands, hotspot_demands]


def _coalesced(monkeypatch, router, call):
    """``call()`` with the in-place writer turned off: the coalesce path."""
    with monkeypatch.context() as m:
        m.setattr(VectorizedHyperXRouter, "_disjoint_paths",
                  lambda self: False)
        return call()


def _assert_identical(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        assert g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("make_demands", DEMANDS)
@pytest.mark.parametrize("plane", sorted(PLANES_1D))
def test_in_place_equals_coalesce_on_1d(monkeypatch, plane, make_demands):
    topo = PLANES_1D[plane]
    router = VectorizedHyperXRouter(topo, backend="numpy")
    dem = make_demands(topo, 800.0)
    with collecting() as mx:
        got = router.incidence(dem, "valiant")
    want = _coalesced(monkeypatch, router,
                      lambda: router.incidence(dem, "valiant"))
    _assert_identical(got, want)
    n = topo.dims[0]
    assert got[0].size == dem.n * (2 * n - 3)
    assert mx.value("incidence.entries") == got[0].size
    assert mx.value("incidence.merged") == 0
    timers = mx.snapshot()["timers"]
    assert {k for k in timers if k.startswith("incidence.")} == \
        {"incidence.walk", "incidence.place"}


@pytest.mark.parametrize("plane", sorted(PLANES_1D))
def test_in_place_through_the_pair_cache(monkeypatch, plane):
    topo = PLANES_1D[plane]
    dem = uniform_demands(topo, 800.0)
    half = DemandArrays(dem.src[::2], dem.dst[::2], dem.gbps[::2])
    results = []
    for patched in (False, True):
        router = VectorizedHyperXRouter(topo, backend="numpy")

        def calls():
            # a partly cached second call replays rows and walks the rest
            return (router.incidence_cached(half, "valiant"),
                    router.incidence_cached(dem, "valiant"))

        results.append(_coalesced(monkeypatch, router, calls) if patched
                       else calls())
    for got, want in zip(*results):
        _assert_identical(got, want)
    _assert_identical(results[0][1],
                      VectorizedHyperXRouter(topo).incidence(dem, "valiant"))


def _dal_paths(topo, s, d):
    """DAL's paths of switch pair (s, d), one by one: each minimal
    ordering of the mismatched dimensions, then for each mismatched
    dimension ``i`` and coordinate ``v`` of ``i`` that is neither end's,
    ``v`` along ``i`` first and then every mismatched dimension in index
    order.  Each path is a list of (switch, dim, target coordinate)."""
    cs, cd = list(topo.id_to_coord(s)), list(topo.id_to_coord(d))
    mism = [i for i in range(len(cs)) if cs[i] != cd[i]]

    def walk(order, cur):
        cur, hops = list(cur), []
        for i in order:
            if cur[i] != cd[i]:
                hops.append((topo.coord_to_id(tuple(cur)), i, cd[i]))
                cur[i] = cd[i]
        return hops

    paths = [walk(order, cs) for order in itertools.permutations(mism)]
    for i in mism:
        for v in range(topo.dims[i]):
            if v in (cs[i], cd[i]):
                continue
            via = list(cs)
            via[i] = v
            paths.append([(s, i, v)] + walk(range(len(cs)), via))
    return paths


@pytest.mark.parametrize("plane", sorted(PLANES_MD))
def test_multi_d_valiant_coalesces_and_matches_its_paths(plane):
    """Deroutes rejoin the minimal paths: the coalesce merges entries,
    and the result is DAL's paths summed edge by edge."""
    topo = PLANES_MD[plane]
    router = VectorizedHyperXRouter(topo, backend="numpy")
    assert not router._disjoint_paths()
    dem = uniform_demands(topo, 800.0)
    with collecting() as mx:
        flow, slot, frac = router.incidence(dem, "valiant")
    assert mx.value("incidence.entries") == flow.size
    assert mx.value("incidence.merged") > 0
    assert "incidence.place" not in mx.snapshot()["timers"]
    idx = router.index
    want = {}
    for f, (s, d) in enumerate(zip(dem.src.tolist(), dem.dst.tolist())):
        paths = _dal_paths(topo, s, d)
        for path in paths:
            for u, i, c in path:
                key = (f, int(idx.slots(np.int64(u), i, c)))
                want[key] = want.get(key, 0.0) + 1.0 / len(paths)
    keys = sorted(want)
    assert list(zip(flow.tolist(), slot.tolist())) == keys
    np.testing.assert_allclose(frac, [want[k] for k in keys], rtol=1e-15)
    # merged away: every entry the walk emitted, less those returned; the
    # walk takes each flow's m mismatched hops in all D! orders of the
    # dimensions, then each deroute's hops
    emitted = 0
    for s, d in zip(dem.src.tolist(), dem.dst.tolist()):
        paths = _dal_paths(topo, s, d)
        m = len(paths[0])
        emitted += math.factorial(len(topo.dims)) * m
        emitted += sum(len(p) for p in paths[math.factorial(m):])
    assert mx.value("incidence.merged") == emitted - flow.size


def test_minimal_on_1d_still_coalesces():
    topo = PLANES_1D["16"]
    router = VectorizedHyperXRouter(topo, backend="numpy")
    with collecting() as mx:
        flow, _, frac = router.incidence(uniform_demands(topo, 800.0),
                                         "minimal")
    assert "incidence.coalesce" in mx.snapshot()["timers"]
    assert mx.value("incidence.merged") == 0
    assert mx.value("incidence.entries") == flow.size == frac.size


def test_jax_matches_numpy_on_1d_dal_churn():
    """Neighbour shift along the one dimension under DAL, with staggered
    starts and sizes: the jitted event loop against the numpy one."""
    topo = PLANES_1D["16"]
    router = VectorizedHyperXRouter(topo, backend="numpy")
    dem = neighbor_shift_demands(topo, 0.9 * topo.nic_bw_gbps)
    inc = flow_incidence(router, dem, "valiant")
    rng = np.random.default_rng(2147483659)
    size = rng.uniform(0.2, 1.0, dem.n) * 16 * 2 ** 20
    start = rng.uniform(0.0, 200e-6, dem.n)
    res = {b: simulate_incidence(inc, size, dem.gbps, start_s=start,
                                 backend=b)
           for b in ("numpy", "jax")}
    assert res["jax"].n_epochs == res["numpy"].n_epochs > dem.n
    np.testing.assert_allclose(res["jax"].finish_s, res["numpy"].finish_s,
                               rtol=1e-9)
    np.testing.assert_allclose(res["jax"].edge_bytes,
                               res["numpy"].edge_bytes, rtol=1e-9)


def test_in_place_refuses_a_walk_with_duplicates(monkeypatch):
    """A walk that emitted the direct hop twice would need the coalesce:
    the in-place writer says so rather than overwrite one entry."""
    orig = VectorizedHyperXRouter._iter_minimal_hops

    def twice(self, src, cs, cd):
        for hop in orig(self, src, cs, cd):
            yield hop
            yield hop
    monkeypatch.setattr(VectorizedHyperXRouter, "_iter_minimal_hops", twice)
    topo = PLANES_1D["6"]
    router = VectorizedHyperXRouter(topo, backend="numpy")
    with pytest.raises(RuntimeError, match="disjoint"):
        router.incidence(neighbor_shift_demands(topo, 800.0), "valiant")
