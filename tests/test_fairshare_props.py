"""Property-based tests for max-min fair water-filling.

Invariants on random COO flow-incidence tensors, checked against the
numpy reference solver and (at fixed shapes, so jit compiles once) the
in-jit jax and Pallas paths:

  * no edge ever carries more than its capacity,
  * every active flow below its demand cap crosses a saturated edge
    (the max-min "bottlenecked" fixpoint condition),
  * rates stay within [0, cap] and below the flow's alone-on-the-fabric
    bottleneck rate; inactive flows hold exactly 0,
  * relabeling flows permutes the rates and nothing else,
  * the three solver backends agree to 1e-9.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep — deterministic fallback shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.sim.fairshare import (FlowIncidence, SegmentLayout,
                                 _compress_edges, _segment_reductions,
                                 max_min_rates)

seed_st = st.integers(0, 10_000)

# jax/pallas recompile per (F, NNZ, compressed-E) signature, so the
# cross-backend tests pin the shape and vary only the values; the
# numpy-only invariants sample shapes freely.
FIXED_F, FIXED_E, FIXED_NNZ = 8, 12, 16


def random_incidence(seed: int, fixed_shape: bool = False):
    """A random coalesced incidence + finite caps + active mask."""
    rng = np.random.default_rng(seed)
    if fixed_shape:
        F, E, nnz = FIXED_F, FIXED_E, FIXED_NNZ
    else:
        F = int(rng.integers(1, 13))
        E = int(rng.integers(1, 17))
        nnz = int(rng.integers(0, min(F * E, 24) + 1))
    pairs = rng.choice(F * E, size=min(nnz, F * E), replace=False)
    flow = (pairs // E).astype(np.int64)
    edge = (pairs % E).astype(np.int64)
    order = np.argsort(flow, kind="stable")
    inc = FlowIncidence(
        flow=flow[order], edge=edge[order],
        frac=rng.uniform(0.1, 2.0, flow.size),
        n_flows=F,
        capacity=rng.uniform(0.5, 10.0, E))
    caps = rng.uniform(0.1, 5.0, F)
    active = rng.random(F) < 0.8
    if not active.any():
        active[0] = True
    return inc, caps, active


def solver_tol(inc, caps) -> float:
    scale = max(inc.capacity.max(initial=0.0),
                caps.max() if caps.size else 0.0, 1.0)
    return 1e-7 * scale


@given(seed=seed_st)
@settings(max_examples=80, deadline=None)
def test_no_edge_over_capacity(seed):
    inc, caps, active = random_incidence(seed)
    rates = max_min_rates(inc, caps, active=active, backend="numpy")
    loads = inc.loads(rates)
    assert np.all(loads <= inc.capacity + solver_tol(inc, caps))


@given(seed=seed_st)
@settings(max_examples=80, deadline=None)
def test_every_uncapped_flow_is_bottlenecked(seed):
    inc, caps, active = random_incidence(seed)
    rates = max_min_rates(inc, caps, active=active, backend="numpy")
    loads = inc.loads(rates)
    tol = solver_tol(inc, caps)
    saturated = loads >= inc.capacity - tol
    for f in range(inc.n_flows):
        if not active[f] or rates[f] >= caps[f] - tol:
            continue
        my_edges = inc.edge[inc.flow == f]
        # a flow held below its cap must be blocked by the fabric: it
        # has fabric edges and at least one of them is saturated
        assert my_edges.size > 0
        assert saturated[my_edges].any()


@given(seed=seed_st)
@settings(max_examples=80, deadline=None)
def test_rate_bounds_and_inactive_flows(seed):
    inc, caps, active = random_incidence(seed)
    rates = max_min_rates(inc, caps, active=active, backend="numpy")
    tol = solver_tol(inc, caps)
    assert np.all(rates >= 0.0)
    assert np.all(rates <= caps + tol)
    assert np.all(rates[~active] == 0.0)
    alone = inc.bottleneck_gbps()
    assert np.all(rates <= np.minimum(caps, alone) + tol)


@given(seed=seed_st)
@settings(max_examples=40, deadline=None)
def test_flow_permutation_invariance(seed):
    inc, caps, active = random_incidence(seed)
    rates = max_min_rates(inc, caps, active=active, backend="numpy")
    rng = np.random.default_rng(seed + 1)
    perm = rng.permutation(inc.n_flows)
    inc_p = FlowIncidence(
        flow=perm[inc.flow], edge=inc.edge, frac=inc.frac,
        n_flows=inc.n_flows, capacity=inc.capacity)
    caps_p = np.empty_like(caps)
    caps_p[perm] = caps
    active_p = np.zeros_like(active)
    active_p[perm] = active
    rates_p = max_min_rates(inc_p, caps_p, active=active_p,
                            backend="numpy")
    scale = max(float(caps.max()), 1.0)
    assert np.abs(rates_p[perm] - rates).max() <= 1e-9 * scale


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@given(seed=seed_st)
@settings(max_examples=15, deadline=None)
def test_backends_agree_with_reference(backend, seed):
    inc, caps, active = random_incidence(seed, fixed_shape=True)
    ref = max_min_rates(inc, caps, active=active, backend="numpy")
    got = max_min_rates(inc, caps, active=active, backend=backend)
    scale = max(float(caps.max()), 1.0)
    assert np.abs(got - ref).max() <= 1e-9 * scale


def test_empty_flow_set():
    inc = FlowIncidence(flow=np.zeros(0, dtype=np.int64),
                        edge=np.zeros(0, dtype=np.int64),
                        frac=np.zeros(0), n_flows=0,
                        capacity=np.ones(4))
    assert max_min_rates(inc, np.zeros(0), backend="numpy").shape == (0,)


def test_single_flow_takes_min_of_cap_and_bottleneck():
    inc = FlowIncidence(flow=np.array([0, 0]), edge=np.array([1, 3]),
                        frac=np.array([1.0, 0.5]), n_flows=1,
                        capacity=np.array([9.0, 4.0, 9.0, 1.0]))
    # bottleneck: min(4.0/1.0, 1.0/0.5) = 2.0
    for backend in ("numpy", "jax", "pallas"):
        r = max_min_rates(inc, np.array([10.0]), backend=backend)
        assert abs(float(r[0]) - 2.0) <= 1e-9
        r = max_min_rates(inc, np.array([1.5]), backend=backend)
        assert abs(float(r[0]) - 1.5) <= 1e-9


def test_infinite_caps_rejected():
    inc = FlowIncidence(flow=np.array([0]), edge=np.array([0]),
                        frac=np.array([1.0]), n_flows=2,
                        capacity=np.array([1.0]))
    with pytest.raises(ValueError, match="finite"):
        max_min_rates(inc, np.array([1.0, np.inf]), backend="numpy")


def test_compress_edges_preserves_solution():
    inc, caps, active = random_incidence(123)
    used, inc_c, _ = _compress_edges(inc)
    # a flow-sorted incidence passes through in its own order
    assert np.array_equal(inc_c.flow, inc.flow)
    assert np.array_equal(used[inc_c.edge], inc.edge)
    assert np.array_equal(inc_c.capacity, inc.capacity[used])
    ref = max_min_rates(inc, caps, active=active, backend="numpy")
    got = max_min_rates(inc_c, caps, active=active, backend="numpy")
    assert np.array_equal(got, ref)


def _layout_case(case: str):
    """An incidence shaped as ``case`` says, with a per-flow weight."""
    rng = np.random.default_rng(7)
    F, E, nnz = 300, 60, 1200
    flow = np.sort(rng.integers(0, F, nnz))
    edge = rng.integers(0, E, nnz)
    frac = rng.uniform(0.1, 2.0, nnz)
    if case == "skewed_edge":
        # a hot edge carries most entries, as the hotspot mix's does
        edge[rng.random(nnz) < 0.7] = 3
    elif case == "empty_flows":
        # flows with no entries, among them the first and the last
        keep = (flow % 3 != 0) & (flow != F - 1)
        flow, edge, frac = flow[keep], edge[keep], frac[keep]
    elif case == "zero_frac":
        frac[rng.random(nnz) < 0.3] = 0.0
        frac[edge == 5] = 0.0       # an edge all of whose entries are 0
    # coalesced: one entry per (flow, edge), in flow order
    key = np.unique(flow * E + edge, return_index=True)[1]
    if case == "unsorted":
        key = rng.permutation(key)
    inc = FlowIncidence(flow=flow[key], edge=edge[key], frac=frac[key],
                        n_flows=F, capacity=rng.uniform(1.0, 9.0, E))
    return inc, rng.uniform(0.5, 3.0, F), rng.random(E) < 0.3


@pytest.mark.parametrize("case", ["flow_sorted", "unsorted", "skewed_edge",
                                  "empty_flows", "zero_frac"])
def test_sorted_segment_reductions_match_add_at(case):
    """The jax path's per-edge float64 sums agree with ``np.add.at`` to
    1e-12 relative, and its freeze predicate (a flow has an entry of
    positive fraction on a saturated edge) agrees exactly."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    inc, weight, sat_all = _layout_case(case)
    used, inc_c, layout = _compress_edges(inc)
    sat = sat_all[used]
    want_sum = np.zeros(inc.n_edges)
    np.add.at(want_sum, inc.edge, weight[inc.flow] * inc.frac)
    hit = sat_all[inc.edge] & (inc.frac > 0)
    want_hit = np.zeros(inc.n_flows, dtype=bool)
    want_hit[inc.flow[hit]] = True
    with jax.enable_x64(True):
        edge_sums, flows_hit = _segment_reductions(
            jnp.asarray(inc_c.flow), jnp.asarray(inc_c.edge),
            jnp.asarray(inc_c.frac), False,
            SegmentLayout(*map(jnp.asarray, layout)))
        w = jnp.asarray(weight)
        got_sum = np.asarray(jax.jit(
            lambda: edge_sums(lambda f, fr: w[f] * fr))())
        got_hit = np.asarray(jax.jit(
            lambda: flows_hit(jnp.asarray(sat)))())
    want_sum = want_sum[used]
    assert np.all(np.abs(got_sum - want_sum) <= 1e-12 * np.abs(want_sum))
    assert np.array_equal(got_hit, want_hit)
