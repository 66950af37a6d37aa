"""Edge bytes of the jitted event loop, totalled once per simulation.

An epoch of ``sim.events._event_loop_jit`` only adds its moved bytes
into a per-flow total; one edge-sum pass over that total follows the
loop.  Pinned here:

  * the total equals the numpy reference loop's per-epoch sums to
    1e-10 relative, under ``minimal`` on a 2D plane, ``valiant`` (DAL)
    on a 1D plane, and with a stalled flow;
  * the bytes are conserved: every delivered flow puts its size on each
    of its edges, weighted by its fraction there;
  * the epoch body does no pass over the entries outside the water-fill
    loop, and exactly one follows the epoch loop;
  * the ``sim.edge_bytes_passes`` counter reads one per jax simulation.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.hyperx import MPHX
from repro.core.routing_vec import (VectorizedHyperXRouter,
                                    neighbor_shift_demands)
from repro.sim.events import simulate_incidence
from repro.sim.fairshare import flow_incidence
from repro.telemetry import collecting

pytest.importorskip("jax")

CASES = {
    "minimal-2d": (MPHX(n=2, p=8, dims=(8, 8)), "minimal"),
    "valiant-1d": (MPHX(n=2, p=16, dims=(16,)), "valiant"),
}


def _staggered(case):
    """Neighbour shift with churn sizes and starts: many epochs."""
    topo, mode = CASES[case]
    router = VectorizedHyperXRouter(topo, backend="numpy")
    dem = neighbor_shift_demands(topo, 0.9 * topo.nic_bw_gbps)
    inc = flow_incidence(router, dem, mode)
    rng = np.random.default_rng(2147483659)
    size = rng.uniform(0.2, 1.0, dem.n) * 16 * 2 ** 20
    start = rng.uniform(0.0, 200e-6, dem.n)
    return inc, size, np.asarray(dem.gbps, dtype=np.float64), start


def _both(inc, size, caps, start):
    return {b: simulate_incidence(inc, size, caps, start_s=start, backend=b)
            for b in ("numpy", "jax")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_jax_edge_bytes_match_numpy(case):
    inc, size, caps, start = _staggered(case)
    res = _both(inc, size, caps, start)
    assert res["jax"].n_epochs == res["numpy"].n_epochs > inc.n_flows
    np.testing.assert_allclose(res["jax"].edge_bytes,
                               res["numpy"].edge_bytes, rtol=1e-10)


@pytest.mark.parametrize("case", sorted(CASES))
def test_jax_edge_bytes_conserve_each_flows_size(case):
    """Sum_e edge_bytes = Sum_f size_f * Sum_{e in f} w_fe, to the
    completion threshold of 1e-9 * size a flow may leave undelivered."""
    inc, size, caps, start = _staggered(case)
    res = simulate_incidence(inc, size, caps, start_s=start, backend="jax")
    assert not res.stalled.any()
    hops = np.bincount(inc.flow, weights=inc.frac, minlength=inc.n_flows)
    np.testing.assert_allclose(res.edge_bytes.sum(), (size * hops).sum(),
                               rtol=1e-9)


def test_jax_edge_bytes_match_numpy_with_a_stall():
    """A flow whose every path crosses a dead edge stops short of its
    size, so its edges are checked against the numpy loop instead."""
    inc, size, caps, start = _staggered("minimal-2d")
    dead = inc.edge[inc.flow == 0]
    inc = dataclasses.replace(inc, capacity=inc.capacity.copy())
    inc.capacity[dead] = 0.0
    res = _both(inc, size, caps, start)
    assert res["numpy"].stalled.any()
    np.testing.assert_array_equal(res["jax"].stalled, res["numpy"].stalled)
    assert res["jax"].n_epochs == res["numpy"].n_epochs
    np.testing.assert_allclose(res["jax"].edge_bytes,
                               res["numpy"].edge_bytes, rtol=1e-10)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` with the sub-jaxprs of its parameters,
    as ``(eqn, [sub-jaxpr, ...])``."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        subs = []
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(x, ClosedJaxpr):
                    subs.append(x.jaxpr)
                elif isinstance(x, Jaxpr):
                    subs.append(x)
        yield eqn, subs


def _wide_gathers(eqns, nnz, skip_while=False):
    """Gathers with at least ``nnz`` outputs among ``eqns`` (from
    :func:`_eqns`) and the jaxprs under them, leaving out those under a
    ``while`` when ``skip_while``."""
    n = 0
    for eqn, subs in eqns:
        if eqn.primitive.name == "gather" and \
                np.prod(eqn.outvars[0].aval.shape) >= nnz:
            n += 1
        if not (skip_while and eqn.primitive.name == "while"):
            n += sum(_wide_gathers(_eqns(s), nnz, skip_while) for s in subs)
    return n


def test_epoch_body_makes_no_pass_over_the_entries():
    import jax
    import jax.numpy as jnp

    from repro.sim.events import _event_loop_jit
    from repro.sim.fairshare import SegmentLayout, _compress_edges

    inc, size, caps, start = _staggered("valiant-1d")
    used, inc_c, layout = _compress_edges(inc)
    nnz = inc_c.flow.size
    assert nnz > max(used.size, inc.n_flows)
    with jax.enable_x64(True):
        args = [jnp.asarray(a) for a in (inc_c.flow, inc_c.edge, inc_c.frac,
                                         inc_c.capacity, size, caps, start,
                                         1e-9)]
        closed = jax.make_jaxpr(lambda *a: _event_loop_jit()(
            *a, layout=SegmentLayout(*map(jnp.asarray, layout)),
            E=used.size, use_pallas=False, record=False, max_j=0))(*args)
    (call,) = [s for _, subs in _eqns(closed.jaxpr) for s in subs]
    eqns = list(_eqns(call))
    (i,) = [k for k, (e, _) in enumerate(eqns) if e.primitive.name == "while"]
    epoch = eqns[i][0].params["body_jaxpr"].jaxpr
    (cond,) = [e for e, _ in _eqns(epoch) if e.primitive.name == "cond"]
    no_active, with_active = (b.jaxpr for b in cond.params["branches"])
    # the water-fill rounds gather over the entries; nothing else may
    assert _wide_gathers(_eqns(with_active), nnz) > 0
    assert _wide_gathers(_eqns(with_active), nnz, skip_while=True) == 0
    assert _wide_gathers(_eqns(no_active), nnz) == 0
    assert _wide_gathers(eqns[i + 1:], nnz) == 1


def test_edge_bytes_passes_count_each_jax_simulation():
    inc, size, caps, start = _staggered("minimal-2d")
    with collecting() as mx:
        for _ in range(2):
            simulate_incidence(inc, size, caps, start_s=start, backend="jax")
    assert mx.value("sim.edge_bytes_passes") == mx.value("sim.runs") == 2
    assert mx.value("sim.epochs") > 2 * inc.n_flows
    with collecting() as mx:
        simulate_incidence(inc, size, caps, start_s=start, backend="numpy")
    assert mx.value("sim.edge_bytes_passes") == 0
