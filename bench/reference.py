"""Plain reference of the flow simulator, independent of the program.

It routes, water-fills and steps epochs as the program documents them,
with nothing imported from the program and nothing taken from it: the
plane comes from the configuration file, the edges are numbered here
(directed switch pair ``u * S + v``), and every array is built from the
simulation's inputs.  ``dtype`` is float64 for the reference of record;
the same code in float32 is the control that the comparison must fail
(``bench/calibrate.py``, ``bench/tests``).

Semantics (those of ``repro.sim`` and ``docs/routing.md``), in the
routing mode that the configuration states:

* ``minimal`` spreads a flow evenly over the D! orders in which its
  mismatched dimensions can be fixed, one hop per mismatched dimension;
* ``valiant`` is DAL (Ahn et al., SC'09): a flow with ``m`` mismatched
  dimensions has ``n_paths = m! + sum over mismatched i of (dims_i - 2)``
  paths and an equal share ``1 / n_paths`` of its rate on each.  The m!
  minimal paths are the orders in which its mismatched dimensions can be
  fixed.  For each mismatched dimension ``i`` and each coordinate ``v``
  of ``i`` other than the source's and the destination's there is one
  deroute path: it first moves to ``v`` along ``i``, then fixes every
  mismatched dimension in index order;
* in either mode the entries of one flow on one edge are summed, and an
  in-dimension hop's capacity is ``links_per_dim / (dims - 1)`` ports;
* max-min fair rates by progressive water-filling: every unfrozen flow
  rises at one pace until an edge saturates or the flow reaches its cap;
* an event loop that re-solves the rates at every start or finish.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass

import numpy as np

F64 = np.float64


def tolerances(dtype) -> "tuple[float, float]":
    """(freeze tolerance, completion tolerance), relative.  float64 uses
    the program's documented 1e-12 and 1e-9; a narrower type cannot
    resolve those, so it uses 64 of its own ulps."""
    eps = float(np.finfo(dtype).eps)
    return max(1e-12, 64 * eps), max(1e-9, 64 * eps)


@dataclass
class RefIncidence:
    flow: np.ndarray        # (NNZ,) int64
    edge: np.ndarray        # (NNZ,) int64 into the used edges
    frac: np.ndarray        # (NNZ,)
    capacity: np.ndarray    # (E,) Gbps of each used edge
    pair: np.ndarray        # (E,) int64 directed pair u * S + v of each
    n_flows: int
    max_capacity: float     # largest edge capacity of the whole plane


def _walk(plane, w, f, cur, steps):
    """(rows, pairs, weights) of the hops of flows ``f`` from coordinates
    ``cur``, setting each (dimension, coordinates) of ``steps`` in turn;
    a hop of flow ``g`` carries the share ``w[g]``."""
    for i, to in steps:
        move = np.flatnonzero(cur[:, i] != to)
        if move.size == 0:
            continue
        u = plane.ids(cur[move])
        cur[move, i] = to[move]
        g = f[move]
        yield g, u * plane.S + plane.ids(cur[move]), w[g]


def _minimal_paths(plane, cs, cd):
    """The D! dimension orders, each carrying ``1 / D!`` of every flow."""
    D = len(plane.dims)
    f = np.arange(cs.shape[0])
    w = np.full(f.size, 1.0 / math.factorial(D))
    for order in itertools.permutations(range(D)):
        yield from _walk(plane, w, f, cs.copy(),
                         [(i, cd[:, i]) for i in order])


def _dal_paths(plane, cs, cd):
    """Every DAL path, each carrying ``1 / n_paths`` of its flow."""
    dims = plane.dims
    mism = cs != cd
    n_paths = np.array([math.factorial(k) for k in mism.sum(axis=1)],
                       dtype=np.int64)
    for i, d in enumerate(dims):
        n_paths += mism[:, i] * max(d - 2, 0)
    w = 1.0 / n_paths
    # minimal paths: every order of each flow's own mismatched dimensions
    patterns, which = np.unique(mism, axis=0, return_inverse=True)
    for p, pattern in enumerate(patterns):
        f = np.flatnonzero(which.reshape(-1) == p)
        for order in itertools.permutations(np.flatnonzero(pattern)):
            yield from _walk(plane, w, f, cs[f].copy(),
                             [(int(i), cd[f, i]) for i in order])
    # deroutes: to coordinate v along i, then dimensions in index order
    for i, d in enumerate(dims):
        for v in range(d):
            f = np.flatnonzero(mism[:, i] & (cs[:, i] != v) & (cd[:, i] != v))
            if f.size:
                yield from _walk(plane, w, f, cs[f].copy(),
                                 [(i, np.full(f.size, v, dtype=np.int64))]
                                 + [(j, cd[f, j]) for j in range(len(dims))])


# routing mode -> its paths: the modes with a fixed per-flow spread
# (``adaptive`` re-routes under load and has no static incidence)
ROUTINGS = {"minimal": _minimal_paths, "valiant": _dal_paths}


def incidence(plane, src: np.ndarray, dst: np.ndarray, dtype=F64,
              routing: str = "minimal") -> RefIncidence:
    """Incidence of the demand rows ``src -> dst`` under ``routing``."""
    if routing not in ROUTINGS:
        raise ValueError(f"no static incidence for routing {routing!r}; "
                         f"the reference knows {', '.join(ROUTINGS)}")
    S = plane.S
    mult = np.array([l / (d - 1) if d > 1 else 0.0
                     for d, l in zip(plane.dims, plane.links_per_dim)])
    cs, cd = plane.coords(src), plane.coords(dst)
    rows, pairs, weights = [], [], []
    for f, pair, w in ROUTINGS[routing](plane, cs, cd):
        rows.append(f)
        pairs.append(pair)
        weights.append(w)
    if not rows:
        z = np.zeros(0, dtype=np.int64)
        return RefIncidence(z, z.copy(), np.zeros(0, dtype=dtype),
                            np.zeros(0, dtype=dtype), z.copy(),
                            int(src.size),
                            float((mult * plane.port_gbps).max()))
    flow = np.concatenate(rows)
    # one entry per (flow, edge): paths of one flow may share a hop
    key, inv = np.unique(flow * np.int64(S * S) + np.concatenate(pairs),
                         return_inverse=True)
    del flow
    frac = np.zeros(key.size, dtype=dtype)
    np.add.at(frac, inv, np.concatenate(weights).astype(dtype))
    flow_u, pair_u = key // (S * S), key % (S * S)
    used, edge = np.unique(pair_u, return_inverse=True)
    # a hop changes one coordinate: that dimension sets its capacity
    hop_dim = np.argmax(plane.coords(used // S) != plane.coords(used % S),
                        axis=1)
    cap = (mult[hop_dim] * plane.port_gbps).astype(dtype)
    return RefIncidence(flow_u, edge.astype(np.int64), frac, cap, used,
                        int(src.size),
                        float((mult * plane.port_gbps).max()))


def segment_sum(ids: np.ndarray, vals: np.ndarray, n: int) -> np.ndarray:
    """Scatter-add in the values' own precision."""
    if vals.dtype == F64:
        return np.bincount(ids, weights=vals, minlength=n)
    out = np.zeros(n, dtype=vals.dtype)
    np.add.at(out, ids, vals)
    return out


def edge_loads(inc: RefIncidence, gbps: np.ndarray) -> np.ndarray:
    """(E,) Gbps per used edge with flow f at ``gbps[f]``."""
    dt = inc.frac.dtype
    return segment_sum(inc.edge, gbps.astype(dt)[inc.flow] * inc.frac,
                       inc.capacity.size)


def flow_hops(inc: RefIncidence) -> np.ndarray:
    """(F,) expected switch-to-switch hops of each flow."""
    return segment_sum(inc.flow, inc.frac, inc.n_flows)


def bottleneck_gbps(flow, edge, frac, capacity, n_flows) -> np.ndarray:
    """(F,) rate each flow could hold alone on an idle fabric."""
    out = np.full(n_flows, np.inf, dtype=frac.dtype)
    with np.errstate(divide="ignore"):
        np.minimum.at(out, flow, capacity[edge] / frac)
    return out


def max_min_rates(inc: RefIncidence, caps: np.ndarray, active: np.ndarray,
                  tol) -> np.ndarray:
    """(F,) max-min fair rates of the active flows by water-filling."""
    dt = inc.frac.dtype
    F, E = inc.n_flows, inc.capacity.size
    rates = np.zeros(F, dtype=dt)
    unfrozen = active.copy()
    cap_left = inc.capacity.copy()
    zero, inf = dt.type(0), dt.type(np.inf)
    for _ in range(F + E + 2):
        if not unfrozen.any():
            return rates
        live = np.where(unfrozen[inc.flow], inc.frac, zero)
        wsum = segment_sum(inc.edge, live, E)
        open_e = wsum > tol
        delta_e = np.where(open_e, cap_left / np.where(open_e, wsum, 1),
                           inf)
        delta_f = np.where(unfrozen, caps - rates, inf)
        delta = min(delta_e.min() if E else inf, delta_f.min())
        delta = max(delta, zero)
        rates = np.where(unfrozen, rates + delta, rates)
        cap_left = cap_left - delta * wsum
        sat = open_e & (cap_left <= tol)
        on_sat = segment_sum(inc.flow, np.where(sat[inc.edge], inc.frac,
                                                zero), F) > 0
        capped = rates >= caps - tol
        unfrozen = unfrozen & ~on_sat & ~capped
    raise RuntimeError(f"reference water-filling did not converge ({F} "
                       "flows)")


@dataclass
class RefResult:
    start_s: np.ndarray
    finish_s: np.ndarray
    fct_s: np.ndarray
    latency_s: np.ndarray
    edge_bytes: np.ndarray   # (E,) over the used edges
    n_epochs: int
    makespan_s: float


def simulate(inc: RefIncidence, size_bytes, caps_gbps, start_s,
             net: dict) -> RefResult:
    """Event loop: between consecutive start/finish events every active
    flow moves at its max-min fair rate.  Works in ``inc.frac.dtype``."""
    dt = inc.frac.dtype
    F = inc.n_flows
    size = np.broadcast_to(np.asarray(size_bytes), (F,)).astype(dt)
    caps = np.broadcast_to(np.asarray(caps_gbps), (F,)).astype(dt)
    start = (np.zeros(F, dtype=dt) if start_s is None else
             np.broadcast_to(np.asarray(start_s), (F,)).astype(dt))
    tol_rel, done_rel = tolerances(dt)
    scale = max(inc.max_capacity, float(caps.max()) if F else 0.0, 1.0)
    tol = dt.type(tol_rel * scale)
    thresh = dt.type(done_rel) * np.maximum(size, dt.type(1))
    to_Bps = dt.type(1e9 / 8.0)
    remaining = size.copy()
    finish = np.full(F, np.inf, dtype=dt)
    finish[size == 0] = start[size == 0]
    edge_bytes = np.zeros(inc.capacity.size, dtype=dt)
    stalled = np.zeros(F, dtype=bool)
    t = start.min() if F else dt.type(0)
    n_epochs = 0
    for _ in range(4 * F + 8):
        open_f = (remaining > thresh) & ~stalled
        active = open_f & (start <= t * dt.type(1 + 1e-12) + dt.type(1e-18))
        pending = start[open_f & ~active]
        if not active.any():
            if pending.size == 0:
                break
            t = pending.min()
            continue
        n_epochs += 1
        rates = max_min_rates(inc, caps, active, tol)
        rates = np.where(active, rates, dt.type(0))
        dead = active & (rates <= 0)
        if dead.any() and pending.size == 0:
            stalled |= dead
            active &= ~dead
            if not active.any():
                continue
        Bps = rates[active] * to_Bps
        dt_fin = (remaining[active] / np.maximum(Bps, dt.type(1e-30))).min()
        dt_arr = pending.min() - t if pending.size else dt.type(np.inf)
        step = min(dt_fin, dt_arr)
        moved = rates * to_Bps * step
        remaining = np.maximum(remaining - moved, dt.type(0))
        edge_bytes = edge_bytes + segment_sum(
            inc.edge, moved[inc.flow] * inc.frac, edge_bytes.size)
        t = t + step
        finish[active & (remaining <= thresh)] = t
    else:
        raise RuntimeError(f"reference event loop did not converge ({F} "
                           "flows)")
    hops = flow_hops(inc)
    lat = (dt.type(net["t_nic"]) + hops * dt.type(net["t_switch"])
           + (hops + dt.type(2)) * dt.type(net["t_prop_per_hop"]))
    done = np.isfinite(finish)
    return RefResult(start, finish, finish - start + lat, lat, edge_bytes,
                     n_epochs,
                     float((finish[done] - start.min()).max())
                     if done.any() else 0.0)


def fct_summary(fct_s, finish_s, size_bytes, caps_gbps, latency_s,
                bottleneck, makespan_s, offered_gbps) -> dict:
    """The FCT summary a user reads: p50/p95/p99 FCT, mean and p99
    slowdown over the uncontended FCT, and the delivered fraction of the
    offered load.  The same arithmetic serves the timed path and the
    reference."""
    ok = np.isfinite(finish_s)
    out = {"stalled": int((~ok).sum())}
    fct = fct_s[ok]
    for q in (50, 95, 99):
        out[f"fct_p{q}_s"] = float(np.percentile(fct, q)) if fct.size \
            else None
    ideal = (size_bytes / (np.minimum(caps_gbps, bottleneck) * 1e9 / 8.0)
             + latency_s)
    slow = fct_s / ideal
    out["slowdown_mean"] = float(slow[ok].mean()) if ok.any() else None
    out["slowdown_p99"] = float(np.percentile(slow[ok], 99)) \
        if ok.any() else None
    delivered = (float(size_bytes[ok].sum()) * 8 / 1e9 / makespan_s
                 if makespan_s > 0 else 0.0)
    out["delivered_fraction"] = delivered / offered_gbps \
        if offered_gbps else 1.0
    return out
