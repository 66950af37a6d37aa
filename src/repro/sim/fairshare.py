"""Batched max-min fair bandwidth allocation (the flow simulator's core).

A routed flow set becomes a *flow-incidence tensor*: COO arrays
``(flow, edge, frac)`` where ``frac`` is the fraction of flow ``f``'s rate
crossing directed edge ``e`` — extracted from the routing engines'
own walk code (``VectorizedHyperXRouter.incidence`` /
``GraphRouter.incidence``), so the simulator's load accounting is the
analytic engines' load accounting by construction (pinned to 1e-6 by
``tests/test_sim.py`` and ``results/BENCH_flow_sim.json``).

Fair shares come from classic progressive water-filling: all unfrozen
flows raise their rate at the same pace until an edge saturates (freezing
every flow crossing it) or a flow hits its demand cap, repeated until all
flows freeze.  Three solver paths compute the identical fixpoint:

``numpy``   the reference: a Python round loop of ``np.bincount``
            scatter-adds — the pre-jit solver the golden fixtures pin
            (``tests/golden/fairshare_golden.json``).
``jax``     the whole solve as ONE jitted ``lax.while_loop`` — no
            Python round-trip per round, float64 via a
            ``jax.enable_x64(True)`` scope regardless of the global flag.
            Its segment reductions run over a layout sorted once on the
            host (:func:`_compress_edges`): per-edge sums are segmented
            scans over the edge-major entries, the per-flow freeze an
            int32 running count over the flow-major ones — no scatter,
            which a TPU runs serially in its emulated float64.  This is
            the 65K-NIC path, and the one that runs on a TPU.
``pallas``  the same while_loop with the segment reductions lowered to
            the Pallas kernels (:mod:`repro.kernels.segment_fairshare`),
            run by the Pallas interpreter on the CPU.  A TPU refuses it:
            Mosaic compiles the kernels at float32 only, and the 1e-9
            agreement below needs float64.
``auto``    jax on a TPU; elsewhere jax when 64-bit mode is on (the
            :func:`~repro.core.routing_vec.get_backend` contract),
            numpy otherwise.

All paths agree to 1e-9 (``tests/test_fairshare_props.py`` /
``tests/test_fairshare_golden.py``).  All rates and capacities are Gbps;
``frac`` is dimensionless.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from repro.core.routing_vec import DemandArrays, _scatter_add, get_backend
from repro.telemetry import get_metrics

FAIRSHARE_BACKENDS = ("numpy", "jax", "pallas", "auto")


@dataclass
class FlowIncidence:
    """Per-flow edge usage of a routed flow set, plus edge capacities.

    ``flow`` / ``edge`` / ``frac`` are parallel COO arrays (coalesced:
    one entry per (flow, edge) pair); ``capacity`` is the per-edge Gbps of
    the router that produced the incidence.  ``sum_e frac[f, e]`` is flow
    ``f``'s expected switch-switch hop count (every unit of flow crosses
    each hop of its path spread once).
    """

    flow: np.ndarray       # (NNZ,) int64 flow index
    edge: np.ndarray       # (NNZ,) int64 directed-edge id / edge slot
    frac: np.ndarray       # (NNZ,) float64 fraction of the flow's rate
    n_flows: int
    capacity: np.ndarray   # (E,) Gbps

    @property
    def n_edges(self) -> int:
        return int(self.capacity.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.flow.shape[0])

    def loads(self, rates_gbps: np.ndarray) -> np.ndarray:
        """(E,) offered Gbps per edge when flow ``f`` runs at
        ``rates_gbps[f]`` — the steady-state link loads."""
        out = np.zeros(self.n_edges)
        np.add.at(out, self.edge, np.asarray(rates_gbps)[self.flow]
                  * self.frac)
        return out

    def utilization(self, rates_gbps: np.ndarray) -> np.ndarray:
        l = self.loads(rates_gbps)
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(self.capacity > 0, l / self.capacity, 0.0)

    def switch_hops(self) -> np.ndarray:
        """(F,) expected switch-switch hops per flow (0 for flows with no
        fabric path, e.g. src == dst)."""
        out = np.zeros(self.n_flows)
        np.add.at(out, self.flow, self.frac)
        return out

    def bottleneck_gbps(self) -> np.ndarray:
        """(F,) max rate each flow could sustain *alone* on an idle
        fabric: ``min_e capacity[e] / frac[f, e]`` over its edges
        (inf for flows with no fabric path)."""
        out = np.full(self.n_flows, np.inf)
        with np.errstate(divide="ignore"):
            per_entry = self.capacity[self.edge] / self.frac
        np.minimum.at(out, self.flow, per_entry)
        return out

    def edge_share(self, edges: np.ndarray) -> np.ndarray:
        """(F,) fraction of each flow's rate crossing any edge in
        ``edges`` (clipped to 1) — first-order stalled share when those
        edges fail before re-routing (:mod:`repro.sim.failures`)."""
        sel = np.isin(self.edge, edges)
        out = np.zeros(self.n_flows)
        np.add.at(out, self.flow[sel], self.frac[sel])
        return np.minimum(out, 1.0)


def flow_incidence(router, demands: DemandArrays,
                   mode: str = "minimal",
                   cached: bool = False) -> FlowIncidence:
    """Extract the per-flow incidence tensor from a batched router
    (:func:`repro.core.netsim.make_router` product: MPHX array engine or
    generic graph engine — both expose ``incidence`` and
    ``edge_capacity``).

    ``cached=True`` routes the extraction through the router's pair-level
    incidence cache (``incidence_cached``): only (src, dst) pairs not
    seen before are walked, so repeated flow sets (collective phases,
    epoch re-solves) skip the ~20x-route-cost extraction entirely.
    """
    if cached and hasattr(router, "incidence_cached"):
        flow, edge, frac = router.incidence_cached(demands, mode)
    else:
        flow, edge, frac = router.incidence(demands, mode)
    return FlowIncidence(flow, edge, frac, demands.n,
                         np.asarray(router.edge_capacity(),
                                    dtype=np.float64))


def resolve_sim_backend(backend: str = "numpy") -> str:
    """Normalize a fair-share solver backend name for this platform.

    ``auto`` is jax on a TPU and otherwise follows the router engines'
    :func:`get_backend` contract (jax only under x64); ``pallas`` is
    refused on a TPU (see the module docstring)."""
    if backend not in FAIRSHARE_BACKENDS:
        raise ValueError(f"unknown fairshare backend {backend!r}; "
                         f"expected one of {FAIRSHARE_BACKENDS}")
    if backend not in ("auto", "pallas"):
        return backend
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if backend == "pallas" and on_tpu:
        raise ValueError(
            "the pallas fair-share backend cannot run on a TPU: Mosaic "
            "compiles its segment kernels at float32 only, and the solver "
            "needs float64 for its 1e-9 agreement with the numpy "
            "reference; use the jax backend")
    if backend == "auto":
        return "jax" if on_tpu else get_backend("auto")[0]
    return backend


def _waterfill_scale(inc: FlowIncidence, caps: np.ndarray) -> float:
    return float(max(np.max(inc.capacity, initial=0.0),
                     caps.max() if caps.size else 0.0, 1.0))


def max_min_rates(inc: FlowIncidence, rate_caps_gbps: np.ndarray,
                  active: "np.ndarray | None" = None,
                  backend: str = "numpy") -> np.ndarray:
    """(F,) max-min fair rates by progressive water-filling.

    Every active flow's rate rises at unit pace until either an edge
    saturates (``sum_f frac * rate == capacity`` — all flows crossing it
    freeze) or the flow reaches its own ``rate_caps_gbps`` demand cap.
    Inactive flows hold rate 0 and consume nothing.  Terminates in at most
    F + E rounds (each round freezes a flow or saturates an edge); rounds
    are O(NNZ) segment reductions on the selected ``backend`` (see the
    module docstring for the numpy/jax/pallas paths).
    """
    F = inc.n_flows
    caps = np.broadcast_to(np.asarray(rate_caps_gbps, dtype=np.float64),
                           (F,))
    if not np.all(np.isfinite(caps)):
        raise ValueError("rate caps must be finite (a flow with no fabric "
                         "path would otherwise fill forever)")
    if active is None:
        active = np.ones(F, dtype=bool)
    backend = resolve_sim_backend(backend)
    if F == 0:
        return np.zeros(0)
    if backend == "numpy":
        return _max_min_rates_reference(inc, caps, active)
    tol = 1e-12 * _waterfill_scale(inc, caps)
    import jax
    import jax.numpy as jnp

    _, inc_c, layout = _compress_edges(inc)
    with jax.enable_x64(True):
        rates, converged, rounds = _waterfill_jit()(
            jnp.asarray(inc_c.flow), jnp.asarray(inc_c.edge),
            jnp.asarray(inc_c.frac), jnp.asarray(inc_c.capacity),
            jnp.asarray(caps), jnp.asarray(active), jnp.asarray(tol),
            layout=SegmentLayout(*map(jnp.asarray, layout)),
            use_pallas=(backend == "pallas"))
        if not bool(converged):
            raise RuntimeError("water-filling failed to converge "
                               f"({F} flows, {inc.n_edges} edges)")
        mx = get_metrics()
        if mx.enabled:
            mx.inc("waterfill.solves")
            mx.inc("waterfill.rounds", int(rounds))
        return np.asarray(rates)


# ---------------------------------------------------------------------------
# Reference path (pre-jit solver — the golden fixtures pin this loop)
# ---------------------------------------------------------------------------


def _max_min_rates_reference(inc: FlowIncidence, caps: np.ndarray,
                             active: np.ndarray) -> np.ndarray:
    xp = np
    F, E = inc.n_flows, inc.n_edges
    flow = xp.asarray(inc.flow)
    edge = xp.asarray(inc.edge)
    frac = xp.asarray(inc.frac)
    cap_e = xp.asarray(inc.capacity)
    caps_x = xp.asarray(caps)
    tol = 1e-12 * _waterfill_scale(inc, caps)
    rates = xp.zeros(F)
    unfrozen = xp.asarray(active.copy())
    cap_left = cap_e
    rounds = 0
    for _ in range(F + E + 2):
        if not bool(unfrozen.any()):
            break
        rounds += 1
        live = xp.where(unfrozen[flow], frac, 0.0)
        wsum = _scatter_add(xp, xp.zeros(E), edge, live)
        open_e = wsum > tol
        delta_e = xp.where(open_e, cap_left / xp.where(open_e, wsum, 1.0),
                           xp.inf)
        delta_f = xp.where(unfrozen, caps_x - rates, xp.inf)
        delta = float(xp.minimum(delta_e.min() if E else xp.inf,
                                 delta_f.min()))
        delta = max(delta, 0.0)
        rates = xp.where(unfrozen, rates + delta, rates)
        cap_left = cap_left - delta * wsum
        sat = open_e & (cap_left <= tol)
        on_sat = _scatter_add(xp, xp.zeros(F), flow,
                              xp.where(sat[edge], frac, 0.0)) > 0
        capped = rates >= caps_x - tol
        unfrozen = unfrozen & ~on_sat & ~capped
    else:
        raise RuntimeError("water-filling failed to converge "
                           f"({F} flows, {E} edges)")
    mx = get_metrics()
    if mx.enabled:
        mx.inc("waterfill.solves")
        mx.inc("waterfill.rounds", rounds)
    return np.asarray(rates)


# ---------------------------------------------------------------------------
# In-jit path: the whole solve as one lax.while_loop over segment ops
# ---------------------------------------------------------------------------


class SegmentLayout(NamedTuple):
    """A compressed incidence laid out for the jax solve's segment
    reductions (:func:`_segment_reductions`).

    In edge-major order, edge ``e``'s entries are one run, opened where
    ``head_e`` is set and closed at ``edge_end[e]``.  In the flow-major
    COO, flow ``f``'s entries are ``flow_off[f]:flow_off[f + 1]`` (an
    empty range for a flow with no fabric path)."""

    flow_e: np.ndarray    # (NNZ,) int32 flow of each entry, edge-major
    frac_e: np.ndarray    # (NNZ,) float64 its fraction, edge-major
    head_e: np.ndarray    # (NNZ,) bool: the entry opens its edge's run
    edge_end: np.ndarray  # (E,) int32 last edge-major entry of each edge
    flow_off: np.ndarray  # (F + 1,) int32 flow-major offsets


def _compress_edges(inc: FlowIncidence):
    """Drop edges no flow crosses before solving, and lay the entries
    out for the sorted segment reductions.

    An edge with zero incidence weight can never saturate (``wsum = 0``
    keeps it out of ``open_e``), so it contributes nothing to any round's
    ``delta`` — the solve over the used-edge subset runs the same
    rounds.  Fabric edge sets are much larger than any one flow set's
    footprint (a 65K-NIC fabric has ~72K directed edges; a
    neighbor-shift flow set touches ~2 per flow).

    Returns ``(used, inc_c, layout)``: the used edge ids (sorted), the
    incidence over them in flow-major order (``inc_c.edge`` indexes
    ``used``; the routers' output is already flow-sorted and passes
    through unpermuted, an unsorted one is sorted stably), and its
    :class:`SegmentLayout`.  One host sort, the edge-major ``argsort``,
    gives ``used``, the compressed edge column and the layout; the
    order of entries within an edge is the sort's, which moves only the
    rounding of that edge's sums.
    """
    flow, edge, frac = inc.flow, inc.edge, inc.frac
    nnz = flow.shape[0]
    if nnz and np.any(flow[1:] < flow[:-1]):
        order = np.argsort(flow, kind="stable")
        flow, edge, frac = flow[order], edge[order], frac[order]
    perm = np.argsort(edge.astype(np.int32))  # int32 keys sort faster
    edge_s = edge[perm]
    head = np.ones(nnz, dtype=bool)
    np.not_equal(edge_s[1:], edge_s[:-1], out=head[1:])
    used = edge_s[head]
    edge_c = np.empty(nnz, dtype=np.int64)
    edge_c[perm] = np.cumsum(head) - 1
    layout = SegmentLayout(
        flow_e=flow.astype(np.int32)[perm], frac_e=frac[perm], head_e=head,
        edge_end=np.flatnonzero(np.append(head[1:], nnz > 0)
                                ).astype(np.int32),
        flow_off=np.concatenate(([0], np.cumsum(np.bincount(
            flow, minlength=inc.n_flows)))).astype(np.int32))
    inc_c = FlowIncidence(flow, edge_c, frac, inc.n_flows,
                          inc.capacity[used])
    return used, inc_c, layout


_LANES = 128  # a TPU vector register's lanes: the scan's row width


def _scan_steps(vals, head, axis: int):
    """Hillis–Steele inclusive scan along ``axis`` (log2 steps over
    shifted copies), restarted wherever ``head`` is set; ``head=None``
    is a plain running sum.  Returns ``(prefix-or of head, sums)``."""
    import jax
    import jax.numpy as jnp

    n = vals.shape[axis]

    def shift(x, k):
        pads = [(0, 0)] * x.ndim
        pads[axis] = (k, 0)
        return jnp.pad(jax.lax.slice_in_dim(x, 0, n - k, axis=axis), pads)

    k = 1
    while k < n:
        if head is None:
            vals = vals + shift(vals, k)
        else:
            vals = jnp.where(head, vals, vals + shift(vals, k))
            head = head | shift(head, k)
        k *= 2
    return head, vals


def _segmented_scan(vals, head=None):
    """Inclusive scan of ``vals`` (traced inside jit), restarted wherever
    ``head`` is set: each addition joins two partial sums of one run
    (``head=None``: a plain running sum).

    Blocked over a (rows, 128) view: a scan along each row, then one
    over the rows' last sums, whose carry is added to the next row up
    to its first head."""
    import jax.numpy as jnp

    n = vals.shape[0]
    if n == 0:
        return vals
    rows = -(-n // _LANES)
    pad = rows * _LANES - n
    v = jnp.pad(vals, (0, pad)).reshape(rows, _LANES)
    h = None if head is None else jnp.pad(head, (0, pad)).reshape(rows,
                                                                  _LANES)
    h, v = _scan_steps(v, h, axis=1)
    _, ends = _scan_steps(v[:, -1], None if h is None else h[:, -1], axis=0)
    carry = jnp.pad(ends[:-1], (1, 0))[:, None]
    v = v + carry if h is None else jnp.where(h, v, v + carry)
    return v.reshape(-1)[:n]


def _segment_reductions(flow, edge, frac, use_pallas: bool,
                        layout: SegmentLayout):
    """The solve's two segment reductions over one compressed incidence
    (traced inside jit), as ``(edge_sums, flows_hit)``.

    ``edge_sums(entry)`` is the (E,) per-edge sum of ``entry(flow_ids,
    fracs)``, a value per entry computed from its flow and fraction.
    The jax path reads a float64 segmented scan over the edge-major
    entries at each edge's last entry: no scatter, and each sum adds
    only its own edge's entries (a global prefix sum read as
    differences would carry every earlier edge's rounding into it).

    ``flows_hit(sat)`` is the (F,) mask of flows with an entry of
    positive fraction on an edge of the (E,) mask ``sat``: exact, an
    int32 running count over the flow-major entries compared at the
    flow offsets.

    The Pallas path keeps its scatter kernel over ``(flow, edge,
    frac)``; it is interpreted on the CPU and compiled everywhere else.
    """
    import jax
    import jax.numpy as jnp

    if use_pallas:
        from repro.kernels.segment_fairshare import segment_sum

        interpret = jax.default_backend() == "cpu"
        E, F = layout.edge_end.shape[0], layout.flow_off.shape[0] - 1

        def edge_sums(entry):
            return segment_sum(entry(flow, frac), edge, E,
                               interpret=interpret)

        def flows_hit(sat):
            return segment_sum(jnp.where(sat[edge], frac, 0.0), flow, F,
                               interpret=interpret) > 0
        return edge_sums, flows_hit

    def edge_sums(entry):
        vals = entry(layout.flow_e, layout.frac_e)
        return _segmented_scan(vals, layout.head_e)[layout.edge_end]

    def flows_hit(sat):
        hits = (sat[edge] & (frac > 0)).astype(jnp.int32)
        count = jnp.pad(_segmented_scan(hits), (1, 0))[layout.flow_off]
        return count[1:] > count[:-1]

    return edge_sums, flows_hit


def _waterfill_body(edge_sums, flows_hit, cap_e, caps, tol):
    """(cond, body, init-builder) of the water-filling while_loop —
    shared by the standalone solver and the in-jit event loop, over the
    reductions of :func:`_segment_reductions`.  A round's device work
    carries three named scopes: ``waterfill.edge_load`` (the live
    entries' sum into edges), ``waterfill.step`` (the uniform raise and
    the edges' remaining capacity) and ``waterfill.freeze`` (the flows
    on saturated edges, and the capped flows)."""
    import jax
    import jax.numpy as jnp

    F, E = caps.shape[0], cap_e.shape[0]

    def cond(state):
        _, unfrozen, _, i = state
        return jnp.logical_and(unfrozen.any(), i < F + E + 2)

    def body(state):
        rates, unfrozen, cap_left, i = state
        with jax.named_scope("waterfill.edge_load"):
            wsum = edge_sums(lambda f, w: jnp.where(unfrozen[f], w, 0.0))
        with jax.named_scope("waterfill.step"):
            open_e = wsum > tol
            delta_e = jnp.where(open_e,
                                cap_left / jnp.where(open_e, wsum, 1.0),
                                jnp.inf)
            delta_f = jnp.where(unfrozen, caps - rates, jnp.inf)
            d_edges = delta_e.min() if E else jnp.inf
            delta = jnp.maximum(jnp.minimum(d_edges, delta_f.min()), 0.0)
            rates = jnp.where(unfrozen, rates + delta, rates)
            cap_left = cap_left - delta * wsum
        with jax.named_scope("waterfill.freeze"):
            sat = open_e & (cap_left <= tol)
            on_sat = flows_hit(sat)
            capped = rates >= caps - tol
            unfrozen = unfrozen & ~on_sat & ~capped
        return rates, unfrozen, cap_left, i + 1

    def init(active):
        return (jnp.zeros(F, dtype=caps.dtype), active, cap_e,
                jnp.int32(0))

    return cond, body, init


@functools.lru_cache(maxsize=1)
def _waterfill_jit():
    """Build (once) the jitted standalone solve:
    ``(rates, converged, rounds)`` (``rounds`` = while-loop iterations —
    the telemetry layer's ``waterfill.rounds`` counter; numerically
    inert, it was always part of the loop state)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("use_pallas",))
    def solve(flow, edge, frac, cap_e, caps, active, tol, *,
              layout: SegmentLayout, use_pallas: bool):
        cond, body, init = _waterfill_body(
            *_segment_reductions(flow, edge, frac, use_pallas, layout),
            cap_e, caps, tol)
        rates, unfrozen, _, i = jax.lax.while_loop(cond, body,
                                                   init(active))
        return rates, jnp.logical_not(unfrozen.any()), i

    return solve
