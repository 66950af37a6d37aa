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
``jax``     the whole solve as ONE jitted ``lax.while_loop`` over sparse
            COO segment ops (``jax.ops.segment_sum``) — no Python
            round-trip per round, float64 via a ``jax.enable_x64(True)``
            scope regardless of the global flag.  This is the 65K-NIC
            path, and the one that runs on a TPU.
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

    used, edge_c, cap_c = _compress_edges(inc)
    with jax.enable_x64(True):
        rates, converged, rounds = _waterfill_jit()(
            jnp.asarray(inc.flow), jnp.asarray(edge_c),
            jnp.asarray(inc.frac), jnp.asarray(cap_c),
            jnp.asarray(caps), jnp.asarray(active), jnp.asarray(tol),
            E=used.size, use_pallas=(backend == "pallas"))
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


def _compress_edges(inc: FlowIncidence):
    """Drop edges no flow crosses before solving.

    An edge with zero incidence weight can never saturate (``wsum = 0``
    keeps it out of ``open_e``), so it contributes nothing to any round's
    ``delta`` — the solve over the used-edge subset runs the *identical*
    float sequence.  Fabric edge sets are much larger than any one flow
    set's footprint (a 65K-NIC fabric has ~72K directed edges; a
    neighbor-shift flow set touches ~2 per flow), so this is the main
    constant-factor win of the jit paths.  Returns ``(used_edge_ids,
    remapped_edge_col, used_capacities)``.
    """
    used, edge_c = np.unique(inc.edge, return_inverse=True)
    return used, edge_c.astype(np.int64), inc.capacity[used]


def _segment_sum(vals, ids, n_segments: int, use_pallas: bool):
    """Backend-selected COO scatter-add (traced inside jit).  The Pallas
    kernel is interpreted on the CPU and compiled everywhere else."""
    import jax

    if use_pallas:
        from repro.kernels.segment_fairshare import segment_sum

        return segment_sum(vals, ids, n_segments,
                           interpret=jax.default_backend() == "cpu")
    return jax.ops.segment_sum(vals, ids, num_segments=n_segments)


def _waterfill_body(flow, edge, frac, cap_e, caps, tol, E: int,
                    use_pallas: bool):
    """(cond, body, init-builder) of the water-filling while_loop —
    shared by the standalone solver and the in-jit event loop.  A round's
    device work carries three named scopes: ``waterfill.edge_load`` (the
    live entries' segment sum into edges), ``waterfill.step`` (the
    uniform raise and the edges' remaining capacity) and
    ``waterfill.freeze`` (saturated edges' segment sum into flows, and
    the capped flows)."""
    import jax
    import jax.numpy as jnp

    F = caps.shape[0]

    def cond(state):
        _, unfrozen, _, i = state
        return jnp.logical_and(unfrozen.any(), i < F + E + 2)

    def body(state):
        rates, unfrozen, cap_left, i = state
        with jax.named_scope("waterfill.edge_load"):
            live = jnp.where(unfrozen[flow], frac, 0.0)
            wsum = _segment_sum(live, edge, E, use_pallas)
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
            on_sat = _segment_sum(jnp.where(sat[edge], frac, 0.0), flow, F,
                                  use_pallas) > 0
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

    @functools.partial(jax.jit, static_argnames=("E", "use_pallas"))
    def solve(flow, edge, frac, cap_e, caps, active, tol, *,
              E: int, use_pallas: bool):
        cond, body, init = _waterfill_body(flow, edge, frac, cap_e, caps,
                                           tol, E, use_pallas)
        rates, unfrozen, _, i = jax.lax.while_loop(cond, body,
                                                   init(active))
        return rates, jnp.logical_not(unfrozen.any()), i

    return solve
