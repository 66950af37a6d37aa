"""Event-driven flow-level simulation loop.

Finite flows start, share the fabric max-min fairly, and complete; the
loop advances time between start/completion events, re-solving fair
shares (:func:`repro.sim.fairshare.max_min_rates`) each epoch.  This
turns the analytic engines' asymptotic utilizations into *measured* flow
completion times (FCTs) — the FatPaths-style evaluation the closed forms
cannot give.

Conventions (matching :mod:`repro.core.netsim`): sizes are bytes,
rates/capacities Gbps, times seconds.  A flow's FCT is its transfer time
(size over its time-varying fair share) plus the path alpha term
``t_nic + sw_hops * t_switch + (sw_hops + 2) * t_prop`` where ``sw_hops``
is the flow's expected hop count from the incidence tensor — so an
uncontended flow's FCT is exactly the closed-form
``bytes / min(rate_cap, bottleneck) + alpha`` bound
(``tests/test_sim.py`` pins it).
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass

import numpy as np

from repro.core.netsim import DEFAULT_NET, NetParams, gbps_to_Bps
from repro.core.routing_vec import DemandArrays
from repro.telemetry import get_metrics, get_recorder, span
from .fairshare import (FlowIncidence, SegmentLayout, _compress_edges,
                        _segment_reductions, _waterfill_body,
                        _waterfill_scale, flow_incidence, max_min_rates,
                        resolve_sim_backend)


@dataclass(frozen=True)
class FlowSpec:
    """One finite flow: ``size_bytes`` from switch ``src`` to ``dst``.

    ``tag`` is an opaque attribution handle (e.g. a tenant id, or a
    ``(tenant, request)`` tuple) carried through the simulation into the
    per-flow results and telemetry — callers never re-derive ownership
    by index arithmetic.  It does not affect the simulated float
    sequence in any way.
    """

    src: int
    dst: int
    size_bytes: float
    start_s: float = 0.0
    tag: object = None


def flows_to_demands(flows: "list[FlowSpec]") -> DemandArrays:
    return DemandArrays(
        np.array([f.src for f in flows], dtype=np.int64),
        np.array([f.dst for f in flows], dtype=np.int64),
        np.ones(len(flows)))


@dataclass
class FlowSimResult:
    """Per-flow outcome of one fabric simulation."""

    start_s: np.ndarray        # (F,)
    finish_s: np.ndarray       # (F,) transfer-complete time (inf = stalled)
    fct_s: np.ndarray          # (F,) finish - start + path alpha term
    latency_s: np.ndarray      # (F,) the per-flow path alpha term
    size_bytes: np.ndarray     # (F,)
    edge_bytes: np.ndarray     # (E,) bytes carried per edge
    incidence: FlowIncidence
    makespan_s: float = 0.0    # last finish (stalled flows excluded)
    n_epochs: int = 0
    tags: "np.ndarray | None" = None   # (F,) object — opaque flow tags

    @property
    def stalled(self) -> np.ndarray:
        return ~np.isfinite(self.finish_s)

    def tag_mask(self, tag) -> np.ndarray:
        """(F,) bool — flows whose tag equals ``tag`` (requires tags)."""
        if self.tags is None:
            raise ValueError("simulation was run without flow tags")
        return np.array([t == tag for t in self.tags], dtype=bool)

    def flow_records(self) -> "list[dict]":
        """Per-flow FCT records (start/finish/fct/size/tag), the
        attribution-ready view tenant accounting consumes."""
        tags = self.tags if self.tags is not None \
            else np.full(self.size_bytes.shape[0], None, dtype=object)
        return [
            {"flow": f, "tag": tags[f],
             "start_s": float(self.start_s[f]),
             "finish_s": float(self.finish_s[f]),
             "fct_s": float(self.fct_s[f]),
             "size_bytes": float(self.size_bytes[f]),
             "stalled": bool(~np.isfinite(self.finish_s[f]))}
            for f in range(self.size_bytes.shape[0])]

    def transfer_s(self) -> np.ndarray:
        return self.finish_s - self.start_s

    def fct_percentiles(self, qs=(50, 95, 99)) -> dict:
        ok = self.fct_s[~self.stalled]
        if ok.size == 0:
            return {f"p{q}": None for q in qs}
        return {f"p{q}": float(np.percentile(ok, q)) for q in qs}

    def slowdown(self, rate_caps_gbps: np.ndarray) -> np.ndarray:
        """(F,) FCT over the uncontended closed-form FCT at each flow's
        own rate cap (1.0 = no queueing/contention inflation)."""
        caps = np.broadcast_to(np.asarray(rate_caps_gbps, dtype=np.float64),
                               self.size_bytes.shape)
        bneck = self.incidence.bottleneck_gbps()
        ideal = (self.size_bytes / gbps_to_Bps(np.minimum(caps, bneck))
                 + self.latency_s)
        return self.fct_s / ideal

    def delivered_gbps(self) -> float:
        """Aggregate delivered injection rate over the makespan."""
        done = self.size_bytes[~self.stalled].sum()
        return float(done * 8 / 1e9 / self.makespan_s) \
            if self.makespan_s > 0 else 0.0

    def mean_utilization_weighted(self) -> np.ndarray:
        """(E,) time-averaged edge utilization over the makespan."""
        cap = self.incidence.capacity
        if self.makespan_s <= 0:
            return np.zeros_like(cap)
        with np.errstate(divide="ignore", invalid="ignore"):
            gbps = self.edge_bytes * 8 / 1e9 / self.makespan_s
            return np.where(cap > 0, gbps / cap, 0.0)


def path_latency(inc: FlowIncidence, net: NetParams = DEFAULT_NET
                 ) -> np.ndarray:
    """(F,) per-flow path alpha term from the incidence hop counts
    (+2 access hops, same hop convention as ``netsim.avg_latency``)."""
    sw = inc.switch_hops()
    return (net.t_nic + sw * net.t_switch
            + (sw + 2.0) * net.t_prop_per_hop)


def _journal_util(inc: FlowIncidence, rates_act: np.ndarray,
                  sel: np.ndarray) -> np.ndarray:
    """(K,) utilization of the selected global edges at the epoch's
    active-flow rates (the numpy-loop side of the epoch journal — the jit
    loop computes the same quantity over compressed edges)."""
    if sel.size == 0:
        return np.zeros(0)
    loads = np.zeros(inc.n_edges)
    np.add.at(loads, inc.edge, rates_act[inc.flow] * inc.frac)
    cap = inc.capacity
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(cap[sel] > 0, loads[sel] / cap[sel], 0.0)


def _normalize_tags(tags, F: int) -> "np.ndarray | None":
    """(F,) object array of opaque flow tags, or None when absent."""
    if tags is None:
        return None
    tag_list = list(tags)
    if len(tag_list) != F:
        raise ValueError(f"expected {F} tags, got {len(tag_list)}")
    out = np.empty(F, dtype=object)
    out[:] = tag_list
    return out


# ordinal of each simulation in the process: the ``run`` argument of its
# ``sim.simulate`` span, which groups a trace's spans by simulation
_RUN_ORDINAL = itertools.count()


def simulate_incidence(inc: FlowIncidence, size_bytes, rate_caps_gbps,
                       start_s=None, net: NetParams = DEFAULT_NET,
                       backend: str = "numpy", tags=None) -> FlowSimResult:
    """Run the event loop over a prebuilt incidence tensor.

    ``size_bytes`` / ``rate_caps_gbps`` / ``start_s`` broadcast to (F,).
    Active flows whose fair share is 0 (every path crosses a
    zero-capacity edge — e.g. after failure injection) are marked stalled
    (``finish_s = inf``) rather than looping forever.

    ``backend`` picks the epoch engine: ``numpy`` is the reference Python
    event loop (one :func:`max_min_rates` call per epoch); ``jax`` /
    ``pallas`` run the *entire* loop — epoch advance plus the nested
    water-filling — as one jitted ``lax.while_loop``, so a simulation is
    a single device call instead of a Python round-trip per re-solve
    (semantics pinned to the numpy loop at 1e-9 by the golden fixtures).

    When a flight recorder is active (:func:`repro.telemetry.recording`)
    both engines additionally journal one row per epoch — epoch clock,
    active-flow count, utilization of the recorder's selected link subset
    — with identical row count and ordering, plus per-flow transfer
    spans.  With no recorder the numpy loop skips the journal code
    entirely and the jitted loop leaves the journal out of its graph
    (``record`` is a static argument), so disabled telemetry cannot
    perturb the golden float sequences.

    The call is the span ``sim.simulate`` (:func:`repro.telemetry.span`,
    with the process's simulation ordinal as its ``run`` argument); on
    the jit path its phases are the child spans ``sim.compress``,
    ``sim.transfer``, ``sim.loop``, ``sim.readback`` and ``sim.finalize``.
    """
    with span("sim.simulate", run=next(_RUN_ORDINAL)):
        F = inc.n_flows
        size = np.broadcast_to(np.asarray(size_bytes, dtype=np.float64),
                               (F,)).copy()
        caps = np.broadcast_to(
            np.asarray(rate_caps_gbps, dtype=np.float64), (F,)).copy()
        start = (np.zeros(F) if start_s is None else
                 np.broadcast_to(np.asarray(start_s, dtype=np.float64),
                                 (F,)).copy())
        if np.any(size < 0) or np.any(caps <= 0):
            raise ValueError("sizes must be >= 0 and rate caps > 0")
        backend = resolve_sim_backend(backend)
        tag_arr = _normalize_tags(tags, F)
        rec = get_recorder()
        mx = get_metrics()
        if backend != "numpy" and F > 0:
            res = _simulate_incidence_jit(inc, size, caps, start, net,
                                          use_pallas=(backend == "pallas"),
                                          recorder=rec)
        else:
            res = _simulate_incidence_numpy(inc, size, caps, start, net,
                                            backend, recorder=rec)
        res.tags = tag_arr
        mx.inc("sim.runs")
        mx.inc("sim.flows", F)
        mx.inc("sim.epochs", res.n_epochs)
        if rec is not None:
            rec.record_flow_sim(res)
        return res


def _simulate_incidence_numpy(inc: FlowIncidence, size, caps, start,
                              net: NetParams, backend: str,
                              recorder=None) -> FlowSimResult:
    F = inc.n_flows
    record = recorder is not None and recorder.link_policy is not None
    if record:
        sel = recorder.link_policy.select(inc, caps)
        max_j = recorder.link_policy.max_epochs
        j_t, j_dt, j_act, j_util = [], [], [], []
        dropped = 0

        def journal(t, dt, act_mask, rates_act):
            nonlocal dropped
            if len(j_t) >= max_j:
                dropped += 1
                return
            j_t.append(t)
            j_dt.append(dt)
            j_act.append(int(act_mask.sum()))
            j_util.append(_journal_util(inc, rates_act, sel))
    remaining = size.copy()
    finish = np.full(F, np.inf)
    finish[size == 0] = start[size == 0]
    edge_bytes = np.zeros(inc.n_edges)
    stalled = np.zeros(F, dtype=bool)
    t = float(start.min()) if F else 0.0
    eps = 1e-9
    n_epochs = 0
    # each epoch completes a flow, admits an arrival batch, or stalls a
    # dead flow set — so 4F + 8 bounds any run
    for _ in range(4 * F + 8):
        open_f = (remaining > eps * np.maximum(size, 1.0)) & ~stalled
        active = open_f & (start <= t * (1 + 1e-12) + 1e-18)
        pending = start[open_f & ~active]
        if not active.any():
            if pending.size == 0:
                break
            t = float(pending.min())
            continue
        n_epochs += 1
        rates = max_min_rates(inc, caps, active=active, backend=backend)
        rates = np.where(active, rates, 0.0)
        dead = active & (rates <= 0)
        if dead.any() and pending.size == 0:
            stalled |= dead
            active &= ~dead
            if not active.any():
                if record:
                    journal(t, 0.0, active, np.zeros(F))
                continue
        Bps = gbps_to_Bps(rates[active])
        dt_fin = float((remaining[active] / np.maximum(Bps, 1e-30)).min())
        dt_arr = float(pending.min() - t) if pending.size else np.inf
        dt = min(dt_fin, dt_arr)
        if record:
            journal(t, dt, active, np.where(active, rates, 0.0))
        moved = gbps_to_Bps(rates) * dt
        remaining = np.maximum(remaining - moved, 0.0)
        np.add.at(edge_bytes, inc.edge,
                  moved[inc.flow] * inc.frac)
        t += dt
        just_done = active & (remaining <= eps * np.maximum(size, 1.0))
        finish[just_done] = t
    else:
        raise RuntimeError(f"flow sim failed to converge ({F} flows)")
    if record:
        recorder.record_epoch_journal(
            j_t, j_dt, j_act, sel,
            np.asarray(j_util).reshape(len(j_t), sel.size),
            dropped=dropped)
    return _finalize_result(inc, size, caps, start, finish, edge_bytes,
                            n_epochs, net)


def _finalize_result(inc: FlowIncidence, size, caps, start, finish,
                     edge_bytes, n_epochs: int, net: NetParams
                     ) -> FlowSimResult:
    with span("sim.finalize"):
        lat = path_latency(inc, net)
        fct = finish - start + lat
        done = np.isfinite(finish)
        return FlowSimResult(
            start_s=start, finish_s=finish, fct_s=fct, latency_s=lat,
            size_bytes=size, edge_bytes=edge_bytes, incidence=inc,
            makespan_s=float((finish[done] - start.min()).max())
            if done.any() else 0.0,
            n_epochs=n_epochs)


@functools.lru_cache(maxsize=1)
def _event_loop_jit():
    """Build (once) the jitted whole-simulation loop.

    One ``lax.while_loop`` iteration is one epoch of the reference loop
    in :func:`simulate_incidence`: admit arrivals / detect completion,
    re-solve max-min fair shares with the nested water-filling
    while_loop (:func:`repro.sim.fairshare._waterfill_body`), advance to
    the next start/finish event.  Same constants, same branch structure,
    same freeze tolerances — the golden fixtures hold it to 1e-9.
    ``(flow, edge, frac)`` is the compressed incidence in flow-major
    order and ``layout`` its :class:`~repro.sim.fairshare.SegmentLayout`
    (both from :func:`~repro.sim.fairshare._compress_edges`), over which
    every per-edge sum and the freeze run
    (:func:`~repro.sim.fairshare._segment_reductions`).

    The loop state also counts the water-filling rounds of every epoch's
    solve (``rounds``, int32, numerically inert): the ``waterfill.rounds``
    counter of the jit path.  Named scopes (op metadata only) mark the
    device work: ``waterfill.edge_load``, ``waterfill.step`` and
    ``waterfill.freeze`` inside a round, ``epoch.admit``,
    ``epoch.advance``, ``epoch.edge_bytes`` and ``epoch.journal`` in an
    epoch.

    ``record`` (static) threads the flight-recorder epoch journal —
    per-epoch clock/dt/active-count plus utilization of the ``sel``
    compressed-edge subset, written into fixed ``max_j``-row arrays with
    masked writes (rows past ``max_j`` are counted, not written, matching
    the reference loop's journal cap).  With ``record=False`` the journal
    keys never enter the loop state.

    Returns ``(finish, edge_bytes, n_epochs, done, ok)``, then the
    journal's ``(t, dt, active, util)`` when ``record``, then ``rounds``.
    """
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit,
                       static_argnames=("E", "use_pallas", "record",
                                        "max_j"))
    def run(flow, edge, frac, cap_e, size, caps, start, tol, sel=None, *,
            layout: SegmentLayout, E: int, use_pallas: bool,
            record: bool = False, max_j: int = 0):
        F = size.shape[0]
        eps = 1e-9
        thresh = eps * jnp.maximum(size, 1.0)
        edge_sums, flows_hit = _segment_reductions(flow, edge, frac,
                                                   use_pallas, layout)
        wf_cond, wf_body, wf_init = _waterfill_body(
            edge_sums, flows_hit, cap_e, caps, tol)

        def solve(active):
            rates, unfrozen, _, rounds = jax.lax.while_loop(
                wf_cond, wf_body, wf_init(active))
            return rates, jnp.logical_not(unfrozen.any()), rounds

        def cond(s):
            return jnp.logical_and(~s["done"], s["i"] < 4 * F + 8)

        def body(s):
            t = s["t"]
            with jax.named_scope("epoch.admit"):
                open_f = (s["remaining"] > thresh) & ~s["stalled"]
                active = open_f & (start <= t * (1 + 1e-12) + 1e-18)
                pend = open_f & ~active
                has_pending = pend.any()
                pending_min = jnp.where(pend, start, jnp.inf).min()
                any_active = active.any()

            def no_active(s):
                # break if nothing is pending, else jump to next arrival
                with jax.named_scope("epoch.advance"):
                    return dict(s, t=jnp.where(has_pending, pending_min, t),
                                done=s["done"] | ~has_pending)

            def with_active(s):
                rates, conv, rounds = solve(active)
                with jax.named_scope("epoch.advance"):
                    rates = jnp.where(active, rates, 0.0)
                    dead = active & (rates <= 0)
                    do_stall = dead.any() & ~has_pending
                    stall_set = dead & do_stall
                    act = active & ~stall_set
                    proceed = act.any()
                    Bps = rates * (1e9 / 8.0)
                    per_dt = jnp.where(
                        act, s["remaining"] / jnp.maximum(Bps, 1e-30),
                        jnp.inf)
                    dt_arr = jnp.where(has_pending, pending_min - t,
                                       jnp.inf)
                    dt = jnp.where(proceed,
                                   jnp.minimum(per_dt.min(), dt_arr), 0.0)
                    # dt=0 when everything active just stalled — the
                    # reference loop's stall-continue epoch
                    moved = Bps * dt
                    remaining = jnp.maximum(s["remaining"] - moved, 0.0)
                    t2 = t + dt
                    just_done = act & (remaining <= thresh)
                with jax.named_scope("epoch.edge_bytes"):
                    edge_bytes = s["edge_bytes"] + edge_sums(
                        lambda f, w: moved[f] * w)
                s2 = dict(
                    s, t=t2, remaining=remaining,
                    finish=jnp.where(just_done, t2, s["finish"]),
                    stalled=s["stalled"] | stall_set,
                    edge_bytes=edge_bytes,
                    n_epochs=s["n_epochs"] + 1, ok=s["ok"] & conv,
                    rounds=s["rounds"] + rounds)
                if record:
                    with jax.named_scope("epoch.journal"):
                        idx = jnp.minimum(s["n_epochs"], max_j - 1)
                        okr = s["n_epochs"] < max_j
                        act_rates = jnp.where(act, rates, 0.0)
                        loads = edge_sums(lambda f, w: act_rates[f] * w)
                        util = jnp.where(cap_e[sel] > 0,
                                         loads[sel] / cap_e[sel], 0.0)
                        s2["j_t"] = s["j_t"].at[idx].set(
                            jnp.where(okr, t, s["j_t"][idx]))
                        s2["j_dt"] = s["j_dt"].at[idx].set(
                            jnp.where(okr, dt, s["j_dt"][idx]))
                        s2["j_act"] = s["j_act"].at[idx].set(
                            jnp.where(okr, act.sum().astype(jnp.int32),
                                      s["j_act"][idx]))
                        s2["j_util"] = s["j_util"].at[idx].set(
                            jnp.where(okr, util, s["j_util"][idx]))
                return s2

            s2 = jax.lax.cond(any_active, with_active, no_active, s)
            return dict(s2, i=s["i"] + 1)

        state = {
            "t": start.min(),
            "remaining": size,
            "finish": jnp.where(size == 0, start, jnp.inf),
            "stalled": jnp.zeros(F, dtype=bool),
            "edge_bytes": jnp.zeros(E, dtype=size.dtype),
            "n_epochs": jnp.int32(0),
            "rounds": jnp.int32(0),
            "i": jnp.int32(0),
            "done": jnp.bool_(False),
            "ok": jnp.bool_(True),
        }
        if record:
            state["j_t"] = jnp.zeros(max_j, dtype=size.dtype)
            state["j_dt"] = jnp.zeros(max_j, dtype=size.dtype)
            state["j_act"] = jnp.zeros(max_j, dtype=jnp.int32)
            state["j_util"] = jnp.zeros((max_j, sel.shape[0]),
                                        dtype=size.dtype)
        out = jax.lax.while_loop(cond, body, state)
        base = (out["finish"], out["edge_bytes"], out["n_epochs"],
                out["done"], out["ok"])
        if record:
            base += (out["j_t"], out["j_dt"], out["j_act"], out["j_util"])
        return base + (out["rounds"],)

    return run


def _simulate_incidence_jit(inc: FlowIncidence, size, caps, start,
                            net: NetParams, use_pallas: bool,
                            recorder=None) -> FlowSimResult:
    import jax
    import jax.numpy as jnp

    with span("sim.compress"):
        tol = 1e-12 * _waterfill_scale(inc, caps)
        # solve over the used-edge subset (unused edges never saturate)
        # in the sorted layout, and scatter edge_bytes back at the end
        used, inc_c, layout = _compress_edges(inc)
    record = recorder is not None and recorder.link_policy is not None
    if record:
        sel_g = recorder.link_policy.select(inc, caps)
        # selected edges carry load, so they all appear in `used`; keep
        # the intersection anyway (degenerate degraded incidences)
        sel_g = sel_g[np.isin(sel_g, used)]
        sel_c = np.searchsorted(used, sel_g)
        max_j = max(1, recorder.link_policy.max_epochs)
    else:
        sel_c, max_j = None, 0
    with jax.enable_x64(True):
        with span("sim.transfer"):
            args = [jnp.asarray(a) for a in (inc_c.flow, inc_c.edge,
                                             inc_c.frac, inc_c.capacity,
                                             size, caps, start, tol)]
            args.append(jnp.asarray(sel_c) if record else None)
            layout = SegmentLayout(*map(jnp.asarray, layout))
        with span("sim.loop"):
            out = _event_loop_jit()(*args, layout=layout, E=used.size,
                                    use_pallas=use_pallas, record=record,
                                    max_j=max_j)
            finish, used_bytes, n_epochs, done, ok = out[:5]
            rounds = out[-1]
            if not bool(ok):
                raise RuntimeError("water-filling failed to converge "
                                   f"({inc.n_flows} flows, {inc.n_edges} "
                                   "edges)")
            if not bool(done):
                raise RuntimeError(
                    f"flow sim failed to converge ({inc.n_flows} flows)")
        with span("sim.readback"):
            finish = np.asarray(finish)
            edge_bytes = np.zeros(inc.n_edges)
            edge_bytes[used] = np.asarray(used_bytes)
            n_epochs = int(n_epochs)
            rounds = int(rounds)
            if record:
                j_t, j_dt, j_act, j_util = (np.asarray(a)
                                            for a in out[5:9])
                n = min(n_epochs, max_j)
                recorder.record_epoch_journal(
                    j_t[:n], j_dt[:n], j_act[:n], sel_g, j_util[:n],
                    dropped=n_epochs - n)
    mx = get_metrics()
    # one water-filling solve per epoch, as in the reference loop
    mx.inc("waterfill.solves", n_epochs)
    mx.inc("waterfill.rounds", rounds)
    if not use_pallas:
        mx.inc("sim.segment_scan")
    return _finalize_result(inc, size, caps, start, finish, edge_bytes,
                            n_epochs, net)


def simulate_flows(router, flows: "list[FlowSpec]", mode: str = "minimal",
                   rate_cap_gbps: "float | np.ndarray | None" = None,
                   net: NetParams = DEFAULT_NET,
                   backend: str = "numpy") -> FlowSimResult:
    """Simulate a list of :class:`FlowSpec` on one plane's fabric.

    ``router`` is a batched router (``netsim.make_router``); routes come
    from its ``mode`` path spread.  ``rate_cap_gbps`` defaults to the
    topology's per-plane port bandwidth (each flow is one NIC port's
    traffic on this plane).
    """
    dem = flows_to_demands(flows)
    inc = flow_incidence(router, dem, mode)
    if rate_cap_gbps is None:
        rate_cap_gbps = router.topo.port_gbps if hasattr(router, "topo") \
            else router.graph.link_gbps
    tags = [f.tag for f in flows]
    return simulate_incidence(
        inc, np.array([f.size_bytes for f in flows]),
        rate_cap_gbps,
        np.array([f.start_s for f in flows]), net=net, backend=backend,
        tags=tags if any(t is not None for t in tags) else None)


def simulate_demands(router, demands: DemandArrays, flow_time_s: float,
                     mode: str = "minimal", net: NetParams = DEFAULT_NET,
                     backend: str = "numpy",
                     inc: "FlowIncidence | None" = None,
                     start_s=None, tags=None) -> dict:
    """Measured-FCT summary of one traffic matrix at its offered rates.

    Each demand row becomes one flow sized so that at its offered Gbps it
    transfers for exactly ``flow_time_s`` (so under zero contention every
    FCT is ``flow_time_s + alpha`` and slowdown is 1.0).  Returns the flat
    row the sweep/sim suites merge into their artifacts.

    The static path spreads don't depend on the offered rates, so a
    caller sweeping load levels of one scenario can extract ``inc`` once
    and pass it in — it must come from a demand matrix with the same
    (src, dst) rows.

    ``start_s`` (scalar or (F,)) staggers per-flow arrival offsets — e.g.
    dependent collective phases of a co-simulated training step arriving
    as the previous phase drains (:mod:`repro.cosim`).

    ``tags`` (length-F, opaque — e.g. tenant ids) attributes each demand
    row; when given, the returned row gains a ``per_tag`` breakdown of
    flow counts and FCT percentiles keyed by ``str(tag)``.
    """
    gbps = np.asarray(demands.gbps, dtype=np.float64)
    if inc is None:
        inc = flow_incidence(router, demands, mode)
    res = simulate_incidence(inc, gbps_to_Bps(gbps) * flow_time_s, gbps,
                             start_s=start_s, net=net, backend=backend,
                             tags=tags)
    pct = res.fct_percentiles()
    slow = res.slowdown(gbps)
    ok = ~res.stalled
    offered = float(gbps.sum())
    row: dict = {
        "sim_flows": int(inc.n_flows),
        "sim_epochs": res.n_epochs,
        "sim_stalled": int(res.stalled.sum()),
        "sim_delivered_fraction":
            round(res.delivered_gbps() / offered, 6) if offered else 1.0,
        "fct_p50_us": round(pct["p50"] * 1e6, 3)
            if pct["p50"] is not None else None,
        "fct_p95_us": round(pct["p95"] * 1e6, 3)
            if pct["p95"] is not None else None,
        "fct_p99_us": round(pct["p99"] * 1e6, 3)
            if pct["p99"] is not None else None,
        "slowdown_mean": round(float(slow[ok].mean()), 4) if ok.any()
            else None,
        "slowdown_p99": round(float(np.percentile(slow[ok], 99)), 4)
            if ok.any() else None,
    }
    if res.tags is not None:
        per_tag: dict = {}
        for tag in dict.fromkeys(res.tags.tolist()):   # stable order
            m = res.tag_mask(tag) & ok
            fct = res.fct_s[m]
            per_tag[str(tag)] = {
                "flows": int(res.tag_mask(tag).sum()),
                "stalled": int((res.tag_mask(tag) & ~ok).sum()),
                "fct_p50_us": round(float(np.percentile(fct, 50)) * 1e6, 3)
                    if fct.size else None,
                "fct_p99_us": round(float(np.percentile(fct, 99)) * 1e6, 3)
                    if fct.size else None,
            }
        row["per_tag"] = per_tag
    return row


@dataclass
class BatchSimResult:
    """Outcome of a serialized sequence of flow batches.

    ``batch_start_s[k]`` / ``batch_finish_s[k]`` bound batch ``k`` on the
    shared fabric clock; ``makespan_s`` is the finish of the last batch.
    ``results[k]`` is the per-batch :class:`FlowSimResult` (its times are
    on the same shared clock).
    """

    batch_start_s: np.ndarray    # (K,)
    batch_finish_s: np.ndarray   # (K,)
    makespan_s: float
    results: "list[FlowSimResult]"

    def batch_span_s(self) -> np.ndarray:
        return self.batch_finish_s - self.batch_start_s


def simulate_flow_batches(router, batches: "list[list[FlowSpec]]",
                          mode: str = "minimal",
                          rate_cap_gbps: "float | np.ndarray | None" = None,
                          gap_s: float = 0.0,
                          net: NetParams = DEFAULT_NET,
                          backend: str = "numpy") -> BatchSimResult:
    """Run dependent flow batches back-to-back on one plane's fabric.

    Batch ``k`` is admitted at the transfer-finish time of batch ``k-1``
    plus ``gap_s`` (e.g. a per-phase software alpha) — the dependency
    structure of a collective schedule, where one phase's flows cannot
    start until the previous phase has drained.  Within a batch, each
    flow's ``start_s`` is relative to the batch admission time, so
    staggered starts inside a phase still work.  Because batches never
    overlap on the fabric, simulating them independently and accumulating
    the clock is exact.

    Incidence extraction goes through the router's pair-level cache
    (``incidence_cached``): a schedule that reuses (src, dst) pairs
    across phases — every collective does — only walks each pair once,
    instead of re-extracting the full batch every phase
    (the ``incidence.walks`` metric counts the actual engine walks).
    """
    if rate_cap_gbps is None:
        rate_cap_gbps = router.topo.port_gbps if hasattr(router, "topo") \
            else router.graph.link_gbps
    t = 0.0
    starts, finishes, results = [], [], []
    for flows in batches:
        starts.append(t)
        if not flows:
            finishes.append(t)
            results.append(None)
            continue
        dem = flows_to_demands(flows)
        inc = flow_incidence(router, dem, mode, cached=True)
        tags = [f.tag for f in flows]
        res = simulate_incidence(
            inc, np.array([f.size_bytes for f in flows]),
            rate_cap_gbps,
            t + np.array([f.start_s for f in flows]),
            net=net, backend=backend,
            tags=tags if any(tg is not None for tg in tags) else None)
        done = np.isfinite(res.finish_s)
        if not done.all():
            raise RuntimeError("stalled flows in batch: fabric has a "
                               "zero-capacity cut for this phase")
        t = float(res.finish_s.max()) + gap_s
        finishes.append(float(res.finish_s.max()))
        results.append(res)
    return BatchSimResult(
        batch_start_s=np.asarray(starts),
        batch_finish_s=np.asarray(finishes),
        makespan_s=finishes[-1] if finishes else 0.0,
        results=results)
