"""Topology-agnostic batched graph routing engine.

:mod:`repro.core.routing_vec` routes by *coordinate arithmetic* and is
therefore MPHX-only; the Table-2 baselines (3-tier Fat-Tree, multi-plane
Fat-Tree, Dragonfly, Dragonfly+) were previously compared through
closed-form bisection bounds, which cannot capture non-minimal path
diversity (FatPaths) — the very thing low-diameter topologies live or die
by.  This module routes over any :class:`~repro.core.topology.SwitchGraph`
instead:

* the multigraph becomes a CSR adjacency with per-edge multiplicity and
  capacity (:class:`CSRGraph`);
* all-pairs hop distances come from a batched frontier BFS (one boolean
  frontier matrix per level — ``numpy`` or ``jax.numpy`` backend, same
  :func:`~repro.core.routing_vec.get_backend` contract);
* a whole demand matrix is routed by **ECMP next-hop splitting**: at every
  switch, flow toward a destination splits over the distance-decreasing
  ("downhill") edges proportionally to link multiplicity, accumulated by
  scatter-add into per-edge loads.  This is a level-by-level *pull* over the
  shortest-path DAG — no path enumeration, O(diameter x E) per destination
  batch.

Routing modes
-------------
``minimal``   ECMP over the shortest-path DAG (multiplicity-weighted).  On
              untrunked MPHX this reproduces ``routing_vec``'s
              ordering-ECMP loads to 1e-9 (pinned by
              ``tests/test_routing_graph.py`` and
              ``results/BENCH_graph_routing.json``); on trunked dims the
              graph engine deliberately weights by physical link count
              where the array engine splits orderings equally.
``valiant``   Classic VLB: route via a uniformly random intermediate switch
              — computed analytically as the two-stage expected load
              (src -> every via at 1/S, via -> dst at 1/S), each stage
              minimal-ECMP.  NOTE: the MPHX array engine's ``valiant`` is
              DAL single-deroute spreading, a *different* non-minimal
              scheme; see ``docs/routing.md``.
``adaptive``  UGAL-style: each demand splits between its minimal DAG and
              the VLB spread, choosing by comparing ``h_min * c_min``
              against ``h_val * c_val`` (hops x congestion, the UGAL
              decision rule) and relaxing the split over a few damped
              rounds.  ``c_min`` is the demand's bottleneck utilization on
              its own minimal DAG (exact, via a backward max-propagation);
              ``c_val`` is the fabric-mean utilization (VLB spreads load
              near-uniformly).

All loads are offered Gbps on *directed* edges; utilization is
load / (multiplicity x link_gbps), matching both existing engines.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..telemetry import span
from .routing_vec import (BaseLinkLoads, DemandArrays, IncidenceCacheMixin,
                          backend_zeros, get_backend)
from .topology import SwitchGraph, Topology

Edge = tuple[int, int]


def _row_scatter_add(xp, mat, rows, vals):
    """mat[rows] += vals along axis 0 (duplicate rows accumulate)."""
    if xp is np:
        np.add.at(mat, rows, vals)
        return mat
    return mat.at[rows].add(vals)


# ---------------------------------------------------------------------------
# CSR adjacency
# ---------------------------------------------------------------------------


@dataclass
class CSRGraph:
    """CSR view of a :class:`SwitchGraph`'s directed edges.

    Directed edge ``e`` leaves ``src[e]`` toward ``dst[e]`` with
    ``mult[e]`` parallel physical links and capacity
    ``cap[e] = mult[e] * link_gbps``.  Edges are sorted by (source,
    target) so edge ids are deterministic.
    """

    graph: SwitchGraph

    def __post_init__(self):
        g = self.graph
        self.n_switches = g.n_switches
        us, vs, mult = g.directed_edge_arrays()
        order = np.lexsort((np.asarray(vs), np.asarray(us)))
        self.src = np.asarray(us, dtype=np.int64)[order]
        self.dst = np.asarray(vs, dtype=np.int64)[order]
        self.mult = np.asarray(mult, dtype=np.float64)[order]
        self.cap = self.mult * g.link_gbps
        self.n_edges = int(self.src.shape[0])
        self.nic_counts = np.asarray(g.nic_counts(), dtype=np.int64)

    def all_pairs_hops(self, xp=np) -> np.ndarray:
        """(S, S) switch-to-switch hop distances via batched frontier BFS.

        One boolean (S, S) frontier per BFS level, expanded with a single
        frontier x adjacency matmul — ``diameter`` matmuls total, on the
        selected backend.  Raises on a disconnected graph.
        """
        S = self.n_switches
        adj = np.zeros((S, S), dtype=np.float32)
        adj[self.src, self.dst] = 1.0
        adj = xp.asarray(adj)
        frontier = xp.eye(S, dtype=bool)
        visited = frontier
        dist = xp.zeros((S, S), dtype=np.int32)
        d = 0
        while True:
            d += 1
            nxt = ((frontier.astype(np.float32) @ adj) > 0) & ~visited
            if not bool(nxt.any()):
                break
            dist = xp.where(nxt, np.int32(d), dist)
            visited = visited | nxt
            frontier = nxt
        visited = np.asarray(visited)
        if not visited.all():
            raise ValueError(f"{self.graph.name}: graph is disconnected")
        return np.asarray(dist)

    def edge_list(self) -> list[Edge]:
        return list(zip(self.src.tolist(), self.dst.tolist()))


# ---------------------------------------------------------------------------
# Link-load result (same API as routing.LinkLoads / routing_vec.ArrayLinkLoads)
# ---------------------------------------------------------------------------


class GraphLinkLoads(BaseLinkLoads):
    """Per-directed-edge loads of a routed demand matrix."""

    def __init__(self, csr: CSRGraph, loads):
        self.csr = csr
        self.loads = loads

    def capacity_array(self) -> np.ndarray:
        return self.csr.cap

    def to_dict(self) -> dict[Edge, float]:
        """Nonzero loads as the legacy ``{(u, v): gbps}`` dict."""
        l = self._np_loads()
        nz = np.nonzero(l)[0]
        return {(int(self.csr.src[e]), int(self.csr.dst[e])): float(l[e])
                for e in nz}


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------


class GraphRouter(IncidenceCacheMixin):
    """Batched routing over any :class:`SwitchGraph` (or any
    :class:`Topology` exposing ``build_graph()``)."""

    def __init__(self, topo_or_graph: "Topology | SwitchGraph",
                 backend: str = "auto", dst_chunk: "int | None" = None):
        if isinstance(topo_or_graph, SwitchGraph):
            graph = topo_or_graph
        else:
            graph = topo_or_graph.build_graph()
        self.graph = graph
        self.csr = CSRGraph(graph)
        self.backend, self.xp = get_backend(backend)
        # destinations routed per batch; auto-sized so the (E, chunk)
        # work matrices stay ~64 MB
        if dst_chunk is None:
            dst_chunk = max(1, int(8e6 // max(self.csr.n_edges, 1)))
        self.dst_chunk = dst_chunk
        self._hops: "np.ndarray | None" = None

    @property
    def hops(self) -> np.ndarray:
        """(S, S) all-pairs switch hop distances (lazy, cached)."""
        if self._hops is None:
            self._hops = self.csr.all_pairs_hops(self.xp)
        return self._hops

    # -------------------------------------------------------- propagation ----

    def _downhill(self, dests: np.ndarray):
        """Downhill structure toward a destination batch.

        Returns ``(dist_to, frac)``: ``dist_to`` (S, C) hop counts,
        ``frac`` (E, C) the ECMP split fraction of edge ``e`` for flow at
        ``src[e]`` headed to ``dests[j]`` (0 on non-downhill edges).
        """
        csr = self.csr
        dist_to = self.hops[:, dests]                       # (S, C)
        down = dist_to[csr.dst] == dist_to[csr.src] - 1     # (E, C)
        w = csr.mult[:, None] * down
        denom = np.zeros((csr.n_switches, dests.shape[0]))
        np.add.at(denom, csr.src, w)
        with np.errstate(divide="ignore", invalid="ignore"):
            frac = np.where(down, w / denom[csr.src], 0.0)
        return dist_to, frac

    def _route_to_dests(self, dests: np.ndarray, inject: np.ndarray, loads):
        """Push ``inject`` (S, C) Gbps minimally toward ``dests``; add the
        resulting edge loads into ``loads`` (E,)."""
        csr, xp = self.csr, self.xp
        dist_to, frac = self._downhill(dests)
        frac = xp.asarray(frac)
        f = xp.asarray(inject)
        for level in range(int(dist_to.max()), 0, -1):
            fa = f * xp.asarray(dist_to == level)
            contrib = frac * fa[csr.src]                    # (E, C)
            loads = loads + contrib.sum(axis=1)
            f = _row_scatter_add(xp, f, csr.dst, contrib)
        return loads

    def _incidence_to_dests(self, dests: np.ndarray, inject: np.ndarray
                            ) -> np.ndarray:
        """Like :meth:`_route_to_dests` but keeps per-column attribution:
        returns ``(E, C)`` — the load each column's injection places on
        every edge (numpy; incidence extraction is sim-scale, not 65K)."""
        csr = self.csr
        dist_to, frac = self._downhill(dests)
        f = np.asarray(inject, dtype=np.float64).copy()
        out = np.zeros((csr.n_edges, dests.shape[0]))
        for level in range(int(dist_to.max()), 0, -1):
            fa = f * (dist_to == level)
            contrib = frac * fa[csr.src]
            out += contrib
            np.add.at(f, csr.dst, contrib)
        return out

    def incidence(self, demands: DemandArrays, mode: str = "minimal"):
        """Per-flow edge incidence of minimal ECMP routing.

        Returns ``(flow, edge, frac)`` COO arrays: ``frac`` is the fraction
        of flow ``flow``'s rate on directed edge ``edge``, so
        scatter-adding ``rates[flow] * frac`` reproduces
        :meth:`route_minimal`'s loads (flow-simulator steady-state
        cross-check, ``tests/test_sim.py``).  ``flow`` indexes rows of
        ``demands``; self-pairs (src == dst) get no entries.  Only
        ``minimal`` has a static per-flow spread here — ``valiant``
        averages over every intermediate switch and ``adaptive`` re-routes
        under load.  Its two phases are the spans ``incidence.walk`` (the
        per-pair walks) and ``incidence.coalesce`` (joining the pairs'
        entries into the COO arrays).
        """
        if mode != "minimal":
            raise ValueError(
                f"no static per-flow incidence for graph-engine mode "
                f"{mode!r} (valiant averages over all intermediates, "
                "adaptive re-routes under load); use minimal")
        with span("incidence.walk"):
            self._count("incidence.walks")
            src = np.asarray(demands.src, dtype=np.int64)
            dst = np.asarray(demands.dst, dtype=np.int64)
            keep = np.flatnonzero(src != dst)
            pairs = np.stack([src[keep], dst[keep]], axis=1)
            upairs, pair_of = np.unique(pairs, axis=0, return_inverse=True)
            # flows grouped by pair: flows_sorted[pair_start[p]:
            # pair_start[p+1]] are the flow rows sharing unique pair p
            order = np.argsort(pair_of, kind="stable")
            flows_sorted = keep[order]
            pair_start = np.searchsorted(pair_of[order],
                                         np.arange(upairs.shape[0] + 1))
            S = self.csr.n_switches
            chunk = min(self.dst_chunk, 256)
            flows, edges, fracs = [], [], []
            for lo in range(0, upairs.shape[0], chunk):
                cols = np.arange(lo, min(lo + chunk, upairs.shape[0]))
                inject = np.zeros((S, cols.shape[0]))
                inject[upairs[cols, 0], np.arange(cols.shape[0])] = 1.0
                out = self._incidence_to_dests(upairs[cols, 1], inject)
                # transposed nonzero scan -> entries arrive grouped by
                # column
                c_idx, e_idx = np.nonzero(out.T)
                vals = out.T[c_idx, e_idx]
                # replicate each column's entry block once per flow of its
                # pair
                n_ent = np.bincount(c_idx, minlength=cols.shape[0])
                ent_start = np.concatenate(([0], np.cumsum(n_ent)))
                for ci, p in enumerate(cols):
                    ent = slice(ent_start[ci], ent_start[ci + 1])
                    for f in flows_sorted[pair_start[p]:pair_start[p + 1]]:
                        flows.append(np.full(int(n_ent[ci]), f,
                                             dtype=np.int64))
                        edges.append(e_idx[ent])
                        fracs.append(vals[ent])
        with span("incidence.coalesce"):
            if not flows:
                z = np.zeros(0, dtype=np.int64)
                return z, z.copy(), np.zeros(0)
            return (np.concatenate(flows), np.concatenate(edges),
                    np.concatenate(fracs))

    def mean_switch_hops(self) -> float:
        """Measured mean switch-switch hops over NIC-weighted switch pairs
        (``hops[u, u] = 0`` same-switch pairs included — the same uniform
        NIC-pair convention as ``MPHX.avg_hops() - 2``)."""
        nics = self.csr.nic_counts.astype(np.float64)
        w = nics / nics.sum()
        return float(w @ self.hops @ w)

    def edge_capacity(self) -> np.ndarray:
        """(E,) directed-edge capacity in Gbps (shared router interface
        with :class:`~repro.core.routing_vec.VectorizedHyperXRouter`)."""
        return self.csr.cap

    def _zeros(self):
        return backend_zeros(self.xp, self.csr.n_edges)

    def _accumulate_minimal(self, src, dst, gbps, loads):
        """ECMP-route (src, dst, gbps) triplets; add into ``loads``."""
        S = self.csr.n_switches
        dests, inv = np.unique(dst, return_inverse=True)
        for lo in range(0, dests.shape[0], self.dst_chunk):
            cols = np.arange(lo, min(lo + self.dst_chunk, dests.shape[0]))
            sel = (inv >= cols[0]) & (inv <= cols[-1])
            inject = np.zeros((S, cols.shape[0]))
            np.add.at(inject, (src[sel], inv[sel] - cols[0]), gbps[sel])
            loads = self._route_to_dests(dests[cols], inject, loads)
        return loads

    # -------------------------------------------------------------- modes ----

    def route(self, demands: DemandArrays, mode: str = "minimal",
              rounds: int = 4) -> GraphLinkLoads:
        if mode == "minimal":
            return self.route_minimal(demands)
        if mode == "valiant":
            return self.route_valiant(demands)
        if mode == "adaptive":
            return self.route_adaptive(demands, rounds=rounds)
        raise ValueError(f"unknown mode {mode}")

    def _prep(self, demands: DemandArrays):
        src = np.asarray(demands.src, dtype=np.int64)
        dst = np.asarray(demands.dst, dtype=np.int64)
        gbps = np.asarray(demands.gbps, dtype=np.float64)
        keep = src != dst
        return src[keep], dst[keep], gbps[keep]

    def route_minimal(self, demands: DemandArrays) -> GraphLinkLoads:
        src, dst, gbps = self._prep(demands)
        return GraphLinkLoads(
            self.csr, self._accumulate_minimal(src, dst, gbps, self._zeros()))

    def route_valiant(self, demands: DemandArrays) -> GraphLinkLoads:
        src, dst, gbps = self._prep(demands)
        return GraphLinkLoads(
            self.csr, self._valiant_loads(src, dst, gbps, self._zeros()))

    def _valiant_loads(self, src, dst, gbps, loads):
        """Expected VLB loads: every demand routes via a uniform random
        intermediate switch, so stage 1 carries each source's total egress
        spread 1/S to every switch and stage 2 each destination's total
        ingress collected 1/S from every switch — both minimal-ECMP."""
        S = self.csr.n_switches
        g_out = np.zeros(S)
        np.add.at(g_out, src, gbps)
        # stage 1: src -> via, for all vias (dest batch = every switch)
        vias = np.arange(S, dtype=np.int64)
        for lo in range(0, S, self.dst_chunk):
            cols = vias[lo:lo + self.dst_chunk]
            inject = np.repeat(g_out[:, None] / S, cols.shape[0], axis=1)
            loads = self._route_to_dests(cols, inject, loads)
        # stage 2: via -> dst, injected equally at every switch
        g_in = np.zeros(S)
        np.add.at(g_in, dst, gbps)
        dests = np.flatnonzero(g_in).astype(np.int64)
        for lo in range(0, dests.shape[0], self.dst_chunk):
            cols = dests[lo:lo + self.dst_chunk]
            inject = np.repeat((g_in[cols] / S)[None, :], S, axis=0)
            loads = self._route_to_dests(cols, inject, loads)
        return loads

    # ----------------------------------------------------- UGAL adaptive ----

    def _bottleneck_to_dests(self, dests: np.ndarray, util: np.ndarray
                             ) -> np.ndarray:
        """(S, C) worst edge utilization on the minimal DAG from every
        switch to each destination (backward max-propagation by level)."""
        csr = self.csr
        dist_to, frac = self._downhill(dests)
        down = frac > 0
        b = np.zeros((csr.n_switches, dests.shape[0]))
        for level in range(1, int(dist_to.max()) + 1):
            cand = np.where(down, np.maximum(util[:, None], b[csr.dst]),
                            -np.inf)
            tmp = np.full_like(b, -np.inf)
            np.maximum.at(tmp, csr.src, cand)
            b = np.where(dist_to == level, tmp, b)
        return b

    def route_adaptive(self, demands: DemandArrays, rounds: int = 4,
                       hop_alpha: float = 0.05) -> GraphLinkLoads:
        """UGAL-style adaptive: per demand, split between minimal ECMP and
        the VLB spread.  Each round compares the UGAL costs
        ``h_min * (c_min + hop_alpha)`` vs ``h_val * (c_val + hop_alpha)``
        under the current loads and damps the split 50% toward the winner
        (``hop_alpha`` keeps minimal preferred at zero load).  This is a
        deterministic batched relaxation of per-packet UGAL — same spirit
        as ``routing_vec``'s parallel-UGAL, generalized to any graph."""
        src, dst, gbps = self._prep(demands)
        csr = self.csr
        if src.size == 0:
            return GraphLinkLoads(csr, self._zeros())
        h_min = self.hops[src, dst].astype(np.float64)
        h_val = self.hops.mean(axis=1)[src] + self.hops.mean(axis=0)[dst]
        dests, inv = np.unique(dst, return_inverse=True)
        phi = np.ones(src.shape[0])          # fraction routed minimally
        loads = None
        for r in range(rounds + 1):
            loads = self._accumulate_minimal(src, dst, gbps * phi,
                                             self._zeros())
            loads = self._valiant_loads(src, dst, gbps * (1 - phi), loads)
            if r == rounds:
                break
            util = GraphLinkLoads(csr, loads).utilization_array()
            c_val = float(util[csr.cap > 0].mean())
            c_min = np.empty(src.shape[0])
            for lo in range(0, dests.shape[0], self.dst_chunk):
                cols = np.arange(lo, min(lo + self.dst_chunk,
                                         dests.shape[0]))
                b = self._bottleneck_to_dests(dests[cols], util)
                sel = (inv >= cols[0]) & (inv <= cols[-1])
                c_min[sel] = b[src[sel], inv[sel] - cols[0]]
            prefer_min = (h_min * (c_min + hop_alpha)
                          <= h_val * (c_val + hop_alpha))
            phi = 0.5 * phi + 0.5 * prefer_min
        return GraphLinkLoads(csr, loads)


# ---------------------------------------------------------------------------
# Generic demand generators (any SwitchGraph, NIC-bearing switches only)
# ---------------------------------------------------------------------------
#
# These generalize the MPHX coordinate generators of ``routing_vec``:
# traffic originates/terminates only at NIC-bearing switches
# (``SwitchGraph.nic_nodes``), each injecting its NIC count's share of
# ``offered_per_nic_gbps`` divided by the plane count (one plane's load,
# like the MPHX builders).  Patterns that need a coordinate system
# (``transpose``) stay MPHX-only.


def _nic_switches(topo: Topology, graph: "SwitchGraph | None"):
    g = graph if graph is not None else topo.build_graph()
    nics = np.asarray(g.nic_counts(), dtype=np.float64)
    nic_sw = np.flatnonzero(nics).astype(np.int64)
    if nic_sw.size < 2:
        raise ValueError(f"{g.name}: needs >= 2 NIC-bearing switches")
    return g, nics, nic_sw


def graph_uniform_demands(topo: Topology, offered_per_nic_gbps: float,
                          graph: "SwitchGraph | None" = None) -> DemandArrays:
    """Every NIC sprays uniformly over all *other* NIC-bearing switches,
    weighted by destination NIC count."""
    g, nics, nic_sw = _nic_switches(topo, graph)
    out = nics * offered_per_nic_gbps / topo.n_planes
    s, d = np.meshgrid(nic_sw, nic_sw, indexing="ij")
    mask = s != d
    s, d = s[mask], d[mask]
    total = nics.sum()
    gbps = out[s] * nics[d] / (total - nics[s])
    return DemandArrays(s, d, gbps)


def graph_shift_demands(topo: Topology, offered_per_nic_gbps: float,
                        graph: "SwitchGraph | None" = None) -> DemandArrays:
    """+1 shift over NIC-bearing switches in id order (the generic
    analogue of the MPHX dim-0 neighbor shift: a permutation with a single
    'adjacent' target per switch)."""
    g, nics, nic_sw = _nic_switches(topo, graph)
    out = nics * offered_per_nic_gbps / topo.n_planes
    dst = np.roll(nic_sw, -1)
    return DemandArrays(nic_sw, dst, out[nic_sw])


def graph_reverse_demands(topo: Topology, offered_per_nic_gbps: float,
                          graph: "SwitchGraph | None" = None) -> DemandArrays:
    """Reverse pairing (switch k -> switch K-1-k over NIC-bearing switches
    in id order) — the generic analogue of MPHX bit-complement: every
    demand crosses the whole fabric."""
    g, nics, nic_sw = _nic_switches(topo, graph)
    out = nics * offered_per_nic_gbps / topo.n_planes
    dst = nic_sw[::-1].copy()
    keep = nic_sw != dst
    return DemandArrays(nic_sw[keep], dst[keep], out[nic_sw][keep])


def graph_hotspot_demands(topo: Topology, offered_per_nic_gbps: float,
                          graph: "SwitchGraph | None" = None,
                          hot_fraction: float = 0.5) -> DemandArrays:
    """``hot_fraction`` of every switch's load incasts on the first
    NIC-bearing switch; the rest sprays uniformly."""
    g, nics, nic_sw = _nic_switches(topo, graph)
    uni = graph_uniform_demands(topo, offered_per_nic_gbps * (1 - hot_fraction),
                                graph=g)
    hot = int(nic_sw[0])
    out = nics * offered_per_nic_gbps * hot_fraction / topo.n_planes
    srcs = nic_sw[nic_sw != hot]
    return DemandArrays(
        np.concatenate([uni.src, srcs]),
        np.concatenate([uni.dst, np.full(srcs.shape[0], hot,
                                         dtype=np.int64)]),
        np.concatenate([uni.gbps, out[srcs]]),
    )


def graph_ring_demands(topo: Topology, offered_per_nic_gbps: float,
                       graph: "SwitchGraph | None" = None) -> DemandArrays:
    """Steady-state link pattern of a ring collective over NIC-bearing
    switches in id order (same convention as ``routing_vec.ring_demands``)."""
    return graph_shift_demands(topo, offered_per_nic_gbps, graph=graph)
