"""Vectorized HyperX routing — batched array engine behind ``repro.experiments``.

The legacy :mod:`repro.core.routing` enumerates per-flow Python paths and
accumulates link loads into dicts; that cannot reach Table-2 scale
(MPHX(4,86,86,9) hosts 66,564 NICs) or sweep many traffic scenarios.  This
module recomputes the same quantities over batched integer/float arrays:

* a whole demand matrix is three parallel arrays ``(src, dst, gbps)``;
* directed links of one plane live in a flat *edge-slot* tensor indexed by
  ``(switch, dimension, target coordinate)`` (:class:`EdgeIndex`);
* path enumeration becomes a walk over dimension *orderings* shared by all
  demands, and link-load accounting a scatter-add over edge slots
  (``np.bincount`` / ``jnp .at[].add``) instead of dict updates.

Equivalence with the legacy router (mode ``minimal`` and ``valiant``) is
exact — the ECMP split over orderings/deroutes is reproduced analytically —
whenever the legacy router does not randomly subsample paths, i.e. for
``m! <= max_orderings`` and ``n_deroutes <= max_paths``; this holds for every
small topology the tests compare on, and ``tests/test_experiments.py`` pins
it to 1e-9.  Mode ``adaptive`` is a *parallel* UGAL/DAL relaxation (loads
update once per quantum round across all demands, not after every greedy
placement), so it tracks but does not bit-match the legacy greedy router.

Backend: ``jax.numpy`` when available (``backend="jax"`` or ``"auto"``),
plain numpy otherwise — the engine is pure index arithmetic, so both give
identical results.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Sequence

import numpy as np

from ..telemetry import span
from .hyperx import MPHX

Edge = tuple[int, int]


# ---------------------------------------------------------------------------
# Backend selection
# ---------------------------------------------------------------------------


def get_backend(backend: str = "auto"):
    """Return ``(name, xp)`` — ``jax.numpy`` with a numpy fallback.

    ``auto`` picks jax only when 64-bit mode is on: without
    ``jax_enable_x64`` the accumulators truncate to float32, which would
    break the 1e-9 equivalence guarantee against the legacy dict engine.
    """
    if backend == "numpy":
        return "numpy", np
    if backend in ("auto", "jax"):
        try:
            import jax
            import jax.numpy as jnp

            if backend == "jax" or jax.config.jax_enable_x64:
                return "jax", jnp
        except ImportError:
            if backend == "jax":
                raise
        return "numpy", np
    raise ValueError(f"unknown backend {backend!r}")


def _scatter_add(xp, loads, idx, w):
    """loads[idx] += w, vectorized (duplicate indices accumulate)."""
    if xp is np:
        loads += np.bincount(idx, weights=w, minlength=loads.size)
        return loads
    return loads.at[idx].add(w)


def backend_zeros(xp, n: int):
    """A length-``n`` float accumulator on the selected backend (float64
    under numpy or jax-x64, float32 otherwise)."""
    if xp is np:
        return np.zeros(n)
    import jax

    dtype = xp.float64 if jax.config.jax_enable_x64 else xp.float32
    return xp.zeros(n, dtype=dtype)


class BaseLinkLoads:
    """Shared result API of the batched routing engines.

    Subclasses hold per-link ``loads`` (offered Gbps, backend array) and
    expose the matching capacities via :meth:`capacity_array`; everything
    downstream (``netsim.load_sweep``, the sweep suite, benchmarks) only
    touches this interface.
    """

    loads = None  # set by subclasses

    def capacity_array(self) -> np.ndarray:
        raise NotImplementedError

    def _np_loads(self) -> np.ndarray:
        return np.asarray(self.loads)

    def utilization_array(self) -> np.ndarray:
        l = self._np_loads()
        cap = self.capacity_array()
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(cap > 0, l / cap, 0.0)

    def max_utilization(self) -> float:
        u = self.utilization_array()
        return float(u.max()) if u.size else 0.0

    def mean_utilization(self) -> float:
        """Mean over *loaded* slots (legacy averages over its dict entries)."""
        u = self.utilization_array()
        nz = self._np_loads() > 0
        return float(u[nz].mean()) if nz.any() else 0.0

    def saturation_throughput(self, offered_per_nic_gbps: float = 0.0) -> float:
        mx = self.max_utilization()
        return 1.0 if mx == 0 else min(1.0, 1.0 / mx)

    def total_load(self) -> float:
        return float(self._np_loads().sum())


# ---------------------------------------------------------------------------
# Edge-slot tensor
# ---------------------------------------------------------------------------


@dataclass
class EdgeIndex:
    """Flat index over the directed links of one MPHX plane.

    Slot of the directed link leaving switch ``u`` along dimension ``i``
    toward in-dimension coordinate ``c``:

        slot(u, i, c) = dim_base[i] + u * dims[i] + c

    ``dim_base[i] = S * sum(dims[:i])``.  Slots with ``c == coord_i(u)``
    (self-links) exist in the tensor but never receive load.  Capacity of
    every dim-``i`` slot is ``multiplicity_i * port_gbps`` where
    ``multiplicity_i = links_per_dim[i] / (dims[i] - 1)`` — MPHX trunking
    (Table 2's MPHX(4,86,86,9)) is uniform within a dimension.
    """

    topo: MPHX

    def __post_init__(self):
        t = self.topo
        dims = np.asarray(t.dims, dtype=np.int64)
        self.dims = dims
        self.D = len(t.dims)
        self.S = t.switches_per_plane
        self.dim_base = self.S * np.concatenate(
            ([0], np.cumsum(dims)[:-1])).astype(np.int64)
        self.n_slots = int(self.S * dims.sum())
        # coord <-> id strides (row-major, matching MPHX.coord_to_id)
        stride = np.ones(self.D, dtype=np.int64)
        for i in range(self.D - 2, -1, -1):
            stride[i] = stride[i + 1] * dims[i + 1]
        self.stride = stride
        # per-slot capacity in Gbps
        mult = np.array([l / (d - 1) if d > 1 else 0.0
                         for d, l in zip(t.dims, t.links_per_dim)])
        cap = np.empty(self.n_slots, dtype=np.float64)
        for i in range(self.D):
            lo = self.dim_base[i]
            hi = lo + self.S * dims[i]
            cap[lo:hi] = mult[i] * t.port_gbps
        self.capacity = cap

    # ------------------------------------------------------------ coords ----

    def ids_to_coords(self, ids: np.ndarray) -> np.ndarray:
        """(M,) switch ids -> (M, D) coordinates."""
        out = np.empty((ids.shape[0], self.D), dtype=np.int64)
        rem = ids.astype(np.int64)
        for i in range(self.D - 1, -1, -1):
            out[:, i] = rem % self.dims[i]
            rem = rem // self.dims[i]
        return out

    def coords_to_ids(self, coords: np.ndarray) -> np.ndarray:
        return coords @ self.stride

    def slots(self, u_ids, dim: int, c_target):
        return self.dim_base[dim] + u_ids * int(self.dims[dim]) + c_target

    def slot_to_edge(self, slot: int) -> Edge:
        """Flat slot -> directed (u, v) switch pair."""
        dim = int(np.searchsorted(self.dim_base, slot, side="right") - 1)
        rel = slot - int(self.dim_base[dim])
        u, c = divmod(rel, int(self.dims[dim]))
        coord = list(self.topo.id_to_coord(u))
        coord[dim] = c
        return u, self.topo.coord_to_id(tuple(coord))


class ArrayLinkLoads(BaseLinkLoads):
    """Array counterpart of :class:`repro.core.routing.LinkLoads`."""

    def __init__(self, index: EdgeIndex, loads):
        self.index = index
        self.topo = index.topo
        self.loads = loads

    def capacity_array(self) -> np.ndarray:
        return self.index.capacity

    def to_dict(self) -> dict[Edge, float]:
        """Nonzero loads as the legacy ``{(u, v): gbps}`` dict."""
        l = self._np_loads()
        out = {}
        for slot in np.nonzero(l)[0]:
            out[self.index.slot_to_edge(int(slot))] = float(l[slot])
        return out


# ---------------------------------------------------------------------------
# Demand matrices as arrays
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DemandArrays:
    """A switch-level traffic matrix as three parallel arrays."""

    src: np.ndarray    # (M,) int64 switch ids
    dst: np.ndarray    # (M,) int64 switch ids
    gbps: np.ndarray   # (M,) float64 offered Gbps per (src, dst) pair

    def __post_init__(self):
        assert self.src.shape == self.dst.shape == self.gbps.shape

    @property
    def n(self) -> int:
        return int(self.src.shape[0])

    def total_gbps(self) -> float:
        return float(self.gbps.sum())

    def to_dict(self) -> dict[Edge, float]:
        out: dict[Edge, float] = {}
        # accumulate: a matrix may list the same (src, dst) pair twice
        # (e.g. hotspot = uniform part + incast part)
        for s, d, g in zip(self.src, self.dst, self.gbps):
            key = (int(s), int(d))
            out[key] = out.get(key, 0.0) + float(g)
        return out


def demands_from_dict(demands: dict[Edge, float]) -> DemandArrays:
    if not demands:
        z = np.zeros(0, dtype=np.int64)
        return DemandArrays(z, z.copy(), np.zeros(0))
    items = sorted(demands.items())
    src = np.array([s for (s, _), _ in items], dtype=np.int64)
    dst = np.array([d for (_, d), _ in items], dtype=np.int64)
    g = np.array([v for _, v in items], dtype=np.float64)
    return DemandArrays(src, dst, g)


def _per_switch_out(topo: MPHX, offered_per_nic_gbps: float) -> float:
    # one plane's share of each switch's p NICs worth of injection
    return topo.p * offered_per_nic_gbps / topo.n


def uniform_demands(topo: MPHX, offered_per_nic_gbps: float) -> DemandArrays:
    """All-pairs uniform spray (matches ``routing.uniform_traffic``)."""
    S = topo.switches_per_plane
    s, d = np.meshgrid(np.arange(S, dtype=np.int64),
                       np.arange(S, dtype=np.int64), indexing="ij")
    mask = s != d
    g = np.full(int(mask.sum()),
                _per_switch_out(topo, offered_per_nic_gbps) / (S - 1))
    return DemandArrays(s[mask], d[mask], g)


def neighbor_shift_demands(topo: MPHX, offered_per_nic_gbps: float,
                           dim: int = 0) -> DemandArrays:
    """+1 shift along ``dim`` (adversarial for minimal routing, §5.2)."""
    idx = EdgeIndex(topo)
    src = np.arange(topo.switches_per_plane, dtype=np.int64)
    c = idx.ids_to_coords(src)
    c[:, dim] = (c[:, dim] + 1) % topo.dims[dim]
    dst = idx.coords_to_ids(c)
    g = np.full(src.shape, _per_switch_out(topo, offered_per_nic_gbps))
    return DemandArrays(src, dst, g)


def bit_complement_demands(topo: MPHX, offered_per_nic_gbps: float
                           ) -> DemandArrays:
    idx = EdgeIndex(topo)
    src = np.arange(topo.switches_per_plane, dtype=np.int64)
    c = idx.ids_to_coords(src)
    cc = (np.asarray(topo.dims, dtype=np.int64) - 1)[None, :] - c
    dst = idx.coords_to_ids(cc)
    keep = dst != src
    g = np.full(src.shape, _per_switch_out(topo, offered_per_nic_gbps))
    return DemandArrays(src[keep], dst[keep], g[keep])


def transpose_demands(topo: MPHX, offered_per_nic_gbps: float) -> DemandArrays:
    """Matrix-transpose permutation: swap the first two (equal) dims.

    Classic adversarial pattern for dimension-ordered routing; defined when
    the topology has >= 2 dimensions and ``dims[0] == dims[1]``.
    """
    if topo.D < 2 or topo.dims[0] != topo.dims[1]:
        raise ValueError(f"transpose undefined for dims={topo.dims}")
    idx = EdgeIndex(topo)
    src = np.arange(topo.switches_per_plane, dtype=np.int64)
    c = idx.ids_to_coords(src)
    ct = c.copy()
    ct[:, 0], ct[:, 1] = c[:, 1], c[:, 0]
    dst = idx.coords_to_ids(ct)
    keep = dst != src
    g = np.full(src.shape, _per_switch_out(topo, offered_per_nic_gbps))
    return DemandArrays(src[keep], dst[keep], g[keep])


def hotspot_demands(topo: MPHX, offered_per_nic_gbps: float,
                    hot: int = 0, hot_fraction: float = 0.5) -> DemandArrays:
    """Every switch sends ``hot_fraction`` of its load to one hot switch and
    sprays the rest uniformly (incast — the hot switch's access links and
    surrounding fabric saturate first)."""
    uni = uniform_demands(topo, offered_per_nic_gbps * (1 - hot_fraction))
    src = np.arange(topo.switches_per_plane, dtype=np.int64)
    keep = src != hot
    g = np.full(src.shape,
                _per_switch_out(topo, offered_per_nic_gbps) * hot_fraction)
    return DemandArrays(
        np.concatenate([uni.src, src[keep]]),
        np.concatenate([uni.dst, np.full(int(keep.sum()), hot,
                                         dtype=np.int64)]),
        np.concatenate([uni.gbps, g[keep]]),
    )


def ring_demands(topo: MPHX, offered_per_nic_gbps: float) -> DemandArrays:
    """Steady-state link pattern of a switch-id-ordered ring collective
    (ring all-reduce / all-gather): switch s -> s+1 mod S at full rate."""
    S = topo.switches_per_plane
    src = np.arange(S, dtype=np.int64)
    dst = (src + 1) % S
    g = np.full(S, _per_switch_out(topo, offered_per_nic_gbps))
    return DemandArrays(src, dst, g)


# ---------------------------------------------------------------------------
# Vectorized router
# ---------------------------------------------------------------------------


class IncidenceCacheMixin:
    """Pair-level cache for per-flow incidence extraction.

    A fixed path spread depends only on the (src, dst) switch pair and the
    mode — not on the offered Gbps — so the per-pair COO rows
    ``(edge_slots, fracs)`` can be reused across flow sets.  The epoch /
    batch loops of the flow simulator re-extract the same pairs over and
    over (collective phases reuse a schedule's pairs every phase; epoch
    re-solves reuse the whole flow set); routing them through
    :meth:`incidence_cached` only walks pairs never seen before.

    Cache effectiveness is reported uniformly by both engines through a
    per-router :class:`~repro.telemetry.MetricsRegistry`
    (``router.metrics``): ``incidence.walks`` counts *engine walks* (full
    :meth:`incidence` extractions — the hook ``tests/test_sim_scale.py``
    uses to assert re-solves stop re-extracting), and
    ``incidence.cache_hits`` / ``incidence.cache_misses`` count pairs
    served from / added to the cache.  When an ambient registry is
    collecting (:func:`repro.telemetry.collecting`), the same events are
    mirrored there.  Invalidate with :meth:`reset_incidence_cache`
    after anything that changes routes (e.g. failure masking builds a new
    router, which starts cold anyway).
    """

    @property
    def metrics(self):
        """This router's private metrics registry (lazy)."""
        m = getattr(self, "_metrics", None)
        if m is None:
            from ..telemetry import MetricsRegistry
            m = self._metrics = MetricsRegistry()
        return m

    @metrics.setter
    def metrics(self, registry) -> None:
        self._metrics = registry

    def _count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to counter ``name`` in this router's registry and in
        the ambient one."""
        from ..telemetry import get_metrics
        for reg in (self.metrics, get_metrics()):
            reg.inc(name, n)

    def _pair_cache(self, mode: str) -> dict:
        if not hasattr(self, "_inc_cache"):
            self._inc_cache: dict = {}
        return self._inc_cache.setdefault(mode, {})

    def reset_incidence_cache(self) -> None:
        self._inc_cache = {}

    def incidence_cached(self, demands: "DemandArrays", mode: str = "minimal"):
        """:meth:`incidence`, but only walking (src, dst) pairs not in the
        cache; cached pairs' rows are replayed.  Same COO contract (rows
        grouped by flow, slot-sorted within a flow)."""
        cache = self._pair_cache(mode)
        src = np.asarray(demands.src, dtype=np.int64)
        dst = np.asarray(demands.dst, dtype=np.int64)
        uniq, inv = np.unique(np.stack([src, dst], axis=1), axis=0,
                              return_inverse=True)
        pairs = [tuple(p) for p in uniq.tolist()]
        miss = [p for p in pairs if p not in cache]
        self._count("incidence.cache_hits", len(pairs) - len(miss))
        self._count("incidence.cache_misses", len(miss))
        if miss:
            ma = np.asarray(miss, dtype=np.int64)
            sub = DemandArrays(ma[:, 0], ma[:, 1], np.ones(ma.shape[0]))
            f, s, fr = self.incidence(sub, mode)
            order = np.argsort(f, kind="stable")
            f, s, fr = f[order], s[order], fr[order]
            bounds = np.searchsorted(f, np.arange(ma.shape[0] + 1))
            for j, p in enumerate(miss):
                lo, hi = int(bounds[j]), int(bounds[j + 1])
                cache[p] = (s[lo:hi], fr[lo:hi])
        per_pair = [cache[p] for p in pairs]
        counts = np.array([e.size for e, _ in per_pair], dtype=np.int64)
        n = src.shape[0]
        if n == 0 or int(counts[inv].sum()) == 0:
            z = np.zeros(0, dtype=np.int64)
            return z, z.copy(), np.zeros(0)
        flow = np.repeat(np.arange(n, dtype=np.int64), counts[inv])
        edge = np.concatenate([per_pair[j][0] for j in inv])
        frac = np.concatenate([per_pair[j][1] for j in inv])
        return flow, edge, frac


class VectorizedHyperXRouter(IncidenceCacheMixin):
    """Array engine for routing whole demand matrices over one MPHX plane."""

    def __init__(self, topo: MPHX, backend: str = "auto"):
        self.topo = topo
        self.index = EdgeIndex(topo)
        self.backend, self.xp = get_backend(backend)

    # ------------------------------------------------------------ helpers ----

    def _prep(self, demands: DemandArrays):
        src = np.asarray(demands.src, dtype=np.int64)
        dst = np.asarray(demands.dst, dtype=np.int64)
        gbps = np.asarray(demands.gbps, dtype=np.float64)
        cs = self.index.ids_to_coords(src)
        cd = self.index.ids_to_coords(dst)
        return src, dst, gbps, cs, cd

    def _zeros(self):
        return backend_zeros(self.xp, self.index.n_slots)

    def _iter_minimal_hops(self, src, cs, cd):
        """Yield ``(slots, mask)`` per hop of every D! full-dimension
        ordering — the single source of truth for the minimal walk, shared
        by load accounting (:meth:`_walk_minimal`) and per-flow incidence
        extraction (:meth:`incidence`, for the flow simulator)."""
        idx = self.index
        for perm in itertools.permutations(range(idx.D)):
            cur_id = src.copy()
            cur = cs.copy()
            for i in perm:
                mask = cur[:, i] != cd[:, i]
                if mask.any():   # skip the O(M) slot math on matched dims
                    yield idx.slots(cur_id, i, cd[:, i]), mask
                cur_id = cur_id + (cd[:, i] - cur[:, i]) * idx.stride[i]
                cur[:, i] = cd[:, i]

    def _walk_minimal(self, loads, src, gbps, cs, cd, perm_weight):
        """Add minimal ECMP loads.  ``perm_weight`` (M,) is the Gbps each of
        the D! full-dimension orderings carries for each demand; a distinct
        mismatched-dim ordering is induced by D!/m! full orderings, so every
        minimal path receives ``perm_weight * D!/m!`` total — set
        ``perm_weight = gbps/D!`` for the plain gbps/m! ECMP split."""
        xp = self.xp
        for slots, mask in self._iter_minimal_hops(src, cs, cd):
            loads = _scatter_add(xp, loads, slots[mask], perm_weight[mask])
        return loads

    def _mismatch_stats(self, cs, cd):
        mism = cs != cd                      # (M, D)
        m = mism.sum(axis=1)                 # mismatched dims per demand
        fact = np.array([math.factorial(k) for k in range(self.index.D + 1)])
        n_minimal = fact[m]                  # m! minimal paths
        dims = np.asarray(self.topo.dims, dtype=np.int64)
        n_deroute = (mism * np.maximum(dims - 2, 0)[None, :]).sum(axis=1)
        return mism, m, n_minimal, n_deroute

    # ------------------------------------------------------------- modes ----

    def route(self, demands: DemandArrays, mode: str = "minimal",
              granularity: int = 8) -> ArrayLinkLoads:
        if mode == "minimal":
            return self.route_minimal(demands)
        if mode == "valiant":
            return self.route_valiant(demands)
        if mode == "adaptive":
            return self.route_adaptive(demands, granularity)
        raise ValueError(f"unknown mode {mode}")

    def route_minimal(self, demands: DemandArrays) -> ArrayLinkLoads:
        src, dst, gbps, cs, cd = self._prep(demands)
        n_perms = math.factorial(self.index.D)
        loads = self._walk_minimal(self._zeros(), src, gbps, cs, cd,
                                   gbps / n_perms)
        return ArrayLinkLoads(self.index, loads)

    def _iter_deroute_hops(self, src, cs, cd, mism):
        """Yield ``(slots, mask)`` per hop of every single-deroute DAL path
        (src -> dim ``i`` := ``via`` -> fix dims in index order) — shared by
        :meth:`route_valiant` and :meth:`incidence`."""
        idx = self.index
        dims = self.topo.dims
        for i in range(idx.D):
            for via in range(dims[i]):
                mask = mism[:, i] & (cs[:, i] != via) & (cd[:, i] != via)
                if not mask.any():
                    continue
                yield idx.slots(src, i, np.full_like(src, via)), mask
                cur_id = src + (via - cs[:, i]) * idx.stride[i]
                cur = cs.copy()
                cur[:, i] = via
                for j in range(idx.D):
                    step = mask & (cur[:, j] != cd[:, j])
                    if step.any():   # skip the O(M) slot math on idle hops
                        yield idx.slots(cur_id, j, cd[:, j]), step
                    cur_id = cur_id + (cd[:, j] - cur[:, j]) * idx.stride[j]
                    cur[:, j] = cd[:, j]

    def route_valiant(self, demands: DemandArrays) -> ArrayLinkLoads:
        """Minimal + all single-deroute DAL paths, load split equally —
        the legacy ``mode="valiant"`` spread, computed in one batch."""
        src, dst, gbps, cs, cd = self._prep(demands)
        idx, xp = self.index, self.xp
        if np.any(src == dst):
            raise ValueError("valiant routing expects src != dst demands")
        mism, m, n_minimal, n_deroute = self._mismatch_stats(cs, cd)
        n_paths = (n_minimal + n_deroute).astype(np.float64)
        per_path = gbps / n_paths
        # minimal component: each of the m! minimal paths carries per_path
        n_full = math.factorial(idx.D)
        loads = self._walk_minimal(self._zeros(), src, gbps, cs, cd,
                                   per_path * n_minimal / n_full)
        # deroute component: src -> (dim i := via) -> fix dims in index order
        for slots, mask in self._iter_deroute_hops(src, cs, cd, mism):
            loads = _scatter_add(xp, loads, slots[mask], per_path[mask])
        return ArrayLinkLoads(self.index, loads)

    # ------------------------------------------------- per-flow incidence ----

    def incidence(self, demands: DemandArrays, mode: str = "minimal"):
        """Per-flow edge incidence of a fixed-spread routing mode.

        Returns ``(flow, slot, frac)`` COO int64/int64/float64 arrays where
        ``frac`` is the fraction of flow ``flow``'s rate carried on edge
        slot ``slot`` — so scatter-adding ``rates[flow] * frac`` over slots
        reproduces :meth:`route`'s loads exactly (the flow simulator's
        steady-state cross-validation, ``tests/test_sim.py``).  ``flow``
        indexes rows of ``demands``; entries are flow-major and
        slot-ascending within a flow.  Supported modes are the fixed path
        spreads: ``minimal`` (ordering ECMP) and ``valiant`` (DAL
        deroutes); ``adaptive`` re-routes under load and has no static
        incidence.  Its phases are the spans ``incidence.walk`` (the hop
        walks) and either ``incidence.place`` (DAL on a 1D plane, whose
        paths share no edge: each entry written straight into its place)
        or ``incidence.coalesce`` (merging duplicate (flow, slot)
        entries).  The counters ``incidence.entries`` and
        ``incidence.merged`` count the entries returned and those the
        coalesce merged away.
        """
        with span("incidence.walk"):
            self._count("incidence.walks")
            src, dst, gbps, cs, cd = self._prep(demands)
            n_full = math.factorial(self.index.D)
            flows, slots_l, fracs = [], [], []

            def emit(slots, mask, w):
                f = np.flatnonzero(mask)
                if f.size:
                    flows.append(f)
                    slots_l.append(slots[mask])
                    fracs.append(w[mask] if w.ndim else np.full(f.size, w))

            if mode == "minimal":
                w = np.float64(1.0 / n_full)
                for slots, mask in self._iter_minimal_hops(src, cs, cd):
                    emit(slots, mask, w)
            elif mode == "valiant":
                if np.any(src == dst):
                    raise ValueError(
                        "valiant routing expects src != dst demands")
                mism, m, n_minimal, n_deroute = self._mismatch_stats(cs, cd)
                n_paths = (n_minimal + n_deroute).astype(np.float64)
                w_min = n_minimal / (n_paths * n_full)
                w_der = 1.0 / n_paths
                for slots, mask in self._iter_minimal_hops(src, cs, cd):
                    emit(slots, mask, w_min)
                for slots, mask in self._iter_deroute_hops(src, cs, cd,
                                                           mism):
                    emit(slots, mask, w_der)
            else:
                raise ValueError(
                    f"no static per-flow incidence for mode {mode!r} "
                    "(adaptive re-routes under load); use minimal or "
                    "valiant")
        if mode == "valiant" and self._disjoint_paths():
            with span("incidence.place"):
                out = self._place_1d(src, dst, flows, slots_l, fracs)
            merged = 0
        else:
            with span("incidence.coalesce"):
                out, merged = self._coalesce(flows, slots_l, fracs)
        self._count("incidence.entries", out[0].size)
        self._count("incidence.merged", merged)
        return out

    def _disjoint_paths(self) -> bool:
        """Whether no two DAL paths of one flow share an edge: true on a
        1D plane, where a flow s -> d takes the link s->d and, for every
        other switch v, the links s->v and v->d.  With more dimensions
        the D! minimal orderings of a flow meet on shared hops, and
        deroutes rejoin the minimal paths."""
        return self.index.D == 1

    def _coalesce(self, flows, slots_l, fracs):
        """Join the walk's entries and sum duplicate (flow, slot) pairs;
        returns the COO arrays and the number of entries merged away."""
        if not flows:
            z = np.zeros(0, dtype=np.int64)
            return (z, z.copy(), np.zeros(0)), 0
        flow = np.concatenate(flows)
        slot = np.concatenate(slots_l)
        frac = np.concatenate(fracs)
        key = flow * np.int64(self.index.n_slots) + slot
        uniq, inv = np.unique(key, return_inverse=True)
        out = np.zeros(uniq.size)
        np.add.at(out, inv, frac)
        return ((uniq // self.index.n_slots, uniq % self.index.n_slots, out),
                key.size - uniq.size)

    def _place_1d(self, src, dst, flows, slots_l, fracs):
        """Write the entries of a DAL walk on a 1D plane of ``n`` switches
        straight into flow-major, slot-ascending COO arrays: the coalesce's
        output, bit for bit, since no entry has a duplicate to sum.

        A flow s -> d has ``2n - 3`` entries.  Its slots in ascending
        order are the second hops v->d with v < s, then every s->c
        (c != s: the minimal hop and the first hops), then the second
        hops with v > s.  So the rank within the flow of slot ``u*n + c``
        is ``u - [d < u]``, plus ``c - [s < c]`` where u == s and
        ``n - 2`` where u > s.
        """
        n = int(self.index.dims[0])
        per_flow = 2 * n - 3
        n_flows = src.shape[0]
        emitted = sum(f.size for f in flows)
        if emitted != n_flows * per_flow:
            raise RuntimeError(
                f"the DAL walk emitted {emitted} entries for {n_flows} "
                f"flows, not {per_flow} a flow: its paths are not the "
                "disjoint ones that the in-place writer places")
        first = np.arange(n_flows, dtype=np.int64) * per_flow
        slot = np.empty(n_flows * per_flow, dtype=np.int64)
        frac = np.empty(n_flows * per_flow)
        for f, x, w in zip(flows, slots_l, fracs):
            s, d = src[f], dst[f]
            u, c = np.divmod(x, n)
            rank = u - (d < u)
            rank += np.where(u == s, c - (s < c), 0)
            rank += np.where(u > s, n - 2, 0)
            pos = first[f] + rank
            slot[pos] = x
            frac[pos] = w
        flow = np.repeat(np.arange(n_flows, dtype=np.int64), per_flow)
        return flow, slot, frac

    def mean_switch_hops(self) -> float:
        """Expected switch-switch minimal hops over uniform NIC pairs
        (coordinates differ in dim ``i`` with probability ``(D_i-1)/D_i``)."""
        return float(sum((d - 1) / d for d in self.topo.dims if d > 1))

    def edge_capacity(self) -> np.ndarray:
        """(n_slots,) per-edge-slot capacity in Gbps (shared router
        interface with :class:`~repro.core.routing_graph.GraphRouter`)."""
        return self.index.capacity

    # ------------------------------------------------- parallel UGAL/DAL ----

    def _candidate_paths(self, src, cs, cd):
        """Enumerate candidate paths as slot matrices.

        Returns a list of ``(slots, valid)`` pairs, one per candidate:
        ``slots`` (M, hops) edge slots (entries only meaningful where the
        hop mask is set), ``valid`` (M, hops) bool.  Candidates are the D!
        minimal orderings plus every (dim, via) single deroute.
        """
        idx = self.index
        cands = []
        for perm in itertools.permutations(range(idx.D)):
            cur_id = src.copy()
            cur = cs.copy()
            slots, valid = [], []
            for i in perm:
                mask = cur[:, i] != cd[:, i]
                slots.append(idx.slots(cur_id, i, cd[:, i]))
                valid.append(mask)
                cur_id = cur_id + (cd[:, i] - cur[:, i]) * idx.stride[i]
                cur[:, i] = cd[:, i]
            cands.append((np.stack(slots, 1), np.stack(valid, 1), None))
        dims = self.topo.dims
        mism = cs != cd
        for i in range(idx.D):
            for via in range(dims[i]):
                usable = mism[:, i] & (cs[:, i] != via) & (cd[:, i] != via)
                if not usable.any():
                    continue
                slots, valid = [], []
                slots.append(idx.slots(src, i, np.full_like(src, via)))
                valid.append(usable)
                cur_id = src + (via - cs[:, i]) * idx.stride[i]
                cur = cs.copy()
                cur[:, i] = via
                for j in range(idx.D):
                    step = usable & (cur[:, j] != cd[:, j])
                    slots.append(idx.slots(cur_id, j, cd[:, j]))
                    valid.append(step)
                    cur_id = cur_id + (cd[:, j] - cur[:, j]) * idx.stride[j]
                    cur[:, j] = cd[:, j]
                cands.append((np.stack(slots, 1), np.stack(valid, 1), usable))
        return cands

    def route_adaptive(self, demands: DemandArrays, granularity: int = 8,
                       sub_batches: int = 8) -> ArrayLinkLoads:
        """Parallel UGAL/DAL: ``granularity`` quantum rounds; per round every
        demand places one quantum on its least-bottlenecked candidate
        (minimal orderings + single deroutes), with the same 0.01/hop
        penalty the legacy greedy router uses.  Link loads refresh between
        ``sub_batches`` interleaved demand groups within each round — with
        one demand per group this *is* the legacy sequential greedy; with
        large groups it is an idealized parallel relaxation that tracks,
        but does not bit-match, the legacy router."""
        src, dst, gbps, cs, cd = self._prep(demands)
        idx, xp = self.index, self.xp
        loads = self._zeros()
        cands = self._candidate_paths(src, cs, cd)
        quantum = gbps / granularity
        safe_cap = np.where(idx.capacity > 0, idx.capacity, np.inf)
        M = src.shape[0]
        # deterministic per-(demand, candidate) jitter: equal-cost candidates
        # would otherwise tie-break identically across the whole batch and
        # herd every demand onto the same deroute each round
        jitter = np.random.default_rng(0).random((M, len(cands))) * 1e-5
        batches = [np.arange(b, M, sub_batches) for b in range(sub_batches)
                   if b < M]
        for _ in range(granularity):
            for rows in batches:
                l_np = np.asarray(loads)
                q = quantum[rows]
                costs = np.full((rows.size, len(cands)), np.inf)
                for k, (slots, valid, usable) in enumerate(cands):
                    sl, va = slots[rows], valid[rows]
                    util = (l_np[sl] + q[:, None]) / safe_cap[sl]
                    util = np.where(va, util, -np.inf)
                    hops = va.sum(axis=1)
                    cost = util.max(axis=1) + 0.01 * hops
                    ok = hops > 0 if usable is None else usable[rows]
                    costs[:, k] = np.where(ok, cost, np.inf)
                choice = np.argmin(costs + jitter[rows], axis=1)
                placeable = np.isfinite(costs[np.arange(rows.size), choice])
                for k, (slots, valid, _) in enumerate(cands):
                    sel = (choice == k) & placeable
                    if not sel.any():
                        continue
                    sel_rows = rows[sel]
                    hop_sel = valid[sel_rows]
                    w = np.repeat(q[sel], hop_sel.sum(axis=1))
                    loads = _scatter_add(xp, loads, slots[sel_rows][hop_sel],
                                         w)
        return ArrayLinkLoads(self.index, loads)
