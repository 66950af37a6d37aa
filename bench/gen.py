"""The benchmark's one traffic generator.

A traffic mix is a JSON file under ``bench/traffic/``; this module turns
it, a configuration and a seed into the k-th simulation's inputs.  The
demand patterns are copies of the program's switch-level generators
(``uniform``, ``hotspot``, ``neighbor_shift`` of one MPHX plane), kept
here so that no change to the program can move the yardstick.

Mix keys:

``pattern``        uniform | hotspot | neighbor_shift
``loads``          offered fractions of NIC bandwidth; simulation k runs
                   ``loads[k % len(loads)]``, so one cycle holds each once
``hot_fraction``   hotspot: share of each switch's load sent to the hot
                   switch, which is drawn from (seed, k)
``shuffle_rows``   permute the demand rows with (seed, k): same work,
                   another order
``sizes``          {"flow_time_s": t}: each flow transfers for t at its
                   offered rate; or {"uniform_bytes": [lo, hi, unit]}:
                   size U(lo, hi) * unit drawn from (seed, k)
``starts``         absent: every flow starts at t=0; or
                   {"uniform_s": [lo, hi]} drawn from (seed, k)
``pool``           distinct inputs, made in set-up: simulation k runs
                   input ``k % pool``, so the window times the system and
                   not the generator; a multiple of ``len(loads)``
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# simulations of a window compared with the reference, drawn from the
# seed; rounded up to whole load levels, so each level is compared
CHECK_SIMS = 4


@dataclass(frozen=True)
class Plane:
    """One MPHX plane as the configuration file states it."""

    n: int                   # planes
    p: int                   # NIC ports per switch per plane
    dims: tuple
    links_per_dim: tuple
    nic_bw_gbps: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Plane":
        t = cfg["topology"]
        dims = tuple(int(d) for d in t["dims"])
        links = tuple(int(x) for x in t.get("links_per_dim")
                      or [d - 1 for d in dims])
        return cls(int(t["n"]), int(t["p"]), dims, links,
                   float(t["nic_bw_gbps"]))

    @property
    def S(self) -> int:
        return int(np.prod(self.dims))

    @property
    def port_gbps(self) -> float:
        return self.nic_bw_gbps / self.n

    def coords(self, ids: np.ndarray) -> np.ndarray:
        """(M,) switch ids -> (M, D) row-major coordinates."""
        out = np.empty((ids.shape[0], len(self.dims)), dtype=np.int64)
        rem = ids.astype(np.int64)
        for i in range(len(self.dims) - 1, -1, -1):
            out[:, i] = rem % self.dims[i]
            rem = rem // self.dims[i]
        return out

    def ids(self, coords: np.ndarray) -> np.ndarray:
        out = np.zeros(coords.shape[0], dtype=np.int64)
        for i, d in enumerate(self.dims):
            out = out * d + coords[:, i]
        return out

    def slot_pairs(self) -> np.ndarray:
        """Directed switch pair ``u * S + v`` of each of the program's
        edge slots, by its documented layout: the link leaving ``u``
        along dimension ``i`` toward coordinate ``c`` has the slot
        ``S * sum(dims[:i]) + u * dims[i] + c``.  A self-link slot
        (``c`` = ``u``'s own coordinate) decodes to ``u * S + u``."""
        S = self.S
        u_all = np.arange(S, dtype=np.int64)
        cu = self.coords(u_all)
        out = []
        for i, d in enumerate(self.dims):
            u = np.repeat(u_all, d)
            cv = np.repeat(cu, d, axis=0)
            cv[:, i] = np.tile(np.arange(d, dtype=np.int64), S)
            out.append(u * S + self.ids(cv))
        return np.concatenate(out)


@dataclass(frozen=True)
class Inputs:
    """What one simulation is given."""

    load: float
    src: np.ndarray          # (F,) switch ids
    dst: np.ndarray          # (F,)
    gbps: np.ndarray         # (F,) offered Gbps, also the per-flow cap
    size_bytes: np.ndarray   # (F,)
    start_s: "np.ndarray | None"


def _per_switch_out(plane: Plane, offered_per_nic_gbps: float) -> float:
    return plane.p * offered_per_nic_gbps / plane.n


def uniform_demands(plane: Plane, offered: float):
    S = plane.S
    s, d = np.meshgrid(np.arange(S, dtype=np.int64),
                       np.arange(S, dtype=np.int64), indexing="ij")
    mask = s != d
    g = np.full(int(mask.sum()), _per_switch_out(plane, offered) / (S - 1))
    return s[mask], d[mask], g


def hotspot_demands(plane: Plane, offered: float, hot: int,
                    hot_fraction: float):
    us, ud, ug = uniform_demands(plane, offered * (1 - hot_fraction))
    src = np.arange(plane.S, dtype=np.int64)
    keep = src != hot
    g = np.full(src.shape, _per_switch_out(plane, offered) * hot_fraction)
    return (np.concatenate([us, src[keep]]),
            np.concatenate([ud, np.full(int(keep.sum()), hot,
                                        dtype=np.int64)]),
            np.concatenate([ug, g[keep]]))


def neighbor_shift_demands(plane: Plane, offered: float, dim: int = 0):
    src = np.arange(plane.S, dtype=np.int64)
    c = plane.coords(src)
    c[:, dim] = (c[:, dim] + 1) % plane.dims[dim]
    g = np.full(src.shape, _per_switch_out(plane, offered))
    return src, plane.ids(c), g


def _rng(seed: int, k: int, stream: int) -> np.random.Generator:
    # SeedSequence takes non-negative words; fold any integer seed in
    return np.random.default_rng(
        [int(seed) & (2**64 - 1), int(k), int(stream)])


class Traffic:
    """The k-th simulation's inputs of one mix on one plane."""

    def __init__(self, mix: dict, plane: Plane, seed: int):
        self.mix = mix
        self.plane = plane
        self.seed = int(seed)
        self.loads = [float(x) for x in mix["loads"]]
        self.period = len(self.loads)
        self.pool = int(mix.get("pool", self.period))
        if self.pool < 1 or self.pool % self.period:
            raise ValueError(f"pool {self.pool} is not a positive multiple "
                             f"of the {self.period} load levels")

    def inputs(self, k: int) -> Inputs:
        """Simulation k's inputs: those of pool entry ``k % pool``, drawn
        afresh from (seed, k % pool)."""
        mix, plane = self.mix, self.plane
        k = k % self.pool
        load = self.loads[k % self.period]
        offered = load * plane.nic_bw_gbps
        pattern = mix["pattern"]
        if pattern == "uniform":
            src, dst, gbps = uniform_demands(plane, offered)
        elif pattern == "hotspot":
            hot = int(_rng(self.seed, k, 1).integers(plane.S))
            src, dst, gbps = hotspot_demands(plane, offered, hot,
                                             float(mix["hot_fraction"]))
        elif pattern == "neighbor_shift":
            src, dst, gbps = neighbor_shift_demands(plane, offered,
                                                    int(mix.get("dim", 0)))
        else:
            raise ValueError(f"unknown traffic pattern {pattern!r}")
        if mix.get("shuffle_rows"):
            order = _rng(self.seed, k, 2).permutation(src.shape[0])
            src, dst, gbps = src[order], dst[order], gbps[order]
        rng = _rng(self.seed, k, 3)
        sizes = mix["sizes"]
        if "flow_time_s" in sizes:
            size = gbps * 1e9 / 8.0 * float(sizes["flow_time_s"])
        else:
            lo, hi, unit = sizes["uniform_bytes"]
            size = rng.uniform(lo, hi, src.shape[0]) * float(unit)
        start = None
        if "starts" in mix:
            lo, hi = mix["starts"]["uniform_s"]
            start = rng.uniform(lo, hi, src.shape[0])
        return Inputs(load, src, dst, gbps, size, start)

    def cycle_done(self, n_window_sims: int) -> bool:
        """True when the window holds whole cycles of the load levels."""
        return n_window_sims % self.period == 0


class CheckSample:
    """The window's simulations that the check compares, drawn from the
    seed as the window runs: one reservoir per load level, so that every
    level is compared and at most ``CHECK_SIMS`` results (rounded up to
    whole levels) are held at once, however long the window."""

    def __init__(self, traffic: Traffic):
        self.period = traffic.period
        self.size = max(1, -(-CHECK_SIMS // traffic.period))
        self.rng = _rng(traffic.seed, 0, 4)
        self.seen = [0] * self.period
        self.kept: "list[list]" = [[] for _ in range(self.period)]

    def offer(self, k: int, out) -> None:
        """Simulation ``k`` finished with ``out``."""
        level = k % self.period
        self.seen[level] += 1
        held = self.kept[level]
        if len(held) < self.size:
            held.append((k, out))
        else:
            j = int(self.rng.integers(self.seen[level]))
            if j < self.size:
                held[j] = (k, out)

    def items(self) -> "list[tuple]":
        """(k, out) of the sample, in window order."""
        return sorted((x for held in self.kept for x in held),
                      key=lambda x: x[0])
