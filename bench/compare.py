"""The comparison that decides ``correct``.

Each simulation drawn for the check is compared with the reference on
four numbers; a run is correct when every number is within its limit
on every simulation compared.  Edges are matched by the directed switch
pair they join, ``u * S + v``: the reference numbers its edges so, and
the program's edge slots are decoded by their documented layout
(``gen.Plane.slot_pairs``), so a load on the wrong edge, even on the
reverse of the right one, is a gap.  Flows are matched row by row, since
both sides index them by demand row, and the incidence entry by entry,
(flow, pair).  Every number has to separate a sound run from the control
(the reference in float32): so the incidence is also weighed by flow
sizes, which float32 cannot hold, where offered rates alone can be exact
in it (one-hop shift traffic), and the epoch count, which float32 need
not change, is held exactly inside the finish-time number.  PERF.md
gives the readings each limit was set from.
"""

from __future__ import annotations

import numpy as np

import reference as ref

# name -> (limit, what it measures).  Every limit is the configurations'
# stated agreement with float64, 1e-9 (the program's own, see
# ``repro.sim.fairshare``): sound runs read at most 1.2e-13 on the chip
# and the float32 control at least 3.0e-8 (PERF.md).
LIMITS = {
    "incidence_gap": (1e-9, "per-edge offered Gbps and offered bytes, "
                             "each widest gap over its largest, and each "
                             "(flow, edge) entry's share, widest gap; "
                             "infinite when the entries differ"),
    "finish_gap": (1e-9, "per-flow finish time, widest relative gap; "
                         "infinite when the epoch counts differ"),
    "edge_bytes_gap": (1e-9, "per-edge bytes carried, widest gap over "
                             "the largest"),
    "summary_gap": (1e-9, "FCT p50/p95/p99, slowdown mean/p99 and "
                          "delivered fraction, widest relative gap"),
}


def _keyed_gap(got_keys, got, want_keys, want) -> float:
    """Widest gap between two per-edge vectors matched by edge key (an
    edge on one side only meets 0 on the other), over the largest entry
    of ``want``."""
    keys = np.union1d(got_keys, want_keys)
    a = np.bincount(np.searchsorted(keys, got_keys),
                    weights=np.asarray(got, dtype=np.float64),
                    minlength=keys.size)
    b = np.bincount(np.searchsorted(keys, want_keys),
                    weights=np.asarray(want, dtype=np.float64),
                    minlength=keys.size)
    if not keys.size:
        return 0.0
    top = float(np.abs(b).max())
    gap = float(np.abs(a - b).max())
    return gap / top if top > 0 else gap


def _entry_gap(got: tuple, want: tuple) -> float:
    """Widest gap between the shares of two incidences given as (flow,
    edge key, share) triples; infinite unless both hold the same
    (flow, edge) entries."""
    (gf, ge, gs), (wf, we, ws) = got, want
    if gf.shape != wf.shape:
        return float("inf")
    og, ow = np.lexsort((ge, gf)), np.lexsort((we, wf))
    if not (np.array_equal(gf[og], wf[ow]) and
            np.array_equal(ge[og], we[ow])):
        return float("inf")
    if not gf.size:
        return 0.0
    return float(np.abs(np.asarray(gs, dtype=np.float64)[og]
                        - np.asarray(ws, dtype=np.float64)[ow]).max())


def _rel_gap(got, want) -> float:
    """Widest relative gap between two per-flow vectors; a flow finite on
    one side and not on the other is an infinite gap."""
    a = np.asarray(got, dtype=np.float64)
    b = np.asarray(want, dtype=np.float64)
    fa, fb = np.isfinite(a), np.isfinite(b)
    if np.any(fa != fb) or a.shape != b.shape:
        return float("inf")
    if not fa.any():
        return 0.0
    d = np.abs(a[fa] - b[fa]) / np.maximum(np.abs(b[fa]), 1e-300)
    return float(d.max())


def _summary_gap(got: dict, want: dict) -> float:
    gap = 0.0
    for key, w in want.items():
        g = got.get(key)
        if w is None or g is None:
            if (w is None) != (g is None):
                return float("inf")
            continue
        gap = max(gap, abs(g - w) / max(abs(w), 1e-300))
    return gap


def view(edge_keys, loads, offered_bytes, edge_bytes, entries, finish_s,
         n_epochs, summary) -> dict:
    """One side of the comparison: per edge, keyed by ``edge_keys``
    (directed pair ``u * S + v``), ``loads`` (offered Gbps),
    ``offered_bytes`` and ``edge_bytes`` (carried); the incidence
    ``entries`` as (flow, edge key, share); per flow ``finish_s``; the
    epoch count and the FCT summary."""
    return {"edge_keys": edge_keys, "loads": loads,
            "offered_bytes": offered_bytes, "edge_bytes": edge_bytes,
            "entries": entries, "finish_s": finish_s,
            "n_epochs": int(n_epochs), "summary": summary}


def numbers(observed: dict, expected: dict) -> dict:
    """The four numbers of one simulation, from two :func:`view` s."""
    finish = _rel_gap(observed["finish_s"], expected["finish_s"])
    if observed["n_epochs"] != expected["n_epochs"]:
        finish = float("inf")

    def per_edge(key):
        return _keyed_gap(observed["edge_keys"], observed[key],
                          expected["edge_keys"], expected[key])

    return {
        "incidence_gap": max(
            per_edge("loads"), per_edge("offered_bytes"),
            _entry_gap(observed["entries"], expected["entries"])),
        "finish_gap": finish,
        "edge_bytes_gap": per_edge("edge_bytes"),
        "summary_gap": _summary_gap(observed["summary"],
                                    expected["summary"]),
    }


def reference_view(plane, config: dict, inp, dtype=np.float64) -> dict:
    """Run the reference on one simulation's inputs, in ``dtype``, with
    the configuration's routing mode and net latencies."""
    inc = ref.incidence(plane, inp.src, inp.dst, dtype, config["routing"])
    res = ref.simulate(inc, inp.size_bytes, inp.gbps, inp.start_s,
                       config["net"])
    gbps = inp.gbps.astype(dtype)
    size = inp.size_bytes.astype(dtype)
    bneck = ref.bottleneck_gbps(inc.flow, inc.edge, inc.frac, inc.capacity,
                                inc.n_flows)
    return view(inc.pair, ref.edge_loads(inc, gbps),
                ref.edge_loads(inc, size), res.edge_bytes,
                (inc.flow, inc.pair[inc.edge], inc.frac),
                res.finish_s, res.n_epochs,
                ref.fct_summary(res.fct_s, res.finish_s, size, gbps,
                                res.latency_s, bneck, res.makespan_s,
                                float(gbps.sum())))


def worst(per_sim: "list[dict]") -> dict:
    """Each number's worst reading over the simulations compared."""
    out = {}
    for name in LIMITS:
        vals = [n[name] for n in per_sim]
        out[name] = max(vals) if vals else float("nan")
    return out


def verdict(worst_numbers: dict) -> bool:
    return all(worst_numbers[k] <= lim for k, (lim, _) in LIMITS.items())
