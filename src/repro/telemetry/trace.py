"""Fabric flight recorder: spans, counters, and Perfetto export.

A :class:`TraceRecorder` journals what the simulated fabric *did over
time* — the flow-level visibility FatPaths argues for and the endpoint
scalars (FCT percentiles, step time) cannot give:

* per-epoch event-loop state (epoch clock, active-flow counts, per-link
  utilization series) journaled out of both event-loop backends — the
  numpy reference loop and the jitted ``lax.while_loop`` journal the
  SAME rows (``tests/test_telemetry.py`` pins count + ordering);
* per-flow start/finish spans (budgeted — see below);
* co-sim collective phases as named spans on per-plane tracks
  (:mod:`repro.cosim.stepsim`), so a training step renders as a timeline;
* failure-recovery windows (detect / re-route / recover) as spans
  (:mod:`repro.sim.failures`).

Everything exports as Chrome/Perfetto ``trace_event`` JSON
(:meth:`TraceRecorder.export`): open the file at https://ui.perfetto.dev
or ``chrome://tracing``.  Simulated-fabric time maps to trace time
(1 simulated second = 1e6 trace microseconds).

Scale is bounded by policy, never silently: a 65K-NIC run journals only
the :class:`LinkSeriesPolicy` link subset (top-K by expected load plus a
seeded reservoir of the remaining used links), at most
``max_epochs`` journal rows, and at most ``max_flow_events`` flow spans
— everything dropped is counted in the recorder's metrics
(``trace.dropped_epochs`` / ``trace.dropped_flow_events``).

Enable with :func:`recording` — it also installs the recorder's
:class:`~repro.telemetry.metrics.MetricsRegistry` as the ambient sink::

    with recording() as rec:
        simulate_step(topo, job)
    rec.export("step_trace.json")
"""

from __future__ import annotations

import json
from contextlib import contextmanager
from dataclasses import dataclass

import numpy as np

from .metrics import MetricsRegistry, collecting

__all__ = ["LinkSeriesPolicy", "TraceRecorder", "get_recorder",
           "recording", "validate_trace"]

_US = 1e6   # simulated seconds -> trace_event microseconds


@dataclass(frozen=True)
class LinkSeriesPolicy:
    """Which links get a per-epoch utilization series, and how long.

    ``top_k`` links by expected load (incidence at demand-cap rates,
    deterministic load-then-id ordering) plus a ``reservoir`` sampled
    without replacement (seeded) from the remaining *used* links — so a
    65K-NIC fabric journals a fixed-width series instead of ~72K columns.
    ``max_epochs`` caps journal rows per simulation; overflow is counted
    (``trace.dropped_epochs``), never silently truncated.
    """

    top_k: int = 16
    reservoir: int = 8
    seed: int = 0
    max_epochs: int = 4096

    def select(self, inc, rate_caps_gbps) -> np.ndarray:
        """(K',) sorted global edge ids to journal for one incidence
        tensor (K' <= top_k + reservoir; only used edges qualify)."""
        caps = np.broadcast_to(np.asarray(rate_caps_gbps, dtype=np.float64),
                               (inc.n_flows,))
        load = inc.loads(caps)
        used = np.flatnonzero(load > 0)
        if used.size == 0:
            return used
        order = used[np.lexsort((used, -load[used]))]
        top = order[:self.top_k]
        rest = np.setdiff1d(used, top, assume_unique=False)
        if rest.size and self.reservoir > 0:
            rng = np.random.default_rng(self.seed)
            res = rng.choice(rest, size=min(self.reservoir, rest.size),
                             replace=False)
            top = np.concatenate([top, res])
        return np.sort(top)


class TraceRecorder:
    """Collects trace events + metrics; exports Perfetto JSON.

    Tracks are named ``(process, thread)`` pairs mapped to stable
    ``(pid, tid)`` ids with ``process_name`` / ``thread_name`` metadata,
    so Perfetto renders e.g. one process per co-simulated topology with
    one thread per plane.
    """

    def __init__(self, link_policy: "LinkSeriesPolicy | None" =
                 LinkSeriesPolicy(),
                 max_flow_events: int = 256):
        self.metrics = MetricsRegistry()
        self.link_policy = link_policy
        self.max_flow_events = max_flow_events
        self.events: "list[dict]" = []
        self.journals: "list[dict]" = []
        self.notes: "list[dict]" = []
        self._procs: dict = {}
        self._threads: dict = {}
        self._meta: "list[dict]" = []
        self._flow_budget = max_flow_events

    # ------------------------------------------------------------ tracks ----

    def track(self, process: str = "sim", thread: str = "main"
              ) -> "tuple[int, int]":
        """(pid, tid) of a named track, registering display metadata on
        first use."""
        pid = self._procs.get(process)
        if pid is None:
            pid = self._procs[process] = len(self._procs) + 1
            self._meta.append({"name": "process_name", "ph": "M",
                               "pid": pid, "tid": 0,
                               "args": {"name": process}})
        tid = self._threads.get((pid, thread))
        if tid is None:
            tid = self._threads[(pid, thread)] = \
                len([1 for (p, _) in self._threads if p == pid]) + 1
            self._meta.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": tid,
                               "args": {"name": thread}})
        return pid, tid

    # ------------------------------------------------------------ events ----

    @property
    def n_events(self) -> int:
        return len(self.events)

    def span(self, name: str, start_s: float, dur_s: float,
             process: str = "sim", thread: str = "main",
             cat: str = "sim", args: "dict | None" = None) -> None:
        """A complete ("X") span on the simulated-time clock."""
        pid, tid = self.track(process, thread)
        ev = {"name": name, "ph": "X", "cat": cat,
              "ts": float(start_s) * _US, "dur": float(dur_s) * _US,
              "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, ts_s: float, process: str = "sim",
                thread: str = "main", cat: str = "sim",
                args: "dict | None" = None) -> None:
        pid, tid = self.track(process, thread)
        ev = {"name": name, "ph": "i", "cat": cat, "s": "t",
              "ts": float(ts_s) * _US, "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name: str, ts_s: float, values: dict,
                process: str = "sim", cat: str = "sim") -> None:
        """A counter ("C") sample; ``values`` maps series name -> value."""
        pid, _ = self.track(process, "main")
        self.events.append({"name": name, "ph": "C", "cat": cat,
                            "ts": float(ts_s) * _US, "pid": pid,
                            "args": {k: float(v)
                                     for k, v in values.items()}})

    def note_skip(self, name: str, reason: str) -> None:
        """Explicit record that a suite/bench path produced no trace."""
        self.notes.append({"name": name, "traced": False,
                           "reason": reason})

    # ------------------------------------------------- sim-layer hooks ----

    def record_flow_sim(self, res, label: str = "flows") -> None:
        """Per-flow transfer spans from a
        :class:`~repro.sim.events.FlowSimResult` (budgeted to
        ``max_flow_events`` total, longest transfers first)."""
        done = np.flatnonzero(np.isfinite(res.finish_s))
        take = done
        if take.size > self._flow_budget:
            transfer = res.finish_s[done] - res.start_s[done]
            keep = np.argsort(-transfer, kind="stable")[:self._flow_budget]
            take = np.sort(done[keep])
            self.metrics.inc("trace.dropped_flow_events",
                             int(done.size - take.size))
        self._flow_budget -= int(take.size)
        tags = getattr(res, "tags", None)
        for f in take.tolist():
            args = {"bytes": float(res.size_bytes[f])}
            if tags is not None and tags[f] is not None:
                args["tag"] = str(tags[f])
            self.span(f"flow[{f}]", float(res.start_s[f]),
                      float(res.finish_s[f] - res.start_s[f]),
                      process="sim", thread=label, cat="flow",
                      args=args)
        stalled = int(res.stalled.sum())
        if stalled:
            self.metrics.inc("sim.stalled_flows", stalled)

    def record_epoch_journal(self, t_s, dt_s, active, edge_ids, util,
                             label: str = "epochs",
                             dropped: int = 0) -> None:
        """Per-epoch journal rows (from either event-loop backend):
        epoch clock, active-flow count, per-selected-link utilization.
        Stored raw in :attr:`journals` and emitted as counter samples."""
        t_s = np.asarray(t_s, dtype=np.float64)
        self.journals.append({
            "label": label,
            "t_s": t_s.tolist(),
            "dt_s": np.asarray(dt_s, dtype=np.float64).tolist(),
            "active_flows": np.asarray(active).astype(int).tolist(),
            "edge_ids": np.asarray(edge_ids).astype(int).tolist(),
            "util": np.asarray(util, dtype=np.float64).tolist(),
            "dropped_epochs": int(dropped),
        })
        if dropped:
            self.metrics.inc("trace.dropped_epochs", int(dropped))
        ids = [f"e{int(e)}" for e in np.asarray(edge_ids).tolist()]
        for i in range(t_s.shape[0]):
            self.counter("active_flows", float(t_s[i]),
                         {label: int(np.asarray(active)[i])})
            if ids:
                self.counter("link_util", float(t_s[i]),
                             dict(zip(ids, np.asarray(util)[i])))

    # ------------------------------------------------------------ export ----

    def to_json(self) -> dict:
        """The Perfetto ``trace_event`` payload (JSON object format)."""
        return {
            "traceEvents": self._meta + self.events,
            "displayTimeUnit": "ms",
            "otherData": {
                "generated_by": "repro.telemetry",
                "clock": "1 simulated second = 1e6 trace us",
                "skipped": self.notes,
                "metrics": self.metrics.snapshot(),
            },
        }

    def export(self, path: "str | None" = None) -> dict:
        payload = self.to_json()
        if path is not None:
            with open(path, "w") as f:
                json.dump(payload, f, indent=1)
                f.write("\n")
        return payload


def validate_trace(payload: dict) -> "list[str]":
    """Schema-check a ``trace_event`` payload; returns problems (empty =
    valid).  Covers the event phases this module emits (M/X/i/C)."""
    problems = []
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    required = {"M": ("name", "ph", "pid", "args"),
                "X": ("name", "ph", "ts", "dur", "pid", "tid"),
                "i": ("name", "ph", "ts", "pid", "tid", "s"),
                "C": ("name", "ph", "ts", "pid", "args")}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in required:
            problems.append(f"event {i}: unknown ph {ph!r}")
            continue
        for key in required[ph]:
            if key not in ev:
                problems.append(f"event {i} (ph={ph}): missing {key!r}")
        for key in ("ts", "dur"):
            if key in ev and (not isinstance(ev[key], (int, float))
                              or ev[key] < 0):
                problems.append(f"event {i}: bad {key}={ev[key]!r}")
    return problems


_recorder: "TraceRecorder | None" = None


def get_recorder() -> "TraceRecorder | None":
    """The ambient recorder (None unless a :func:`recording` scope is
    active) — the sim/cosim layers consult this, so tracing needs no
    signature changes anywhere."""
    return _recorder


@contextmanager
def recording(recorder: "TraceRecorder | None" = None):
    """Install ``recorder`` (default: a fresh one) as the ambient flight
    recorder AND its metrics registry as the ambient metrics sink."""
    global _recorder
    rec = recorder if recorder is not None else TraceRecorder()
    prev = _recorder
    _recorder = rec
    try:
        with collecting(rec.metrics):
            yield rec
    finally:
        _recorder = prev
