"""Attribute a JAX profiler trace to the program's own scopes and spans.

    python3 bench/trace_scopes.py <trace dir | .trace.json.gz>

Two reductions beside ``trace_reduce.reduce``, over the same window (the
first ``bench.sim`` start to the last ``bench.sim`` end) and averaged
over the TPUs in the same way:

* ``device_scopes``: the device's self time per named scope.  The
  program marks its jitted code with ``jax.named_scope`` (``waterfill.*``
  inside a water-filling round, ``epoch.*`` in an event-loop epoch); an
  op's scope is the innermost component of its ``tf_op`` path that
  starts with one of ``SCOPE_PREFIXES``, and ops with none go to
  ``NO_SCOPE``.
* ``idle_by_span``: the device's idle time, each part charged to the
  innermost host span that covers it, among the harness's ``bench.*``
  and the program's ``sim.*`` and ``incidence.*`` spans
  (``repro.telemetry.span``).

Both read the profiler's JSON rendering of the trace, which it writes
beside the ``.xplane.pb``: ``jax.profiler.ProfileData`` gives an event's
own stats but not those of its metadata, where ``tf_op`` lives.  The
JSON keeps times in microseconds to three decimals, so nanoseconds.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys

import numpy as np

import trace_reduce as tr

SCOPE_PREFIXES = ("waterfill.", "epoch.")
SPAN_PREFIXES = ("bench.", "sim.", "incidence.")
NO_SCOPE = "(no scope)"


def load(path: str) -> "list[dict]":
    """The ``traceEvents`` of a trace directory's ``.trace.json.gz``, or
    of that file itself."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .trace.json.gz under {path}")
        path = found[-1]
    with gzip.open(path, "rt") as f:
        return json.load(f)["traceEvents"]


def _tracks(events):
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    return procs, threads


def _interval(e) -> "tuple[float, float]":
    """An event's [start, end) in nanoseconds."""
    return e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3


def host_spans(events, prefixes=SPAN_PREFIXES
               ) -> "list[tuple[str, float, float]]":
    """(name, start_ns, end_ns) of every host span whose name starts with
    one of ``prefixes``."""
    procs, _ = _tracks(events)
    return [(e["name"], *_interval(e)) for e in events
            if e.get("ph") == "X" and e["name"].startswith(prefixes)
            and procs.get(e["pid"], "").startswith("/host:")]


def device_ops(events) -> "dict[str, list[tuple[str, float, float]]]":
    """Per TPU: (``tf_op`` path, start_ns, end_ns) of each op on its ops
    line; an op with no path (``while``, ``conditional``, copies) has
    ``""``."""
    procs, threads = _tracks(events)
    out: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        plane = procs.get(e["pid"], "")
        if (tr.DEVICE_PLANE.match(plane)
                and threads.get((e["pid"], e["tid"])) == tr.OPS_LINE):
            out.setdefault(plane, []).append(
                (e.get("args", {}).get("tf_op", ""), *_interval(e)))
    return out


def scope_of(tf_op: str) -> str:
    """The innermost component of ``tf_op`` that starts with one of
    ``SCOPE_PREFIXES``, or ``NO_SCOPE``."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part.startswith(SCOPE_PREFIXES):
            return part
    return NO_SCOPE


def _window(events):
    sims = [(s, e) for name, s, e in host_spans(events, (tr.SIM_SPAN,))
            if name == tr.SIM_SPAN]
    if not sims:
        return None
    return min(s for s, _ in sims), max(e for _, e in sims)


def device_scopes(events) -> "dict | None":
    """Seconds of device self time per scope in the window, averaged over
    the TPUs, largest first; None when the trace holds no simulation or
    no device operation."""
    window, devices = _window(events), device_ops(events)
    if window is None or not devices:
        return None
    out: "dict[str, float]" = {}
    for ops in devices.values():
        # self times keyed by op index: names repeat across programs
        own = tr.self_times([(i, s, e) for i, (_, s, e) in enumerate(ops)],
                            *window)
        for i, t in own.items():
            key = scope_of(ops[i][0])
            out[key] = out.get(key, 0.0) + t
    n = len(devices)
    return dict(sorted(((k, v / n * 1e-9) for k, v in out.items()),
                       key=lambda kv: -kv[1]))


def idle_by_span(events) -> "dict | None":
    """Seconds of device idle time in the window charged to the innermost
    span (names starting with one of ``SPAN_PREFIXES``) covering each
    part of it, averaged over the TPUs, largest first; None as for
    :func:`device_scopes`."""
    window, devices = _window(events), device_ops(events)
    if window is None or not devices:
        return None
    w0, w1 = window
    spans = host_spans(events)
    idle: "dict[str, float]" = {}
    for ops in devices.values():
        iv = np.array([(max(s, w0), min(e, w1)) for _, s, e in ops
                       if e > w0 and s < w1]).reshape(-1, 2)
        merged = tr._union(iv)
        gaps = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        tr._charge(idle, gaps[gaps[:, 1] > gaps[:, 0]], spans, w0, w1)
    n = len(devices)
    return dict(sorted(((k, v / n * 1e-9) for k, v in idle.items()),
                       key=lambda kv: -kv[1]))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    events = load(argv[0])
    print(json.dumps({"device_scopes": device_scopes(events),
                      "idle_by_span": idle_by_span(events)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
