"""Reduce a JAX profiler trace to device busy time, idle gaps and top ops.

The harness wraps each simulation in ``bench.sim`` and each layer call in
``bench.<layer>`` (``jax.profiler.TraceAnnotation``); the profiler puts
those host spans and the device's operations on one clock.  Busy time is
the union of the intervals of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane inside the traced window (the first
``bench.sim`` start to the last ``bench.sim`` end), averaged over the
devices.  An op's time is its self time: ops on that line nest (a
``while`` holds its body's ops), so each is charged only for the part of
its interval that no op inside it covers.  Idle time is split over what
the host was doing: each part of a gap goes to the layer span
(``bench.incidence``, ``bench.solve``, ``bench.summary``) that covers
it, to ``bench.sim`` where only a simulation's own span does, and to
``outside bench.sim`` between simulations.
"""

from __future__ import annotations

import glob
import gzip
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SIM_SPAN = "bench.sim"
SPAN_PREFIX = "bench."
TOP = 10
HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<type>.*?) "
                 r"(?P<op>[a-z][\w-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")


def load(path: str):
    """ProfileData from a trace directory, an ``.xplane.pb`` file, or a
    gzipped one."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (N, 2) [start, end) intervals into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def host_spans(pd) -> "list[tuple[str, float, float]]":
    """(name, start_ns, end_ns) of every ``bench.*`` span."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns)))
    return out


def device_ops(pd) -> "dict[str, list[tuple[str, float, float]]]":
    """Per TPU plane: (op name, start_ns, end_ns) on its ops line."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += [(ev.name, float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        out[plane.name] = ops
    return out


def short_name(hlo: str) -> str:
    """``fusion.100 fusion (f32[71982], f32[71982])`` from an op's HLO
    text: its name, opcode and result type without layouts."""
    m = HLO.match(hlo)
    if m is None:
        return hlo[:120]
    typ = m["type"]
    while LAYOUT.search(typ):
        typ = LAYOUT.sub("", typ)
    return f"{m['name']} {m['op']} {typ}"[:120]


def self_times(ops, w0: float, w1: float) -> "dict[str, float]":
    """Self time of each op name inside [w0, w1): an op's clipped
    interval less the clipped intervals of the ops directly inside it."""
    out: "dict[str, float]" = {}
    stack: list = []           # [end, name] of the enclosing ops
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        clip = max(0.0, min(e, w1) - max(s, w0))
        out[name] = out.get(name, 0.0) + clip
        if stack:
            parent = stack[-1][1]
            out[parent] = out.get(parent, 0.0) - clip
        stack.append([e, name])
    return out


def _label(spans, t: float) -> str:
    """Innermost ``bench.*`` span holding time ``t``."""
    best, width = "outside bench.sim", float("inf")
    for name, s, e in spans:
        if s <= t < e and e - s < width:
            best, width = name, e - s
    return best


def _idle_upto(gaps: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Idle time in [first gap start, t) for sorted disjoint ``gaps``."""
    before = np.concatenate([[0.0], np.cumsum(gaps[:, 1] - gaps[:, 0])])
    i = np.searchsorted(gaps[:, 0], t, side="right")
    j = np.maximum(i - 1, 0)
    part = np.clip(np.minimum(t, gaps[j, 1]) - gaps[j, 0], 0.0, None)
    return np.where(i > 0, before[j] + part, 0.0)


def _charge(idle: dict, gaps: np.ndarray, spans, w0: float,
            w1: float) -> None:
    """Add the idle ``gaps`` (N, 2) inside [w0, w1) to the innermost span
    that covers each part of them."""
    if gaps.size == 0:
        return
    cuts = np.unique(np.clip([w0, w1] + [x for _, s, e in spans
                                         for x in (s, e)], w0, w1))
    per_piece = np.diff(_idle_upto(gaps, cuts))
    for k in np.flatnonzero(per_piece > 0):
        lab = _label(spans, 0.5 * (cuts[k] + cuts[k + 1]))
        idle[lab] = idle.get(lab, 0.0) + float(per_piece[k])


def reduce(pd) -> "dict | None":
    """Busy and window seconds, simulations traced, top device ops and
    idle seconds by host span; None when the trace holds no simulation
    or no device operation."""
    spans = host_spans(pd)
    sims = [(s, e) for name, s, e in spans if name == SIM_SPAN]
    devices = {k: v for k, v in device_ops(pd).items() if v}
    if not sims or not devices:
        return None
    w0 = min(s for s, _ in sims)
    w1 = max(e for _, e in sims)
    busy, op_time, idle = [], {}, {}
    for ops in devices.values():
        iv = np.array([(max(s, w0), min(e, w1)) for _, s, e in ops
                       if e > w0 and s < w1]).reshape(-1, 2)
        merged = _union(iv)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()))
        for name, t in self_times(ops, w0, w1).items():
            op_time[name] = op_time.get(name, 0.0) + t
        gaps = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        _charge(idle, gaps[gaps[:, 1] > gaps[:, 0]], spans, w0, w1)
    n_dev = len(devices)
    ns = 1e-9
    return {
        "busy_s": sum(busy) / n_dev * ns,
        "window_s": (w1 - w0) * ns,
        "n_sims": len(sims),
        "n_devices": n_dev,
        "device_ops": [[short_name(k), v / n_dev * ns] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v / n_dev * ns] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]],
    }
