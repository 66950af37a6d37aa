"""The trace reduction (``bench/trace_reduce.py``) on made-up traces and
on a small trace recorded on a TPU v5e (``data/``, made by
``record_trace.py``), checked there against the profiler's own JSON
rendering of the same trace."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps():
    iv = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0]])
    np.testing.assert_array_equal(tr._union(iv), [[0, 4], [5, 6]])
    assert tr._union(np.zeros((0, 2))).shape == (0, 2)


def test_self_times_take_out_nested_ops():
    ops = [("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 6.0),
           ("c", 4.5, 5.0), ("a", 11.0, 12.0)]
    got = tr.self_times(ops, 0.0, 11.5)
    assert got == pytest.approx({"while": 6.0, "a": 2.5, "b": 1.5,
                                 "c": 0.5})
    assert sum(got.values()) == pytest.approx(10.5)


def test_short_name_drops_layouts():
    hlo = ("%fusion.100 = (f32[71982]{0:T(1024)S(1)}, f32[71982]{0:T(1024)"
           "S(1)}) fusion(f32[71982]{0:T(1024)S(1)} %p), kind=kCustom")
    assert tr.short_name(hlo) == "fusion.100 fusion (f32[71982], f32[71982])"
    assert tr.short_name("jit_run(123)") == "jit_run(123)"


def _fake(host, device):
    def ev(name, s, e):
        return NS(name=name, start_ns=s, duration_ns=e - s)
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python3", events=[
            ev(*h) for h in host])]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=[ev(*d) for d in device]),
            NS(name="Async XLA Ops", events=[ev("copy", 0, 1000)])]),
    ])


def test_reduce_on_a_made_up_trace():
    host = [("bench.sim", 100, 200), ("bench.incidence", 100, 130),
            ("bench.solve", 130, 190), ("bench.sim", 210, 300),
            ("bench.incidence", 210, 240), ("bench.solve", 240, 300)]
    device = [("while", 135, 185), ("fusion", 140, 160),
              ("while", 245, 295), ("early", 50, 120)]
    red = tr.reduce(_fake(host, device))
    ns = 1e-9
    assert red["n_sims"] == 2 and red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(200 * ns)
    assert red["busy_s"] == pytest.approx((20 + 50 + 50) * ns)
    ops = dict(red["device_ops"])
    assert ops["while"] == pytest.approx(80 * ns)
    assert ops["fusion"] == pytest.approx(20 * ns)
    assert ops["early"] == pytest.approx(20 * ns)
    gaps = dict(red["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(80 * ns)
    assert gaps["bench.incidence"] == pytest.approx((10 + 30) * ns)
    assert gaps["bench.solve"] == pytest.approx((5 + 5 + 5 + 5) * ns)
    assert gaps["bench.sim"] == pytest.approx(10 * ns)
    assert gaps["outside bench.sim"] == pytest.approx(10 * ns)


def test_reduce_finds_nothing_without_device_or_sims():
    assert tr.reduce(_fake([], [("while", 0, 10)])) is None
    assert tr.reduce(_fake([("bench.sim", 0, 10)], [])) is None


# -- the trace recorded on the chip ------------------------------------------

def _json_union(path: str):
    """Busy time and window from the profiler's JSON rendering, read
    without ``trace_reduce``: the union of the TPU's "XLA Ops" events
    between the first and last ``bench.sim``."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    sims = [(e["ts"], e["ts"] + e["dur"]) for e in events
            if e.get("ph") == "X" and e.get("name") == "bench.sim"]
    w0, w1 = min(s for s, _ in sims), max(e for _, e in sims)
    iv = sorted((max(e["ts"], w0), min(e["ts"] + e["dur"], w1))
                for e in events
                if e.get("ph") == "X"
                and procs.get(e["pid"]) == "/device:TPU:0"
                and threads.get((e["pid"], e["tid"])) == "XLA Ops"
                and e["ts"] + e["dur"] > w0 and e["ts"] < w1)
    busy, cur = 0.0, None
    for s, e in iv:
        if cur is None or s > cur[1]:
            if cur is not None:
                busy += cur[1] - cur[0]
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    busy += cur[1] - cur[0]
    return busy * 1e-6, (w1 - w0) * 1e-6, len(sims)


@pytest.fixture(scope="module")
def chip_trace():
    path = os.path.join(DATA, "small.xplane.pb.gz")
    return tr.reduce(tr.load(path))


def test_chip_trace_matches_the_json_rendering(chip_trace):
    busy, window, n = _json_union(os.path.join(DATA, "small.trace.json.gz"))
    assert chip_trace["n_sims"] == n == 3
    assert chip_trace["n_devices"] == 1
    # the JSON keeps microseconds to three decimals
    assert chip_trace["window_s"] == pytest.approx(window, abs=1e-8)
    assert chip_trace["busy_s"] == pytest.approx(busy, abs=1e-7)


def test_chip_trace_accounts_for_its_window(chip_trace):
    red = chip_trace
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-9)
    labels = {k for k, _ in red["idle_gaps"]}
    assert labels <= {"bench.incidence", "bench.solve", "bench.summary",
                      "bench.sim", "outside bench.sim"}
    # self times never add up to more than the device was busy
    assert sum(v for _, v in red["device_ops"]) <= red["busy_s"] * (1 + 1e-9)
    assert len(red["device_ops"]) <= tr.TOP
