"""The scope and span attribution (``bench/trace_scopes.py``) on a
made-up trace and on a small trace recorded on a TPU v5e with the
program's spans and scopes (``data/small_scoped.*``, made by
``record_trace.py``), checked there against ``trace_reduce`` on the
profiler's ``.xplane.pb`` of the same trace."""

import os

import pytest

import trace_reduce as tr
import trace_scopes as ts

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e-6


def _events(host, device):
    """JSON trace events: ``host`` (name, start, end) and ``device``
    (tf_op, start, end), times in microseconds."""
    meta = [{"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "name": "process_name", "pid": 2,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
             "args": {"name": "python3"}},
            {"ph": "M", "name": "thread_name", "pid": 2, "tid": 1,
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "name": "thread_name", "pid": 2, "tid": 2,
             "args": {"name": "XLA Modules"}}]
    evs = [{"ph": "X", "pid": 1, "tid": 1, "name": n, "ts": s, "dur": e - s}
           for n, s, e in host]
    evs += [{"ph": "X", "pid": 2, "tid": 1, "name": f"op{i}", "ts": s,
             "dur": e - s, "args": {"tf_op": op} if op else {}}
            for i, (op, s, e) in enumerate(device)]
    evs.append({"ph": "X", "pid": 2, "tid": 2, "name": "jit_run", "ts": 0,
                "dur": 300})
    return meta + evs


BODY = "jit(run)/while/body/cond/branch_1_fun/while/body/"
HOST = [("bench.sim", 100, 200), ("bench.incidence", 100, 130),
        ("incidence.walk", 100, 110), ("incidence.coalesce", 110, 128),
        ("bench.solve", 130, 190), ("sim.simulate", 131, 189),
        ("sim.compress", 131, 140), ("sim.loop", 140, 185),
        ("other", 100, 200)]
DEVICE = [("", 141, 184), (BODY + "waterfill.edge_load/scatter-add:", 142,
                           150),
          (BODY + "waterfill.freeze/scatter-add:", 150, 160),
          ("jit(run)/while/body/cond/branch_1_fun/epoch.edge_bytes/add:",
           170, 175), ("", 135, 137), ("early:", 50, 101)]


def test_scope_of_takes_the_innermost_scope():
    assert ts.scope_of(BODY + "waterfill.step/jit(_where)/select_n:") == \
        "waterfill.step"
    assert ts.scope_of("a/waterfill.step/epoch.advance/x:") == \
        "epoch.advance"
    assert ts.scope_of("jit(run)/while:") == ts.NO_SCOPE
    assert ts.scope_of("") == ts.NO_SCOPE


def test_device_scopes_on_a_made_up_trace():
    got = ts.device_scopes(_events(HOST, DEVICE))
    assert got == pytest.approx({
        "waterfill.edge_load": 8 * US, "waterfill.freeze": 10 * US,
        "epoch.edge_bytes": 5 * US,
        # the while's self time, the copy, and the part of "early" inside
        # the window
        ts.NO_SCOPE: (20 + 2 + 1) * US})
    assert list(got) == sorted(got, key=lambda k: -got[k])


def test_idle_by_span_on_a_made_up_trace():
    got = ts.idle_by_span(_events(HOST, DEVICE))
    assert got == pytest.approx({
        "incidence.walk": 9 * US, "incidence.coalesce": 18 * US,
        "bench.incidence": 2 * US, "bench.solve": 2 * US,
        "sim.compress": 7 * US, "sim.loop": 2 * US,
        "sim.simulate": 4 * US, "bench.sim": 10 * US})
    # the same gaps as trace_reduce, which charges bench.* spans only
    assert sum(got.values()) == pytest.approx((100 - 1 - 2 - 43) * US)


def test_reductions_find_nothing_without_sims_or_device():
    assert ts.device_scopes(_events([], DEVICE)) is None
    assert ts.idle_by_span(_events(HOST, [])) is None


# -- the trace recorded on the chip ------------------------------------------

@pytest.fixture(scope="module")
def chip():
    events = ts.load(os.path.join(DATA, "small_scoped.trace.json.gz"))
    pd = tr.load(os.path.join(DATA, "small_scoped.xplane.pb.gz"))
    return events, pd, tr.reduce(pd)


def test_chip_trace_has_the_program_spans(chip):
    events, _, red = chip
    spans = ts.host_spans(events)
    names = {n for n, _, _ in spans}
    assert {"sim.simulate", "sim.compress", "sim.transfer", "sim.loop",
            "sim.readback", "sim.finalize", "incidence.walk",
            "incidence.coalesce"} <= names
    runs = [e["args"]["run"] for e in events
            if e.get("ph") == "X" and e["name"] == "sim.simulate"]
    # warm-up simulations ran before the trace: six calls, three traced
    assert len(runs) == len(set(runs)) == red["n_sims"] == 3


def test_chip_device_scopes_cover_the_device_time(chip):
    events, pd, red = chip
    got = ts.device_scopes(events)
    assert {"waterfill.edge_load", "waterfill.step", "waterfill.freeze",
            "epoch.admit", "epoch.advance", "epoch.edge_bytes"} <= set(got)
    # the same self times as trace_reduce reads from the .xplane.pb
    w0 = min(s for n, s, _ in tr.host_spans(pd) if n == tr.SIM_SPAN)
    w1 = max(e for n, _, e in tr.host_spans(pd) if n == tr.SIM_SPAN)
    (ops,) = tr.device_ops(pd).values()
    own = sum(tr.self_times(ops, w0, w1).values()) * 1e-9
    assert sum(got.values()) == pytest.approx(own, abs=1e-7)
    # the JSON keeps nanoseconds, rounded, op by op
    assert sum(got.values()) <= red["busy_s"] + 1e-7


def test_chip_idle_by_span_splits_the_idle_gaps(chip):
    events, _, red = chip
    got = ts.idle_by_span(events)
    gaps = dict(red["idle_gaps"])
    assert sum(got.values()) == pytest.approx(sum(gaps.values()), abs=1e-7)
    assert set(got) <= {"outside bench.sim", "bench.sim", "bench.incidence",
                        "bench.solve", "bench.summary", "sim.simulate",
                        "sim.compress", "sim.transfer", "sim.loop",
                        "sim.readback", "sim.finalize", "incidence.walk",
                        "incidence.coalesce"}
    # a program span lies inside the harness's span around its call
    solve = sum(v for k, v in got.items()
                if k == "bench.solve" or k.startswith("sim."))
    inc = sum(v for k, v in got.items() if k.startswith(
        ("bench.incidence", "incidence.")))
    assert solve == pytest.approx(gaps["bench.solve"], abs=1e-7)
    assert inc == pytest.approx(gaps["bench.incidence"], abs=1e-7)
    assert got.get("incidence.coalesce", 0) + got.get("incidence.walk", 0) \
        > 0.5 * gaps["bench.incidence"]
