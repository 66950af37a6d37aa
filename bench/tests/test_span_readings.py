"""The replay of the program's span, scope and round-counter readings
(``bench/span_readings.py``): its arithmetic on made-up numbers, and a
traced run of each cell on the CPU, where the host spans and the
counter are read and the device's scopes find no TPU plane."""

import pytest

import run
import span_readings as sr

CELLS = ["mphx4p-hotspot", "mphx4p-uniform", "mphx4p-churn"]


def test_readings_and_coverage_on_made_up_numbers():
    timers = {name: {"count": 4, "total_s": 0.4}
              for name in sr.SPANS.values()}
    snap = {"timers": timers,
            "counters": {"sim.runs": 4, "sim.epochs": 12,
                         "waterfill.rounds": 24}}
    scopes = {"waterfill.edge_load": 1.0, "waterfill.freeze": 0.5,
              "epoch.edge_bytes": 0.25, "(no scope)": 0.1}
    read = sr.readings(snap, 4, scopes, 2)
    assert read == pytest.approx({
        **{key: 0.1 for key in sr.SPANS}, "waterfill_rounds": 6.0,
        "epochs": 3.0, "waterfill_device_s": 0.75,
        "epoch_device_s": 0.125})
    metrics = {"solve_s": {"value": 1.0}, "incidence_s": {"value": 0.25},
               "device_busy_s": {"value": 0.875}}
    gaps = [["bench.solve", 0.3], ["bench.incidence", 0.1],
            ["outside bench.sim", 1.0]]
    idle = {"sim.compress": 0.2, "incidence.coalesce": 0.1,
            "bench.solve": 0.1, "outside bench.sim": 1.0}
    assert sr.coverage(read, metrics, gaps, idle) == pytest.approx({
        "solve_s": 0.5, "incidence_s": 0.8, "device_busy_s": 1.0,
        "idle_in_program_spans": 0.75})


def test_readings_find_nothing_without_telemetry_or_trace():
    snap = {"timers": {}, "counters": {}}
    read = sr.readings(snap, 0, None, 0)
    assert set(read) == set(sr.SPANS) | set(sr.SCOPES) | {
        "waterfill_rounds", "epochs"}
    assert all(v is None for v in read.values())
    assert all(v is None for v in sr.coverage(read, {}, None, None).values())


@pytest.mark.parametrize("workload", CELLS)
def test_cpu_run_reads_the_program_spans(x64, spec, small_config, workload):
    import trace_reduce

    import repro.telemetry as telemetry

    patched = (telemetry.collecting, trace_reduce.load, trace_reduce.reduce)
    resolved = run.resolve(spec, workload)
    resolved["config"] = small_config
    out = sr.run_with_readings(resolved, 2**31 + 4243, 0.2,
                               require_tpu=False)
    # the run's patches are undone
    assert (telemetry.collecting, trace_reduce.load,
            trace_reduce.reduce) == patched
    assert out["correct"] is True
    read = out["readings"]
    for key in sr.SPANS:
        assert read[key] > 0, key
    assert read["waterfill_rounds"] >= read["epochs"] >= 1
    # no TPU plane on the CPU
    assert read["waterfill_device_s"] is read["epoch_device_s"] is None
    cov = out["coverage"]
    for key in ("solve_s", "incidence_s"):
        assert 0 < cov[key] <= 1, key
    assert cov["device_busy_s"] is None
