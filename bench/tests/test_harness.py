"""The harness's machinery on the CPU: every cell of ``BENCHMARK.json``
resolved from its files and driven through ``run.run_cell`` at
``mphx-2p-8x8`` (past the look for a chip), the DAL test configuration
through the same path, the result line's shape, and the refusals of the
command itself."""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

import run

CELLS = ["mphx4p-hotspot", "mphx4p-uniform", "mphx4p-churn"]
NICS = {"mphx-4p-86x9": 66564, "mphx-8p-256": 65536}   # paper Table 2


def test_every_entry_finds_its_files(spec):
    """Cells, configurations, mixes and metrics are found by name."""
    assert [w["name"] for w in spec["workloads"]] == CELLS
    for w in spec["workloads"]:
        res = run.resolve(spec, w["name"])
        assert res["config"]["name"] == w["config"]
        assert res["config"]["precision"] == "float64"
        assert "pattern" in res["mix"]
        for m in res["end_to_end"] + res["per_layer"]:
            assert callable(run.reader(m["name"]))
    for c in spec["configs"]:
        cfg = run.load_json(os.path.join(run.ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"] == []


def test_configurations_are_the_presets(spec):
    """Every configuration file, those that no cell uses yet too."""
    from repro.experiments.sweep import SWEEP_TOPOLOGIES

    files = sorted(glob.glob(os.path.join(run.BENCH, "configs", "*.json")))
    assert {os.path.join(run.ROOT, c["file"]) for c in spec["configs"]} \
        <= set(files)
    for path in files:
        cfg = run.load_json(path)
        assert os.path.basename(path) == cfg["name"] + ".json"
        topo = SWEEP_TOPOLOGIES[cfg["preset"]]
        plane = run.Plane.from_config(cfg)
        assert plane.S == topo.switches_per_plane
        assert plane.S * plane.p == topo.n_nics == NICS[cfg["name"]]
        assert run.routing_of(cfg) == cfg["routing"]
        assert cfg["reduced"] == []
        run.build_router(cfg)


@pytest.mark.parametrize("mode", ["adaptive", "ecmp", None])
def test_routing_without_static_incidence_is_refused(tmp_path, spec,
                                                      small_config, mode):
    """A configuration routed in a mode that the reference cannot check,
    or in none (``None``: the key left out), is refused while the cell
    is resolved, before any run."""
    cfg = {k: v for k, v in small_config.items() if k != "routing"}
    if mode is not None:
        cfg["routing"] = mode
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    bad = dict(spec, configs=[dict(c, file=str(path))
                              for c in spec["configs"]])
    with pytest.raises(SystemExit,
                       match=f"routes {mode!r}.*adaptive re-routes"):
        run.resolve(bad, CELLS[0])


def test_unknown_workload_is_refused(spec):
    with pytest.raises(SystemExit, match="unknown workload"):
        run.resolve(spec, "no-such-cell")


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_through_the_harness(x64, capsys, spec, small_config,
                                       workload, trace):
    resolved = run.resolve(spec, workload)
    resolved["config"] = small_config
    res = run.run_cell(resolved, 2**31 + 4242, 0.2, trace=trace,
                       require_tpu=False)
    assert res["correct"] is True, res["checks"]
    assert "routing: minimal (configuration mphx-2p-8x8)" in \
        capsys.readouterr().err
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name
    want = resolved["per_layer" if trace else "end_to_end"]
    got = res["metrics"]
    if trace:
        # the CPU has no TPU plane: the trace readers find nothing and
        # leave their metrics out; the host spans are there
        assert set(got) == {"incidence_s", "solve_s"}
        assert "busy_s" not in res["device"]
    else:
        assert set(got) == {m["name"] for m in want}
        assert got["sim_s"]["value"] > 0 and got["setup_s"]["value"] > 0
    for m in want:
        if m["name"] in got:
            assert got[m["name"]]["unit"] == m["unit"]
    json.dumps(res)


@pytest.mark.parametrize("workload", CELLS)
def test_dal_configuration_runs_through_the_harness(x64, capsys, spec,
                                                    dal_config, workload):
    """The program routes the configuration's ``valiant`` mode and the
    reference checks it in the same mode."""
    resolved = run.resolve(spec, workload)
    resolved["config"] = dal_config
    res = run.run_cell(resolved, 2**31 + 4243, 0.2, trace=False,
                       require_tpu=False)
    err = capsys.readouterr().err
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert "routing: valiant (configuration mphx-2p-8x8-dal)" in err
    assert res["checks"]["incidence_gap"]["value"] <= 1e-15


def test_readers_return_nothing_without_readings():
    ctx = {"sims": [], "n_sims": 0, "window_s": 0.0, "setup_s": 1.0,
           "trace": None}
    for name in ("sim_s", "incidence_s", "solve_s", "device_busy_s",
                 "idle_share"):
        assert run.reader(name)(ctx) is None, name


def test_command_refuses_the_cpu(capsys):
    assert run.main(["--workload", "mphx4p-churn", "--seed", "1",
                     "--seconds", "1"]) == 3
    assert capsys.readouterr().out == ""


def test_command_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and bench/ prints no result
    and exits non-zero."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mphx4p-churn",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
