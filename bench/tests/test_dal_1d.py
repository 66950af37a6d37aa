"""DAL (``valiant``) on a 1D plane through the harness, on the CPU.

The test configuration ``mphx-2p-16-dal`` is a full mesh of 16 switches
(the preset ``mphx-2p-16``, registered here for the tests), where each
flow takes the direct link and 14 two-hop deroutes and the routing
engine writes its incidence in place instead of coalescing it.  A sound
run is correct under the churn and hotspot mixes, the float32 control
is not, and neither is a run whose direct paths carry twice their
weight.
"""

import json
import os

import numpy as np
import pytest

import compare
import run
from gen import Plane, Traffic
from repro.core.hyperx import MPHX
from repro.core.routing_vec import VectorizedHyperXRouter

HERE = os.path.dirname(os.path.abspath(__file__))
SEEDS = (2**31 + 11, 2**32 + 3, 7)
WORKLOADS = {"churn": "mphx4p-churn", "hotspot": "mphx4p-hotspot"}


@pytest.fixture
def config(monkeypatch):
    from repro.experiments.sweep import SWEEP_TOPOLOGIES

    monkeypatch.setitem(SWEEP_TOPOLOGIES, "mphx-2p-16",
                        MPHX(n=2, p=16, dims=(16,)))
    return run.load_json(os.path.join(HERE, "mphx-2p-16-dal.json"))


def _run(spec, config, mix, seed=2**31 + 99):
    resolved = run.resolve(spec, WORKLOADS[mix])
    resolved["config"] = config
    return run.run_cell(resolved, seed, 0.2, trace=False, require_tpu=False)


def _direct_doubled(monkeypatch):
    orig = VectorizedHyperXRouter.incidence

    def incidence(self, demands, mode="minimal"):
        flow, edge, frac = orig(self, demands, mode)
        direct = edge == self.index.slots(demands.src[flow], 0,
                                          demands.dst[flow])
        return flow, edge, np.where(direct, 2 * frac, frac)
    monkeypatch.setattr(VectorizedHyperXRouter, "incidence", incidence)


def test_dal_cell_is_the_uncut_table2_fabric(spec):
    """``mphx8p-dal-churn`` resolves to the Table-2 MPHX(8,256,256) preset
    at its 65,536 NICs, routed with DAL, under the unchanged churn mix."""
    from repro.experiments.sweep import SWEEP_TOPOLOGIES

    res = run.resolve(spec, "mphx8p-dal-churn")
    cfg = res["config"]
    assert cfg["name"] == "mphx-8p-256-dal"
    assert cfg["precision"] == "float64"
    assert run.routing_of(cfg) == "valiant"
    assert cfg["reduced"] == []
    assert res["mix"] == run.load_json(
        os.path.join(run.BENCH, "traffic", "churn.json"))
    plane = Plane.from_config(cfg)
    topo = SWEEP_TOPOLOGIES[cfg["preset"]]
    assert plane.S == topo.switches_per_plane == 256
    assert plane.S * plane.p == topo.n_nics == 65536
    minimal = run.load_json(os.path.join(run.BENCH, "configs",
                                         "mphx-8p-256.json"))
    for key in ("preset", "topology", "net", "precision", "guarantees"):
        assert cfg[key] == minimal[key], key


@pytest.mark.parametrize("mix", sorted(WORKLOADS))
def test_sound_run_is_correct(x64, capsys, spec, config, mix):
    from repro.telemetry import collecting

    with collecting() as warm:      # the window collects on its own
        res = _run(spec, config, mix)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["incidence_gap"]["value"] <= 1e-15
    # the entries were placed, not coalesced
    timers = warm.snapshot()["timers"]
    assert "incidence.place" in timers and "incidence.coalesce" not in timers
    line = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("counters: ")][-1]
    counters = json.loads(line[len("counters: "):])
    assert counters["incidence.merged"] == 0
    per_sim = counters["incidence.entries"] / counters["sim.runs"]
    flows = counters["sim.flows"] / counters["sim.runs"]
    assert per_sim == flows * (2 * 16 - 3)


@pytest.mark.parametrize("mix", sorted(WORKLOADS))
def test_direct_path_doubled_is_incorrect(x64, monkeypatch, spec, config,
                                          mix):
    _direct_doubled(monkeypatch)
    res = _run(spec, config, mix)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["incidence_gap"]["value"] > 1e-9


@pytest.mark.parametrize("mix", sorted(WORKLOADS))
@pytest.mark.parametrize("seed", SEEDS)
def test_float32_control_fails(config, mix, seed):
    plane = Plane.from_config(config)
    traffic = Traffic(run.load_json(os.path.join(run.BENCH, "traffic",
                                                 mix + ".json")),
                      plane, seed)
    inp = traffic.inputs(1)
    want = compare.reference_view(plane, config, inp)
    control = compare.reference_view(plane, config, inp, dtype=np.float32)
    nums = compare.numbers(control, want)
    assert not compare.verdict(nums), nums
    assert nums["incidence_gap"] > compare.LIMITS["incidence_gap"][0]
