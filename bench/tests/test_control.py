"""The control: the reference computed in float32, one precision below
the configurations' float64, put in the program's place, has to come
out as not correct.  Here at ``mphx-2p-8x8`` on the CPU; at the cells'
own sizes on the chip with ``bench/calibrate.py``."""

import os

import numpy as np
import pytest

import compare
import run
from gen import Plane, Traffic

SEEDS = (2**31 + 5, 2**32 + 17, 3)


def _views(config, mix, seed, k):
    plane = Plane.from_config(config)
    traffic = Traffic(run.load_json(os.path.join(run.BENCH, "traffic",
                                                 mix + ".json")),
                      plane, seed)
    inp = traffic.inputs(k)
    return (compare.reference_view(plane, config, inp),
            compare.reference_view(plane, config, inp, dtype=np.float32))


@pytest.mark.parametrize("mix", ["hotspot", "uniform", "churn"])
@pytest.mark.parametrize("seed", SEEDS)
def test_float32_control_fails(small_config, mix, seed):
    want, control = _views(small_config, mix, seed, k=1)
    nums = compare.numbers(control, want)
    assert not compare.verdict(nums), nums
    # the incidence and the finish times each catch it on their own
    lim = {k: v[0] for k, v in compare.LIMITS.items()}
    assert nums["incidence_gap"] > lim["incidence_gap"]
    assert nums["finish_gap"] > lim["finish_gap"]


@pytest.mark.parametrize("mix", ["hotspot", "uniform", "churn"])
@pytest.mark.parametrize("seed", SEEDS)
def test_float32_control_fails_under_dal(dal_config, mix, seed):
    want, control = _views(dal_config, mix, seed, k=1)
    nums = compare.numbers(control, want)
    assert not compare.verdict(nums), nums
    lim = {k: v[0] for k, v in compare.LIMITS.items()}
    assert nums["incidence_gap"] > lim["incidence_gap"]


@pytest.mark.parametrize("mix", ["hotspot", "churn"])
def test_reference_against_itself_is_exact(small_config, mix):
    want, _ = _views(small_config, mix, SEEDS[0], k=0)
    again, _ = _views(small_config, mix, SEEDS[0], k=0)
    nums = compare.numbers(again, want)
    assert compare.verdict(nums)
    assert all(v == 0 for v in nums.values()), nums
