"""A run with the timed path broken underneath comes out not correct.

Each test drives ``run.run_cell`` as a run does, past the look for a
chip, at ``mphx-2p-8x8`` with one of the cells' traffic mixes, with one
fault planted in the program:

* a step that returns its state unchanged: the jitted event loop hands
  back its initial state;
* half of the batch left out: the routing engine's incidence drops the
  entries of the second half of the flows;
* an answer altered where it is produced: one flow's finish time is
  moved by one part in 10**7 as the result is assembled;
* a load put on the wrong edge: the routing engine puts every hop on
  the reverse of its link (``v -> u`` for ``u -> v``), which leaves the
  hops, and under symmetric traffic the finish times and the multiset of
  edge loads, as they were.

Under DAL routing (``valiant``, the test configuration
``mphx-2p-8x8-dal``) the same four, and two of DAL's own:

* deroutes dropped: the routing engine's incidence is the minimal one,
  with minimal weights, whatever mode it is asked for;
* a deroute via the destination's coordinate: besides DAL's deroutes,
  each flow gets, for each mismatched dimension, a path that first moves
  to the destination's coordinate there, with the same share.

The fourth fault of the list, the exchange between chips left out, has
no place here: every cell runs on one chip and the program exchanges
nothing between chips.
"""

import numpy as np
import pytest

import run
from repro.core.routing_vec import VectorizedHyperXRouter
from repro.sim import events


def _run(spec, config, workload):
    resolved = run.resolve(spec, workload)
    resolved["config"] = config
    return run.run_cell(resolved, 2**31 + 99, 0.2, trace=False,
                        require_tpu=False)


def _state_unchanged(monkeypatch):
    def loop():
        def run_(flow, edge, frac, cap_e, size, caps, start, tol, sel=None,
                 **kw):
            import jax.numpy as jnp

            finish = jnp.where(size == 0, start, jnp.inf)
            return (finish, jnp.zeros(kw["E"], dtype=size.dtype),
                    jnp.int32(0), jnp.bool_(True), jnp.bool_(True),
                    jnp.int32(0))
        return run_
    monkeypatch.setattr(events, "_event_loop_jit", loop)


def _half_batch(monkeypatch):
    orig = VectorizedHyperXRouter.incidence

    def incidence(self, demands, mode="minimal"):
        flow, edge, frac = orig(self, demands, mode)
        keep = flow < demands.n // 2
        return flow[keep], edge[keep], frac[keep]
    monkeypatch.setattr(VectorizedHyperXRouter, "incidence", incidence)


def _answer_altered(monkeypatch):
    orig = events._finalize_result

    def finalize(inc, size, caps, start, finish, *a, **kw):
        finish = np.array(finish, dtype=np.float64)
        finish[-1] *= 1 + 1e-7
        return orig(inc, size, caps, start, finish, *a, **kw)
    monkeypatch.setattr(events, "_finalize_result", finalize)


def _edges_reversed(monkeypatch):
    orig = VectorizedHyperXRouter.incidence

    def incidence(self, demands, mode="minimal"):
        flow, edge, frac = orig(self, demands, mode)
        idx = self.index
        dim = np.searchsorted(idx.dim_base, edge, side="right") - 1
        dims = np.asarray(idx.dims, dtype=np.int64)[dim]
        u, c = np.divmod(edge - idx.dim_base[dim], dims)
        cu = idx.ids_to_coords(u)
        cv = cu.copy()
        cv[np.arange(edge.size), dim] = c
        v = idx.coords_to_ids(cv)
        return flow, idx.dim_base[dim] + v * dims + cu[np.arange(edge.size),
                                                       dim], frac
    monkeypatch.setattr(VectorizedHyperXRouter, "incidence", incidence)


def _deroutes_dropped(monkeypatch):
    orig = VectorizedHyperXRouter.incidence

    def incidence(self, demands, mode="minimal"):
        return orig(self, demands, "minimal")
    monkeypatch.setattr(VectorizedHyperXRouter, "incidence", incidence)


def _via_destination(monkeypatch):
    orig = VectorizedHyperXRouter._iter_deroute_hops

    def hops(self, src, cs, cd, mism):
        yield from orig(self, src, cs, cd, mism)
        idx = self.index
        for i in range(idx.D):
            mask = mism[:, i]
            yield idx.slots(src, i, cd[:, i]), mask
            cur_id = src + (cd[:, i] - cs[:, i]) * idx.stride[i]
            cur = cs.copy()
            cur[:, i] = cd[:, i]
            for j in range(idx.D):
                step = mask & (cur[:, j] != cd[:, j])
                if step.any():
                    yield idx.slots(cur_id, j, cd[:, j]), step
                cur_id = cur_id + (cd[:, j] - cur[:, j]) * idx.stride[j]
                cur[:, j] = cd[:, j]
    monkeypatch.setattr(VectorizedHyperXRouter, "_iter_deroute_hops", hops)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered,
          "edges_reversed": _edges_reversed}
DAL_FAULTS = {"deroutes_dropped": _deroutes_dropped,
              "via_destination": _via_destination}
CELLS = ["mphx4p-hotspot", "mphx4p-uniform", "mphx4p-churn"]


def _assert_incorrect(res):
    assert res["correct"] is False, res["checks"]
    assert list(res)[-1] == "checks"
    assert any(c["value"] > 1e-9 for c in res["checks"].values()), \
        res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(x64, spec, small_config, workload):
    res = _run(spec, small_config, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_makes_run_incorrect(x64, monkeypatch, spec, small_config,
                                   workload, fault):
    FAULTS[fault](monkeypatch)
    _assert_incorrect(_run(spec, small_config, workload))


@pytest.mark.parametrize("fault", sorted(FAULTS) + sorted(DAL_FAULTS))
@pytest.mark.parametrize("workload", ["mphx4p-hotspot", "mphx4p-churn"])
def test_fault_makes_dal_run_incorrect(x64, monkeypatch, spec, dal_config,
                                       workload, fault):
    {**FAULTS, **DAL_FAULTS}[fault](monkeypatch)
    _assert_incorrect(_run(spec, dal_config, workload))
