"""Read the program's own spans, scopes and round counter in one cell.

    python3 bench/span_readings.py --workload <cell> --seed <n> --seconds <s>

Run on the chip from the checkout root.  It makes one ``--trace 1`` run
of the cell through ``run.run_cell``, unchanged, and keeps two things the
harness drops: the window's telemetry registry (the program's ``sim.*``
and ``incidence.*`` span timers and its counters) and, before the
profile is deleted, the ``trace_scopes`` reductions of it.  The last
line of standard output is one JSON object:

* ``readings``: per simulation, ``compress_s``, ``transfer_s``,
  ``loop_s``, ``readback_s``, ``finalize_s`` (the solver call's spans),
  ``walk_s``, ``coalesce_s`` (the incidence's), means over the window;
  ``waterfill_rounds`` and ``epochs`` (counters over ``sim.runs``);
  ``waterfill_device_s`` and ``epoch_device_s`` (device self time under
  the ``waterfill.*`` and ``epoch.*`` scopes per traced simulation);
  None where nothing was found;
* ``coverage``: the share of ``solve_s``, ``incidence_s`` and
  ``device_busy_s`` that those readings account for, and of the idle
  that ``trace_reduce`` puts under ``bench.solve`` and
  ``bench.incidence`` that ``idle_by_span`` charges to a program span;
* the run's ``correct``, ``metrics`` and ``breakdown``, and the two
  reductions in full.

Nothing in ``BENCHMARK.json`` reads this; the metrics it prints are the
ones a benchmark change would add as readers of the harness.
"""

import argparse
import contextlib
import json
import os
import sys

import run

SPANS = {"compress_s": "sim.compress", "transfer_s": "sim.transfer",
         "loop_s": "sim.loop", "readback_s": "sim.readback",
         "finalize_s": "sim.finalize", "walk_s": "incidence.walk",
         "coalesce_s": "incidence.coalesce"}
SCOPES = {"waterfill_device_s": "waterfill.", "epoch_device_s": "epoch."}
COVER = {"solve_s": ("compress_s", "transfer_s", "loop_s", "readback_s",
                     "finalize_s"),
         "incidence_s": ("walk_s", "coalesce_s"),
         "device_busy_s": ("waterfill_device_s", "epoch_device_s")}
PROGRAM_SPANS = ("sim.", "incidence.")


def readings(snapshot: dict, n_sims: int, scopes: "dict | None",
             n_traced: int) -> dict:
    """The ten readings (and epochs) from a registry ``snapshot`` over
    ``n_sims`` window simulations and the ``device_scopes`` of
    ``n_traced`` traced ones."""
    timers, counters = snapshot["timers"], snapshot["counters"]
    out = {key: (timers[name]["total_s"] / n_sims
                 if name in timers and n_sims else None)
           for key, name in SPANS.items()}
    runs = counters.get("sim.runs", 0)
    out["waterfill_rounds"] = (counters["waterfill.rounds"] / runs
                               if runs and "waterfill.rounds" in counters
                               else None)
    out["epochs"] = counters.get("sim.epochs", 0) / runs if runs else None
    for key, prefix in SCOPES.items():
        out[key] = (sum(v for k, v in scopes.items()
                        if k.startswith(prefix)) / n_traced
                    if scopes and n_traced else None)
    return out


def coverage(read: dict, metrics: dict, idle_gaps, idle_spans) -> dict:
    """Shares (0 to 1) of each covered metric and of the harness spans'
    idle that the program's readings account for; None where a part is
    missing."""
    out = {}
    for total, parts in COVER.items():
        whole = metrics.get(total, {}).get("value")
        vals = [read[p] for p in parts]
        out[total] = (sum(vals) / whole
                      if whole and None not in vals else None)
    gaps = dict(idle_gaps or [])
    harness = gaps.get("bench.solve", 0.0) + gaps.get("bench.incidence", 0.0)
    out["idle_in_program_spans"] = (
        sum(v for k, v in idle_spans.items()
            if k.startswith(PROGRAM_SPANS)) / harness
        if idle_spans and harness > 0 else None)
    return out


def run_with_readings(resolved: dict, seed: int, seconds: float,
                      require_tpu: bool = True) -> dict:
    """One ``--trace 1`` run of the cell, with the readings it drops."""
    import trace_reduce
    import trace_scopes

    import repro.telemetry as telemetry

    kept: dict = {"registry": None, "trace": None, "scopes": None,
                  "idle_spans": None}
    collecting, load, reduce = (telemetry.collecting, trace_reduce.load,
                                trace_reduce.reduce)

    @contextlib.contextmanager
    def keep_registry(*a, **kw):
        with collecting(*a, **kw) as reg:
            kept["registry"] = reg
            yield reg

    def load_both(path):
        try:
            events = trace_scopes.load(path)
        except FileNotFoundError:
            events = None
        if events is not None:
            kept["scopes"] = trace_scopes.device_scopes(events)
            kept["idle_spans"] = trace_scopes.idle_by_span(events)
        return load(path)

    def keep_reduce(pd):
        kept["trace"] = reduce(pd)
        return kept["trace"]

    telemetry.collecting = keep_registry
    trace_reduce.load, trace_reduce.reduce = load_both, keep_reduce
    try:
        result = run.run_cell(resolved, seed, seconds, trace=True,
                              require_tpu=require_tpu)
    finally:
        telemetry.collecting = collecting
        trace_reduce.load, trace_reduce.reduce = load, reduce
    n_sims = result["attempted"] - result["failed"]
    red = kept["trace"]
    read = readings(kept["registry"].snapshot(), n_sims, kept["scopes"],
                    red["n_sims"] if red else 0)
    return {"correct": result["correct"], "readings": read,
            "coverage": coverage(read, result["metrics"],
                                 red["idle_gaps"] if red else None,
                                 kept["idle_spans"]),
            "metrics": result["metrics"],
            "breakdown": result.get("breakdown"),
            "device_scopes": kept["scopes"],
            "idle_by_span": kept["idle_spans"],
            "device": result["device"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    resolved = run.resolve(run.load_json(os.path.join(run.ROOT,
                                                      "BENCHMARK.json")),
                           args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    try:
        out = run_with_readings(resolved, args.seed, args.seconds)
    except run.NoChip as e:
        run.log(f"span_readings: {e}")
        return 3
    out = {"workload": args.workload, "seed": args.seed, **out}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
