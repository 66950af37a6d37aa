"""Readings that the limits of ``bench/compare.py`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [<n> ...]
                               [--seconds 2]

Run on the chip from the checkout root; one process, so set-up is paid
once per seed and the compiled programs are shared.  For each seed it
makes one run of the cell (as ``bench/run.py`` does, with a short
window) and keeps the worst number of each kind that the run's check
read: the program's readings.  Then, for the first three seeds, it
puts the reference computed in float32 (the precision below the
configuration's float64) in the program's place, on the inputs of
the first window simulation of each load level, and compares it with
the float64 reference in the same way: the control's readings.  A limit
lies above the largest program reading and below the smallest control
reading.  One JSON object per line on standard output, the summary
last.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

import compare  # noqa: E402
import run  # noqa: E402
from gen import Plane, Traffic  # noqa: E402

CONTROL_SEEDS = 3


def control_readings(resolved: dict, seed: int) -> dict:
    """Worst numbers of the float32 reference against the float64 one,
    over the first window simulation of each load level."""
    config = resolved["config"]
    plane = Plane.from_config(config)
    traffic = Traffic(resolved["mix"], plane, seed)
    per_sim = []
    for k in range(traffic.period):
        inp = traffic.inputs(k)
        want = compare.reference_view(plane, config, inp)
        try:
            got = compare.reference_view(plane, config, inp,
                                         dtype=np.float32)
        except (RuntimeError, FloatingPointError) as e:
            # a control that gives no number has failed
            print(json.dumps({"control_error": repr(e), "seed": seed,
                              "k": k}), flush=True)
            continue
        per_sim.append(compare.numbers(got, want))
    return compare.worst(per_sim)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    resolved = run.resolve(run.load_json(os.path.join(ROOT,
                                                      "BENCHMARK.json")),
                           args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    program, control = [], []
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = run.run_cell(resolved, seed, args.seconds, trace=False,
                           t_start=t0)
        row = {"seed": seed, "correct": res["correct"],
               "attempted": res["attempted"],
               "checks": {k: v["value"] for k, v in res["checks"].items()},
               "sim_s": res["metrics"].get("sim_s", {}).get("value"),
               "run_s": time.perf_counter() - t0}
        program.append(row)
        print(json.dumps({"program": row}), flush=True)
    for seed in args.seeds[:CONTROL_SEEDS]:
        t0 = time.perf_counter()
        row = {"seed": seed,
               "checks": control_readings(resolved, seed),
               "run_s": time.perf_counter() - t0}
        control.append(row)
        print(json.dumps({"control": row}), flush=True)
    names = list(compare.LIMITS)
    summary = {
        "workload": args.workload,
        "program_max": {n: max(r["checks"][n] for r in program)
                        for n in names},
        "control_min": {n: min(r["checks"][n] for r in control)
                        for n in names} if control else None,
        "limits": {n: lim for n, (lim, _) in compare.LIMITS.items()},
    }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
