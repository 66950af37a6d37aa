"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py --out <dir>

Run on the chip from the checkout root.  Three simulations of the small
``mphx-2p-8x8`` plane (two hotspot, one uniform), each under the
harness's ``bench.*`` annotations, with the profiler on; the profiler's
``.xplane.pb`` (gzipped) and its ``.trace.json.gz`` rendering are
written to ``<dir>``.  Commit them as ``bench/tests/data/``.
"""

import argparse
import glob
import gzip
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import run  # noqa: E402
from gen import Plane, Traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax

    run.chips(1)
    config = run.load_json(os.path.join(HERE, "mphx-2p-8x8.json"))
    plane = Plane.from_config(config)
    sim = run.Simulator(run.build_router(config))
    inputs = []
    for name, ks in (("hotspot", (0, 1)), ("uniform", (0,))):
        mix = run.load_json(os.path.join(BENCH, "traffic", name + ".json"))
        traffic = Traffic(mix, plane, seed=2**31 + 11)
        inputs += [traffic.inputs(k) for k in ks]
    for inp in inputs:            # compile outside the trace
        sim(inp)
    tmp = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    for inp in inputs:
        sim(inp)
    jax.profiler.stop_trace()
    os.makedirs(args.out, exist_ok=True)
    for path in glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True):
        with open(path, "rb") as f, \
                gzip.open(os.path.join(args.out, "small.xplane.pb.gz"),
                          "wb") as g:
            g.write(f.read())
    for path in glob.glob(os.path.join(tmp, "**", "*.trace.json.gz"),
                          recursive=True):
        shutil.copy(path, os.path.join(args.out, "small.trace.json.gz"))
    shutil.rmtree(tmp, ignore_errors=True)
    print(sorted(os.listdir(args.out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
