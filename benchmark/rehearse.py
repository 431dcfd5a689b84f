#!/usr/bin/env python3
"""CPU rehearsal: every cell's code path at SF 0.01, no chip and no number.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--workload <cell>] [--trace 1]

Drives benchmark/run.py's ``run_cell`` (data from the seed, session, warm-up,
window, trace reduction, readers, reference, comparison) on the CPU platform
with Pallas in interpret mode. What it prints are counts and the outcome of
the comparison, under names that are not the device metrics': a time, a rate
or a share from here means nothing and is not printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SCALE = 0.01


def main(argv=None) -> int:
    from benchmark import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=2**31 + 12345)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cells = args.workload or [
        w["name"] for w in run.load_json(ROOT, "BENCHMARK.json")["workloads"]]
    ok = True
    for cell in cells:
        r = run.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         rehearsal=True, scale=SCALE,
                         workdir=os.path.join(run.WORKDIR, "rehearsal"))
        ok = ok and r["correct"]
        print(json.dumps({
            "rehearsal": True, "cell": cell, "scale_factor": SCALE,
            "platform": r["device"]["platform"],
            "results_agree_with_reference": r["correct"],
            "queries_started": r["attempted"], "queries_failed": r["failed"],
            "readers_that_found_something": sorted(r["metrics"]),
            "compared": r["compared"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
