#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place and
computed in float32, the nearest precision below the float64 that the
configurations state. It has to come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 [--scale 1]

Needs no chip and does not touch JAX: data from each seed at the cell's own
scale, the float64 reference, the float32 control, and the same comparison
that a run makes. Prints one line a seed with each number beside its limit;
exits 0 only if every seed came out as not correct.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control_rows(cell_name: str, seed: int, scale=None, workdir=None) -> tuple:
    """(reference rows, control rows, limit) by query name."""
    from benchmark import run
    _cell, _entry, config, traffic, _bench = run.find_cell(cell_name)
    generator = importlib.import_module(
        f"benchmark.datagen.{config['generator']}")
    sf = config["scale_factor"] if scale is None else scale
    workdir = workdir or run.WORKDIR
    os.makedirs(workdir, exist_ok=True)
    paths = generator.generate(sf, seed, config["tables"], workdir)
    refs = run.references_for(traffic["queries"], paths)
    controls = run.references_for(traffic["queries"], paths,
                                  dtype=np.float32)
    return refs, controls, float(config["compare"]["float_gap_limit"])


def main(argv=None) -> int:
    from benchmark import compare
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--scale", type=float, default=None)
    args = ap.parse_args(argv)
    all_failed = True
    for seed in args.seeds:
        refs, controls, limit = control_rows(args.workload, seed, args.scale)
        correct, compared = compare.compare_all(
            list(controls.items()), refs, limit, 0)
        all_failed = all_failed and not correct
        print(json.dumps({"control": "float32", "cell": args.workload,
                          "seed": seed, "correct": correct,
                          "compared": compared}), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
