#!/usr/bin/env python3
"""Device seconds and runs by program name, a device plane at a time.

    python tools/trace_by_chip.py <dir or file.xplane.pb> [--json out.json]

What `python -m benchmark.metrics_per_layer._programs` prints summed over the
planes, kept apart by chip: on a mesh the question is which chip ran what.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def by_chip(path: str) -> dict:
    """{chip: {program: [seconds, runs]}} over the whole file."""
    from benchmark.metrics_per_layer import _programs
    out = {}
    for chip, events in sorted(_programs.module_events(path).items()):
        rows = out.setdefault(chip, {})
        for s, e, name in events:
            row = rows.setdefault(_programs.base_name(name), [0.0, 0])
            row[0] += (e - s) / 1e9
            row[1] += 1
    return out


def main(argv=None) -> int:
    from benchmark import trace_reduce
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--json")
    args = ap.parse_args(argv)
    path = args.path
    if not os.path.isfile(path):
        path = trace_reduce.find_xplane(path)
    table = by_chip(path)
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(table, f, indent=1)
    names = sorted({n for rows in table.values() for n in rows},
                   key=lambda n: -sum(rows.get(n, [0])[0]
                                      for rows in table.values()))
    chips = sorted(table)
    print("program".ljust(44) + "".join(f"  chip {c}: s / runs".rjust(22)
                                        for c in chips))
    for n in names:
        print(n[:44].ljust(44) + "".join(
            f"{table[c].get(n, [0.0, 0])[0]:14.4f} /{table[c].get(n, [0.0, 0])[1]:5d}"
            for c in chips))
    return 0


if __name__ == "__main__":
    sys.exit(main())
