"""From a profiler trace (``.xplane.pb``) to device-busy time, time by device
program and operation, and idle gaps labelled by what the host was doing.

    python -m benchmark.trace_reduce --selftest          # no chip needed
    python -m benchmark.trace_reduce <file.xplane.pb>    # print the reduction

What counts as the device running: the events of the lines ``XLA Ops`` and
``Async XLA Ops`` (copies that run beside the compute) of each plane
``/device:TPU:<n>``. ``XLA Modules`` gives the time by program; other lines of
those planes repeat the same intervals and are not read. Busy is the union of
the operations' intervals inside the window, so overlapping operations count
once. Without a TPU plane (a CPU rehearsal) the host-plane events that carry
an ``hlo_module`` stat stand in, so that the same code runs; nothing from such
a trace is a device number.

The window is the host range named ``WINDOW`` that the harness writes around
the traced query; without one it is the whole trace.

An idle gap is labelled by what a Python thread of the host was doing at its
middle: the two innermost ranges open there, as ``outer > inner``. A Python
thread is a host line that holds the window range or a ``PjitFunction(..)``
range (JAX's dispatch of one jitted call); what the runtime nests inside such
a dispatch (argument parsing, allocation, enqueueing) is not descended into,
since it says nothing the dispatch's own name does not. Where several threads
have a range open, the one whose innermost range is shortest is taken: a
thread that waits sits in a long range, the one that works in short ones.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

import numpy as np

WINDOW = "benchmark.traced_query"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
DISPATCH = "PjitFunction("


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def read_planes(path: str) -> dict:
    """{"device": {chip: {line: [(start_ns, end_ns, name)]}},
        "host": [[(start_ns, end_ns, name)] a line], "stand_in": bool}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: dict = {}
    host: list = []
    host_xla: list = []
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            lines = device.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = []
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    ev = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if not device and any(k == "hlo_module"
                                          for k, _ in e.stats):
                        host_xla.append(ev)
                    else:
                        events.append(ev)
                host.append(events)
    stand_in = not any(lines for lines in device.values())
    if stand_in:
        device = {0: {OPS_LINE: host_xla}}
    return {"device": device, "host": host, "stand_in": stand_in}


def union_length(intervals, lo, hi):
    """Merged (start, end) pairs clipped to [lo, hi], and their length."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    merged = []
    for s, e in clipped:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def _base(name: str) -> str:
    """A host label without its arguments and counters:
    ``scan.read[file=..]`` -> ``scan.read``, ``step12`` -> ``step``."""
    name = name.split("[", 1)[0].split("#", 1)[0].strip()
    return re.sub(r"[.\d]+$", "", name) or name


_HLO = re.compile(r"^%(?P<name>[^ ]+) = (?P<type>.*?) (?P<op>[a-z][a-z0-9-]*)\(")


def op_key(name: str) -> str:
    """A device operation as the trace names it (its HLO text) cut to what
    says which work it is: the instruction's name without its number, a
    custom call's target, and the result's type without layouts."""
    m = _HLO.match(name)
    if not m:
        return _base(name)
    what = re.sub(r"[.\d]+$", "", m.group("name")) or m.group("op")
    target = re.search(r'custom_call_target="([^"]+)"', name)
    if target:
        what += ":" + target.group(1)
    result = re.sub(r"\{[^}]*\}", "", m.group("type"))
    return (what + " " + result)[:80]


def _top(totals: dict, n: int = 10) -> list:
    return [[k, v / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def _clipped_totals(events, lo, hi, key) -> dict:
    out: dict = {}
    for s, e, name in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            k = key(name)
            out[k] = out.get(k, 0) + d
    return out


def _stacks_at(events: list, points: list) -> list:
    """For one host line whose ranges nest, the names open at each of the
    sorted ``points``, outermost first, cut after the first dispatch; with
    each the length of the innermost range kept."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    out, stack, i = [], [], 0
    for p in points:
        while i < len(events) and events[i][0] <= p:
            while stack and stack[-1][1] <= events[i][0]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        kept = []
        for ev in stack:
            if ev[2] == WINDOW:
                continue
            kept.append(ev)
            if ev[2].startswith(DISPATCH):
                break
        out.append(([ev[2] for ev in kept],
                    kept[-1][1] - kept[-1][0] if kept else None))
    return out


def label_gaps(gaps: list, host: list) -> dict:
    """{label: ns} over every gap; see the module's docstring."""
    out: dict = {}
    gaps = sorted(gaps)
    mids = [(s + e) / 2 for s, e in gaps]
    threads = [line for line in host
               if any(ev[2] == WINDOW or ev[2].startswith(DISPATCH)
                      for ev in line)]
    per_thread = [_stacks_at(line, mids) for line in threads]
    for g, (s, e) in enumerate(gaps):
        best = None
        for stacks in per_thread:
            names, innermost = stacks[g]
            if names and (best is None or innermost < best[1]):
                best = (names, innermost)
        if best is None:
            label = "(harness)" if threads else "(no host range)"
        else:
            label = " > ".join(_base(n) for n in best[0][-2:])
        out[label] = out.get(label, 0) + e - s
    return out


def reduce_planes(planes: dict, chips: int = 1) -> dict:
    host = planes["host"]
    flat = [ev for line in host for ev in line]
    windows = [h for h in flat if h[2] == WINDOW]
    if windows:
        lo, hi = windows[0][0], windows[0][1]
    else:
        every = flat + [ev for lines in planes["device"].values()
                        for evs in lines.values() for ev in evs]
        if not every:
            raise ValueError("the trace holds no event")
        lo, hi = min(e[0] for e in every), max(e[1] for e in every)
    per_chip = {}
    for chip, lines in planes["device"].items():
        evs = (lines.get(OPS_LINE) or []) + (lines.get(ASYNC_LINE) or [])
        merged, busy = union_length([(s, e) for s, e, _ in evs], lo, hi)
        per_chip[chip] = {"busy": busy, "merged": merged, "ops": evs,
                          "programs": lines.get(MODULES_LINE) or []}
    used = sorted(per_chip, key=lambda c: -per_chip[c]["busy"])[:chips]
    if not used or per_chip[used[0]]["busy"] <= 0:
        raise ValueError("no operation ran on a device inside the window")
    busy_ns = sum(per_chip[c]["busy"] for c in used) / len(used)
    ops: dict = {}
    programs: dict = {}
    for c in used:
        for k, v in _clipped_totals(per_chip[c]["ops"], lo, hi,
                                    op_key).items():
            ops[k] = ops.get(k, 0) + v / len(used)
        for k, v in _clipped_totals(per_chip[c]["programs"], lo, hi,
                                    lambda n: n).items():
            programs[k] = programs.get(k, 0) + v / len(used)
    merged = per_chip[used[0]]["merged"]
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "window_from_annotation": bool(windows),
            "window_ns": [lo, hi],
            "stand_in": planes["stand_in"],
            "device_events": sum(len(per_chip[c]["ops"]) for c in used),
            "host_events": len(flat),
            "program_runs": sum(len(per_chip[c]["programs"]) for c in used),
            "device_ops": _top(ops), "device_programs": _top(programs),
            "idle_gaps": _top(label_gaps(gaps, host)),
            "longest_gap_s": max((e - s for s, e in gaps), default=0) / 1e9}


def reduce_file(path: str, chips: int = 1) -> dict:
    return reduce_planes(read_planes(path), chips)


# -- self-test ---------------------------------------------------------------

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata", "small_tpu.xplane.pb")


def _selftest() -> None:
    # 1. made-up planes whose answers are known by hand (ns)
    hlo = "%copy.3 = s32[32768]{0:T(1024)} copy(s32[32768]{0:T(1024)} %a.1)"
    planes = {"stand_in": False, "device": {0: {
        OPS_LINE: [(100, 200, "fusion.1"), (150, 260, "sort.7"),
                   (400, 500, "fusion.2")],
        ASYNC_LINE: [(900, 1200, hlo)],
        MODULES_LINE: [(100, 260, "jit_a(1)"), (400, 500, "jit_b(2)"),
                       (900, 1200, "jit_a(1)")]}},
        "host": [[(0, 1000, WINDOW), (0, 1000, "query"),
                  (255, 405, "scan.read[file=x]"), (500, 890, "join.build"),
                  (600, 700, "sync")],
                 [(560, 860, DISPATCH + "sort)"), (650, 750, "ParseArgs")],
                 [(0, 1000, "a runtime thread, never asked")]]}
    r = reduce_planes(planes)
    assert r["window_s"] == 1000e-9, r
    # busy: [100,260] + [400,500] + [900,1000 clipped] = 160 + 100 + 100
    assert abs(r["busy_s"] - 360e-9) < 1e-15, r
    ops = dict(map(tuple, r["device_ops"]))
    assert abs(ops["fusion"] - 200e-9) < 1e-15 and \
        abs(ops["sort"] - 110e-9) < 1e-15 and \
        abs(ops["copy s32[32768]"] - 100e-9) < 1e-15, ops
    progs = dict(map(tuple, r["device_programs"]))
    assert abs(progs["jit_a(1)"] - 260e-9) < 1e-15, progs
    gaps = dict(map(tuple, r["idle_gaps"]))
    # gaps: [0,100] in query; [260,400] in scan.read; [500,900]: at 700 the
    # first thread is in join.build (390 long, sync has ended), the second
    # in a dispatch (300 long, not descended into), and the shorter wins
    assert abs(gaps["query"] - 100e-9) < 1e-15 and \
        abs(gaps["query > scan.read"] - 140e-9) < 1e-15 and \
        abs(gaps[DISPATCH + "sort)"] - 400e-9) < 1e-15, gaps
    assert abs(sum(gaps.values()) + r["busy_s"] - r["window_s"]) < 1e-15
    # two chips: the mean of the two unions
    planes["device"][1] = {OPS_LINE: [(0, 1000, "fusion.9")]}
    assert abs(reduce_planes(planes, chips=2)["busy_s"] - 680e-9) < 1e-15
    # 2. the recorded trace, against a second way of getting the same numbers
    rec = read_planes(RECORDED)
    assert not rec["stand_in"], "the recorded trace holds no TPU plane"
    r = reduce_planes(rec)
    assert r["window_from_annotation"], r
    lo, hi = r["window_ns"]
    evs = rec["device"][0][OPS_LINE] + rec["device"][0].get(ASYNC_LINE, [])
    # brute force: mark every nanosecond tick of a coarse grid that an
    # operation covers; agrees with the merge to the grid's width
    step = max((hi - lo) // 2_000_000, 1)
    grid = np.zeros(int((hi - lo) // step) + 2, dtype=np.int32)
    for s, e, _ in evs:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // step)] += 1
            grid[int((b - lo) // step) + 1] -= 1
    covered = (np.cumsum(grid) > 0).sum() * step
    assert abs(covered - r["busy_s"] * 1e9) <= 2 * step * (len(evs) + 1), \
        (covered, r["busy_s"])
    assert 0 < r["busy_s"] <= r["window_s"], r
    gaps = sum(v for _, v in r["idle_gaps"])
    assert abs(gaps - (r["window_s"] - r["busy_s"])) < 1e-9, r
    with open(RECORDED + ".expected.json") as f:
        want = json.load(f)
    for k in ("window_s", "busy_s"):
        assert abs(r[k] - want[k]) <= 1e-12, (k, r[k], want[k])
    assert r["device_ops"] == want["device_ops"], r["device_ops"]
    assert r["idle_gaps"] == want["idle_gaps"], r["idle_gaps"]
    labels = {k for k, _ in r["idle_gaps"]}
    assert set(want["must_label"]) <= labels, labels
    print("trace_reduce selftest ok:", json.dumps(
        {k: r[k] for k in ("window_s", "busy_s", "device_events",
                           "host_events")}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", nargs="?")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    if args.selftest:
        _selftest()
        return 0
    if not args.path:
        ap.error("give a trace file or --selftest")
    path = args.path if os.path.isfile(args.path) else find_xplane(args.path)
    print(json.dumps(reduce_file(path, args.chips), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
