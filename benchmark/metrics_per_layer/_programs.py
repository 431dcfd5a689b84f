"""What the readers of device program names share: every run of a program
on the device inside the traced span, from the ``.xplane.pb`` that the run
has just written.

    python -m benchmark.metrics_per_layer._programs <dir or file.xplane.pb>

prints device seconds and runs by program name, the fingerprint that the
trace appends (``jit_srt_HashJoin_probe(123..)``) cut off.

Only the ``XLA Modules`` lines of the ``/device:TPU:<n>`` planes are read, so
this costs seconds where a second full reduction would cost a minute. The
file is looked for where ``run.py`` puts it (``<workdir>/trace``, the
workdir being ``benchmark_work`` or a directory below it), and taken only if
it holds as many program runs as the reduction in ``ctx["trace"]`` counted:
another run's file gives no reading. A trace without a TPU plane (a CPU
rehearsal) gives none either.
"""

import glob
import os
import re
import sys

from benchmark import trace_reduce

_WORK = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark_work")


def base_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def module_events(path: str) -> dict:
    """{chip: [(start_ns, end_ns, name)]} from the device planes alone."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce._TPU_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                out[int(m.group(1))] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
    return out


def program_runs(ctx):
    """[(name, seconds inside the traced span)] a run, or None."""
    if "_program_runs" in ctx:
        return ctx["_program_runs"]
    ctx["_program_runs"] = None
    trace = ctx.get("trace")
    if not trace or trace.get("stand_in") or not trace.get("window_ns"):
        return None
    lo, hi = trace["window_ns"]
    chips = int(ctx["cell"]["chips"])
    for logdir in [os.path.join(_WORK, "trace")] + sorted(
            glob.glob(os.path.join(_WORK, "*", "trace"))):
        try:
            by_chip = module_events(trace_reduce.find_xplane(logdir))
        except (FileNotFoundError, OSError):
            continue
        used = sorted(by_chip, key=lambda c: -len(by_chip[c]))[:chips]
        if sum(len(by_chip[c]) for c in used) != trace.get("program_runs"):
            continue
        ctx["_program_runs"] = [
            (base_name(name), (min(e, hi) - max(s, lo)) / 1e9)
            for c in used for s, e, name in by_chip[c]
            if min(e, hi) > max(s, lo)]
        break
    return ctx["_program_runs"]


def queries_in_span(ctx) -> float:
    """How many queries the traced span holds, a query that lies partly
    inside it counting for that part (as query_hbm_roofline counts bytes)."""
    span = ctx.get("traced_span")
    if not span:
        return 0.0
    total = 0.0
    for d in ctx["done"]:
        inside = min(d["end"], span[1]) - max(d["start"], span[0])
        if inside > 0 and d["end"] > d["start"]:
            total += inside / (d["end"] - d["start"])
    return total


def by_name(path: str) -> list:
    """[(name, seconds, runs)] over the whole file, most seconds first."""
    totals: dict = {}
    for events in module_events(path).values():
        for s, e, name in events:
            row = totals.setdefault(base_name(name), [0.0, 0])
            row[0] += (e - s) / 1e9
            row[1] += 1
    return sorted(((k, v[0], v[1]) for k, v in totals.items()),
                  key=lambda r: -r[1])


if __name__ == "__main__":
    where = sys.argv[1]
    if not os.path.isfile(where):
        where = trace_reduce.find_xplane(where)
    for name, secs, runs in by_name(where):
        print(f"{secs:12.6f} s {runs:8d} runs  {name}")
