"""Share of the traced query's wall time in which the device was idle while
the host worked in the scan: the idle gaps (benchmark/trace_reduce.py) whose
label names a scan range (either of its two names starts with ``scan.`` or
``FileScan.``), over the window. A gap whose innermost range is ``gc`` is a
collection, not the scan's work, and does not count.

The reduction keeps only the ten longest labels, so this is a lower bound.
None for a stand-in trace (no TPU plane) or where no kept label names the
scan."""

SCAN = ("scan.", "FileScan.")


def names_scan(label: str) -> bool:
    names = label.split(" > ")
    return names[-1] != "gc" and any(n.startswith(SCAN) for n in names)


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0 or trace["stand_in"]:
        return None
    idle = [s for label, s in trace["idle_gaps"] if names_scan(label)]
    if not idle:
        return None
    return 100.0 * sum(idle) / trace["window_s"]
