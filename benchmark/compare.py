"""The comparison that decides ``correct``.

Every result that the timed window returned is compared with the plain
reference's rows for the same text on the same data. Three numbers, each
with a limit of its own:

- ``rows_off``: rows too many or too few, summed over the results. Limit 0.
- ``exact_mismatch``: cells of a key, a count, a date or a string that
  differ, or a column that is missing. Limit 0.
- ``float_gap``: the widest gap of a float cell, |got - ref| / max(1, |ref|).
  The limit comes from the configuration's file (``compare.float_gap_limit``)
  and stands between what sound float64 runs read and what the reference
  computed in float32 reads; PERF.md gives both readings.
"""

from __future__ import annotations

import datetime
import math

_EPOCH = datetime.date(1970, 1, 1)


def _plain(v):
    if isinstance(v, datetime.date):
        return (v - _EPOCH).days
    return v


def compare_rows(got: list, ref: list) -> dict:
    """One result against the reference: rows as dicts keyed by column."""
    rows_off = abs(len(got) - len(ref))
    mismatch = 0
    gap = 0.0
    for g, r in zip(got, ref):
        for col, want in r.items():
            if col not in g or g[col] is None:
                mismatch += 1
                continue
            have = _plain(g[col])
            if isinstance(want, float):
                d = abs(float(have) - want) / max(1.0, abs(want))
                gap = max(gap, d) if not math.isnan(d) else math.inf
            elif have != want:
                mismatch += 1
    return {"rows_off": rows_off, "exact_mismatch": mismatch,
            "float_gap": gap}


def compare_all(results: list, references: dict, float_gap_limit: float,
                unanswered: int) -> tuple:
    """``results``: [(query name, rows)] of the window; ``unanswered``: the
    queries that failed (a window with no result at all counts one more).
    Returns (correct, compared), compared being {number: [reading, limit]}."""
    total = {"rows_off": 0, "exact_mismatch": 0, "float_gap": 0.0}
    for name, rows in results:
        one = compare_rows(rows, references[name])
        total["rows_off"] += one["rows_off"]
        total["exact_mismatch"] += one["exact_mismatch"]
        total["float_gap"] = max(total["float_gap"], one["float_gap"])
    compared = {
        "unanswered": [unanswered + (0 if results else 1), 0],
        "rows_off": [total["rows_off"], 0],
        "exact_mismatch": [total["exact_mismatch"], 0],
        "float_gap": [total["float_gap"], float_gap_limit],
    }
    correct = (compared["unanswered"][0] == 0
               and total["rows_off"] == 0 and total["exact_mismatch"] == 0
               and total["float_gap"] <= float_gap_limit)
    return correct, compared
