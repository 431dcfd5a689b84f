"""``correct`` has to come out false for the float32 control and for a timed
path that is broken underneath, and true for a sound run.

Small scale, CPU platform: the harness's look for a chip is skipped
(``rehearsal=True``) and everything after it is the code of a real run. The
same control at the cells' own scale is ``python3 benchmark/control.py``.
"""

import copy

import pytest

from benchmark import compare, control, run

SCALE = 0.01
SEED = 2**31 + 77
CELLS = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.fixture
def workdir(tmp_path_factory):
    return str(tmp_path_factory.getbasetemp() / "benchmark_work")


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_is_not_correct(cell, workdir):
    refs, controls, limit = control.control_rows(cell, SEED, SCALE, workdir)
    correct, compared = compare.compare_all(list(controls.items()), refs,
                                            limit, 0)
    assert not correct, compared
    # it is the float gap that catches it, by a wide margin
    assert compared["float_gap"][0] > 3 * limit, compared
    # and the reference against itself is exact
    assert compare.compare_all(list(refs.items()), refs, limit, 0)[0]


def _nudge_float(rows):
    for k, v in rows[0].items():
        if isinstance(v, float):
            rows[0][k] = v * (1 + 1e-7)
            return rows
    raise AssertionError("no float column to alter")


def _alter_key(rows):
    for k, v in rows[-1].items():
        if isinstance(v, int):
            rows[-1][k] = v + 1
            return rows
    for k, v in rows[-1].items():
        if isinstance(v, str):
            rows[-1][k] = v + "x"
            return rows
    raise AssertionError("no exact column to alter")


def _drop_row(rows):
    return rows[:-1]


def _half_the_input(rows):
    # what leaving half of the batches out does to every sum
    return [{k: v / 2 if isinstance(v, float) else v for k, v in r.items()}
            for r in rows]


FAULTS = {"float_nudged_1e-7": _nudge_float, "key_altered": _alter_key,
          "row_dropped": _drop_row, "half_the_input": _half_the_input}


def _break(monkeypatch, entry_cls, fault):
    sound = entry_cls.run
    calls = {"n": 0}

    def broken(self, query):
        rows, spans = sound(self, query)
        calls["n"] += 1
        # the warm-up is the first call; break one answer of the window only
        if calls["n"] == 3:
            rows = fault(copy.deepcopy(rows))
        return rows, spans
    monkeypatch.setattr(entry_cls, "run", broken)


@pytest.mark.parametrize("cell", ["tpch_sf1_batch.q1", "tpch_sf1_served.q3x2"])
def test_sound_run_is_correct(cell, workdir):
    r = run.run_cell(cell, SEED, 1.0, False, rehearsal=True, scale=SCALE,
                     workdir=workdir)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 3, r
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["tpch_sf1_batch.q1", "tpch_sf1_batch.q5",
                                  "tpch_sf1_served.q3x2"])
def test_altered_answer_is_not_correct(cell, fault, workdir, monkeypatch):
    cls = run.ENTRIES[run.find_cell(cell)[2]["entry"]]
    _break(monkeypatch, cls, FAULTS[fault])
    r = run.run_cell(cell, SEED, 1.0, False, rehearsal=True, scale=SCALE,
                     workdir=workdir)
    assert r["attempted"] >= 3 and not r["correct"], r


def test_query_that_raises_is_not_correct(workdir, monkeypatch):
    def broken(rows):
        raise RuntimeError("planted")
    _break(monkeypatch, run.SqlEntry, broken)
    r = run.run_cell("tpch_sf1_batch.q3", SEED, 1.0, False, rehearsal=True,
                     scale=SCALE, workdir=workdir)
    assert r["failed"] == 1 and not r["correct"], r
    assert r["compared"]["unanswered"] == [1, 0]


def test_cached_reply_counts_as_failed(workdir, monkeypatch):
    from spark_rapids_tpu.runtime.endpoint import EndpointClient
    sound = EndpointClient.submit

    def cached(self, *a, **kw):
        out = sound(self, *a, **kw)
        self.last_summary = dict(self.last_summary or {}, cached=True)
        return out
    monkeypatch.setattr(EndpointClient, "submit", cached)
    with pytest.raises(RuntimeError, match="result cache"):
        # the warm-up already refuses it: no window opens on a cache
        run.run_cell("tpch_sf1_served.q3x2", SEED, 1.0, False, rehearsal=True,
                     scale=SCALE, workdir=workdir)
