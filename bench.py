"""Benchmark: TPC-H q1/q3/q5 end-to-end through the session API.

BASELINE.md config-2 (TPC-H SF0.1+ scan+filter+agg+join on one TPU VM),
replacing round-1's synthetic fused stage. Each query runs end-to-end
(parquet scan → device pipeline → collect) and is first CHECKED against an
independent single-core NumPy oracle (benchmarks/tpch.py) — a wrong answer
reports value 0 rather than a throughput. Prints ONE JSON line:

  value       = geomean over q1/q3/q5 of (lineitem rows / hot-run seconds), Mrows/s
  vs_baseline = geomean over queries of (numpy oracle E2E time / hot-run time),
                where the oracle re-reads the query's parquet tables per run —
                both sides pay the scan (VERDICT r4 next #2: the old preloaded-
                array oracle capped q3/q5 at the decode floor). The reference's
                own claim is 3x-7x vs CPU Spark, docs/FAQ.md:82-88.
  vs_baseline_compute = the round-4-and-earlier denominator (oracle computes on
                preloaded arrays; engine still pays its scan), kept one round
                for continuity.

One process: the run needs an accelerator and exits non-zero without one, or
on any failure. `--dryrun-cpu` lets the same code run on the CPU platform at a
tiny scale (ci.sh's control-flow check); the line names the device it ran on,
and a CPU line is never a device measurement.
"""

import json
import math
import os
import statistics
import subprocess
import sys
import time

TPCH_SF = float(os.environ.get("TPCH_SF", "0.1"))
DATA_DIR = os.environ.get("TPCH_DIR", f"/tmp/tpch_sf{TPCH_SF}")
# statistically honest measurement (VERDICT r5 weak #1: run-to-run variance
# was comparable to a round's progress): every timed section runs BENCH_REPS
# times, the metric is the MEDIAN, and the relative spread (max-min)/median
# is reported per query; a spread past BENCH_MAX_SPREAD marks the line
# degraded so a noisy box can't mint a quiet number
BENCH_REPS = int(os.environ.get("BENCH_REPS", "5"))
BENCH_MAX_SPREAD = float(os.environ.get("BENCH_MAX_SPREAD", "0.5"))

def _check_q1(got, exp):
    assert len(got) == len(exp), (len(got), len(exp))
    for g_, e in zip(got, exp):
        g = list(g_.values())
        assert g[0] == e[0] and g[1] == e[1], (g, e)
        for a, b in zip(g[2:], e[2:]):
            assert abs(a - b) <= 1e-6 * max(1.0, abs(b)), (g, e)


def _check_q3(got, exp):
    assert len(got) == len(exp), (len(got), len(exp))
    for g, (k, d, p, rev) in zip(got, exp):
        assert g["l_orderkey"] == k, (g, k)
        assert abs(g["revenue"] - rev) <= 1e-6 * max(1.0, abs(rev))


def _check_q5(got, exp):
    assert len(got) == len(exp), (len(got), len(exp))
    for g, (n, v) in zip(got, exp):
        assert g["n_name"] == n, (g, n)
        assert abs(g["revenue"] - v) <= 1e-6 * max(1.0, abs(v))


def _check_q18(got, exp):
    import datetime
    assert len(got) == len(exp), (len(got), len(exp))
    epoch = datetime.date(1970, 1, 1)
    for g, (c, o, d, t, s) in zip(got, exp):
        assert g["c_custkey"] == c and g["o_orderkey"] == o, (g, (c, o))
        gd = g["o_orderdate"]
        if isinstance(gd, datetime.date):
            gd = (gd - epoch).days
        assert gd == d, (gd, d)
        assert abs(g["o_totalprice"] - t) <= 1e-6 * max(1.0, abs(t))
        assert abs(g["sum_qty"] - s) <= 1e-6 * max(1.0, abs(s))


CHECKS = {"q1": _check_q1, "q3": _check_q3, "q5": _check_q5,
          "q18": _check_q18}
NP_QUERIES = {"q1": "np_q1", "q3": "np_q3", "q5": "np_q5", "q18": "np_q18"}
# (table -> columns) each query scans — the fair oracle re-reads exactly
# these per run, mirroring what the engine's COLUMN-PRUNED plan scans every
# collect() (plan/pruning.py narrows the FileScanNode the same way)
Q_TABLES = {
    "q1": {"lineitem": ["l_discount", "l_extendedprice", "l_linestatus",
                        "l_quantity", "l_returnflag", "l_shipdate", "l_tax"]},
    "q3": {"customer": ["c_custkey", "c_mktsegment"],
           "orders": ["o_custkey", "o_orderdate", "o_orderkey",
                      "o_shippriority"],
           "lineitem": ["l_discount", "l_extendedprice", "l_orderkey",
                        "l_shipdate"]},
    "q5": {"customer": ["c_custkey", "c_nationkey"],
           "orders": ["o_custkey", "o_orderdate", "o_orderkey"],
           "lineitem": ["l_discount", "l_extendedprice", "l_orderkey",
                        "l_suppkey"],
           "supplier": ["s_nationkey", "s_suppkey"],
           "nation": ["n_name", "n_nationkey", "n_regionkey"],
           "region": ["r_name", "r_regionkey"]},
    "q18": {"customer": ["c_custkey"],
            "orders": ["o_custkey", "o_orderdate", "o_orderkey",
                       "o_totalprice"],
            "lineitem": ["l_orderkey", "l_quantity"]},
}


def _h2d_sites():
    """h2d bytes by metering SITE from the global movement ledger (the
    per-query collector mirror aggregates by link only)."""
    from spark_rapids_tpu.runtime import movement as MV
    out: dict = {}
    for (edge, link, site), rec in MV.snapshot().items():
        if edge == "h2d":
            out[site] = out.get(site, 0) + rec["bytes"]
    return out


def _require_device(dryrun_cpu: bool) -> dict:
    """The device as JAX reports it. A measuring run needs an accelerator;
    only --dryrun-cpu may run on the CPU platform."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] == "cpu" and not dryrun_cpu:
        sys.exit(f"bench.py needs an accelerator; JAX found {device} "
                 "(--dryrun-cpu runs the control flow on the CPU platform)")
    return device


def bench_main(dryrun_cpu: bool = False) -> int:
    """The measured run, in this process; prints the JSON line. Returns the
    exit code: non-zero when a secondary sweep had failing queries."""
    device = _require_device(dryrun_cpu)
    from spark_rapids_tpu.runtime import compile_cache
    compile_cache.enable()
    import spark_rapids_tpu  # noqa: F401  (enables x64)
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.session import TpuSession

    paths = tpch.generate(TPCH_SF, DATA_DIR)
    # COALESCING stitches the per-partition files into few large batches —
    # fewer per-batch fixed costs; measured fastest on both backends at this
    # scale (docs/tuning.md; reference COALESCING reader role).
    # SRT_PIPELINE=0 disables the pipelined executor for A/B runs (the ci.sh
    # pipeline gate uses this switch).
    # SRT_STAGE_FUSION=0 likewise disables whole-stage fusion (the ci.sh
    # fusion gate compares dispatch counts and bit-identity across the two).
    pipeline_on = os.environ.get("SRT_PIPELINE", "1") == "1"
    fusion_on = os.environ.get("SRT_STAGE_FUSION", "1") == "1"
    spark = TpuSession({
        "spark.rapids.tpu.sql.format.parquet.reader.type": "COALESCING",
        "spark.rapids.tpu.pipeline.enabled": pipeline_on,
        "spark.rapids.tpu.sql.stageFusion.enabled": fusion_on})
    dfs = tpch.load(spark, paths, files_per_partition=4)
    tb = tpch.load_np(paths)
    n_lineitem = len(tb["lineitem"]["l_orderkey"])

    from spark_rapids_tpu.benchmarks.common import read_np

    speedups_e2e, speedups_compute, mrows = [], [], []
    per_query, spreads = {}, []
    for name, q in tpch.QUERIES.items():
        df = q(dfs)
        res = df.collect()                  # warm (compiles cached after)
        got = res.to_pylist()
        exp = getattr(tpch, NP_QUERIES[name])(tb)
        CHECKS[name](got, exp)              # wrong answer → no number
        # per-SITE h2d split (global ledger delta over the timed reps,
        # averaged back to one rep): the per-query collector mirror has
        # no site dimension, and the encoded-upload win is precisely the
        # scan.encoded-vs-scan.device split (tools/bench_compare.py)
        site0 = _h2d_sites()
        ts = []
        for _ in range(BENCH_REPS):
            t0 = time.perf_counter()
            df.collect()
            ts.append(time.perf_counter() - t0)
        site_delta = {
            k: (v - site0.get(k, 0)) // BENCH_REPS
            for k, v in _h2d_sites().items() if v - site0.get(k, 0) > 0}
        eng = statistics.median(ts)
        spread = (max(ts) - min(ts)) / eng if eng > 0 else 0.0
        # fair oracle: re-read this query's tables from parquet +
        # compute, same rep count (both sides pay the scan; OS page
        # cache is warm for both)
        np_ts = []
        for _ in range(BENCH_REPS):
            t0 = time.perf_counter()
            tb_q = {t: read_np(paths[t], columns=cols)
                    for t, cols in Q_TABLES[name].items()}
            getattr(tpch, NP_QUERIES[name])(tb_q)
            np_ts.append(time.perf_counter() - t0)
            del tb_q
        np_e2e = statistics.median(np_ts)
        # legacy denominator: oracle computes on preloaded arrays
        t0 = time.perf_counter()
        getattr(tpch, NP_QUERIES[name])(tb)
        np_compute = time.perf_counter() - t0
        speedups_e2e.append(np_e2e / eng)
        speedups_compute.append(np_compute / eng)
        mrows.append(n_lineitem / eng / 1e6)
        spreads.append(spread)
        per_query[name] = {
            "engine_s": round(eng, 4), "spread": round(spread, 3),
            "oracle_e2e_s": round(np_e2e, 4),
            "vs_baseline": round(np_e2e / eng, 3),
        }
        # per-operator attribution (query observability collector): the
        # last timed rep's self-time breakdown, so BENCH_*.json
        # trajectories are attributable to operators, not whole queries
        qm = spark.last_query_metrics()
        if qm is not None:
            # retrace denominator: the last timed rep runs hot, so a
            # healthy compile cache shows compiles == 0 here while
            # dispatches stays O(batches) (ROADMAP item 1's gate input)
            cm = qm.compile_metrics()
            per_query[name]["compiles"] = cm["compiles"]
            per_query[name]["dispatches"] = cm["dispatches"]
            ops = []
            queue_stall_ns = 0
            for n in qm.node_summaries():
                if n["id"] is None:
                    continue
                m = n["metrics"]
                self_s = m.get("selfTime", 0) / 1e9
                build_s = m.get("buildSelfTime", 0) / 1e9
                # pipeline queue stall total (consumer wait, all edges)
                queue_stall_ns += sum(
                    v for k, v in m.items()
                    if k.startswith("queueWaitTime:"))
                ops.append({"op": f"{n['name']}#{n['id']}",
                            "self_s": round(self_s, 4),
                            "rows": m.get("numOutputRows")})
                if build_s > 0:
                    ops.append({"op": f"{n['name']}#{n['id']} (build)",
                                "self_s": round(build_s, 4)})
            ops.sort(key=lambda r: -r["self_s"])
            total_self = sum(r["self_s"] for r in ops)
            per_query[name]["operators"] = ops[:8]
            per_query[name]["op_coverage"] = (
                round(total_self / qm.wall_s, 3) if qm.wall_s else None)
            per_query[name]["queue_stall_s"] = round(
                queue_stall_ns / 1e9, 4)
            # memory trajectory (allocation-site heap profiler): BENCH
            # files record the hot rep's device high-water mark and who
            # owned it, not just throughput
            msum = qm.memory or {}
            if msum:
                per_query[name]["peak_device_bytes"] = \
                    msum.get("peak_device_bytes", 0)
                msites = msum.get("sites") or {}
                if msites:
                    per_query[name]["top_alloc_site"] = max(
                        msites.items(),
                        key=lambda kv: kv[1].get("peak_bytes", 0))[0]
            # statistics plane (runtime/stats.py): how far the admission
            # estimate was from the hot rep's observed peak, and whether
            # the plan-history store primed it — trajectories of
            # estimate_error show the history store learning a workload
            stats = qm.stats or {}
            if stats.get("estimate_error") is not None:
                per_query[name]["estimate_error"] = \
                    stats["estimate_error"]
            if stats:
                per_query[name]["history_hit"] = \
                    bool(stats.get("history_hit"))
            # movement plane (runtime/movement.py): the hot rep's
            # boundary-crossing bytes by link class — BENCH trajectories
            # catch a change that silently starts moving more data, not
            # just one that slows down
            mstats = qm.movement_stats()
            if mstats:
                def _mv(pred):
                    return sum(v["bytes"] for k, v in mstats.items()
                               if pred(*k))
                total_moved = sum(v["bytes"] for v in mstats.values())
                per_query[name]["movement"] = {
                    "tcp_bytes": _mv(lambda e, lk: lk == "tcp"),
                    "loopback_bytes": _mv(
                        lambda e, lk: lk == "loopback"),
                    "h2d_bytes": _mv(lambda e, lk: e == "h2d"),
                    "d2h_bytes": _mv(lambda e, lk: e == "d2h"),
                    "spill_io_bytes": _mv(
                        lambda e, lk: e.startswith("spill.")),
                    "movement_amplification": (
                        round(total_moved / res.nbytes, 3)
                        if res.nbytes else None),
                    "h2d_sites": site_delta,
                }

    # resilience counters (retry/split/fetch-failover totals across the
    # whole ladder run): with faults disabled these must be zero — a later
    # round seeing nonzero values here caught a real robustness regression
    from spark_rapids_tpu.runtime import fuse as rfuse
    from spark_rapids_tpu.runtime import metrics as rmetrics
    resilience = rmetrics.resilience_snapshot()
    compile_totals = rfuse.stage_metrics()

    geo = lambda xs: math.exp(sum(math.log(x) for x in xs) / len(xs))
    qnames = "".join(tpch.QUERIES)
    line = {
        "metric": f"tpch_sf{TPCH_SF}_{qnames}_geomean",
        "value": round(geo(mrows), 3),
        "unit": "Mrows/s",
        "device": device,
        "vs_baseline": round(geo(speedups_e2e), 3),
        "vs_baseline_compute": round(geo(speedups_compute), 3),
        "baseline_denominator": "numpy-oracle e2e (per-query parquet re-read)",
        "reps": BENCH_REPS,
        "stat": "median",
        "pipeline": pipeline_on,
        "fusion": fusion_on,
        "spread": round(max(spreads), 3),
        "variance_ok": max(spreads) <= BENCH_MAX_SPREAD,
        "queries": per_query,
        "resilience": resilience,
        # whole-process XLA compile/dispatch totals (runtime/fuse.py);
        # per-query hot-rep deltas live in queries.<q>.compiles/dispatches
        "compiles": compile_totals["traces"],
        "dispatches": compile_totals["dispatches"],
    }
    if not line["variance_ok"]:
        line["degraded"] = (f"spread {line['spread']} exceeds "
                            f"{BENCH_MAX_SPREAD}")
    if os.environ.get("BENCH_JOIN_MICRO", "1") == "1":
        line["join_microbench"] = join_microbench(smoke=True)
    # secondary metric: the 22-query TPC-DS sweep at small scale (breadth —
    # window/decimal/basket shapes; reference qa_nightly role). A failing
    # query is recorded by name and makes the exit code non-zero. Default
    # OFF on the chip: the sweep compiles hundreds of programs.
    default_secondary = "1" if device["platform"] != "tpu" else "0"
    failed_any = False
    if os.environ.get("TPCDS_SECONDARY", default_secondary) == "1":
        from spark_rapids_tpu.benchmarks import tpcds
        from spark_rapids_tpu.sql.tpcds_queries import SQL_QUERIES
        sf = float(os.environ.get("TPCDS_SF", "0.01"))
        dpaths = tpcds.generate(sf, os.environ.get(
            "TPCDS_DIR", f"/tmp/tpcds_sf{sf}"))
        ddfs = tpcds.load(spark, dpaths)
        dtb = tpcds.load_np(dpaths)

        def sweep(metric, runs):
            """runs: [(qname, run() -> rows, oracle() -> rows, float_cols)].
            wall_s times ENGINE execution only (plan + collect); the oracle
            and the value check run off the clock. A query that raises or
            misses its oracle is recorded by name — one bad query does not
            void the others' results, and it fails the run at the end."""
            wall, results, failed = 0.0, [], []
            for qname, run, oracle, float_cols in runs:
                try:
                    t0 = time.perf_counter()
                    got = [tuple(r.values()) for r in run().to_pylist()]
                    wall += time.perf_counter() - t0
                    results.append((qname, got, oracle, float_cols))
                except Exception as e:  # noqa: BLE001 — recorded, exit != 0
                    failed.append(f"{qname}: {e!r}"[:200])
            for qname, got, oracle, float_cols in results:
                try:
                    # full value equality (exact + per-column float approx),
                    # same check as tests/test_tpcds.py
                    tpcds.check_rows(got, [tuple(r) for r in oracle()],
                                     float_cols)
                except AssertionError as e:
                    failed.append(f"{qname}: {e!r}"[:200])
            out = {"metric": metric, "queries_ok": len(runs) - len(failed),
                   "queries_total": len(runs), "check": "value-equality",
                   "wall_s": round(wall, 2)}
            if failed:
                out["failed"] = failed
            return out

        line["secondary"] = sweep(f"tpcds_sf{sf}_22q_sweep", [
            (qname, lambda q=q: q(ddfs).collect(),
             lambda qname=qname: tpcds.NP_QUERIES[qname](dtb),
             tpcds.FLOAT_COLS[qname])
            for qname, q in tpcds.QUERIES.items()])
        # the official-SQL-text suite through session.sql() — the
        # reference's qa_nightly_sql.py role, value-checked
        oracles = tpcds.sql_suite_oracles()
        line["sql_suite"] = sweep(
            f"tpcds_sf{sf}_{len(SQL_QUERIES)}q_sql_sweep", [
                (qname, lambda qname=qname: spark.sql(
                    SQL_QUERIES[qname]).collect(),
                 lambda qname=qname: oracles[qname][0](dtb),
                 oracles[qname][1])
                for qname in sorted(SQL_QUERIES, key=lambda q: int(q[1:]))])
        failed_any = bool(line["secondary"].get("failed")
                          or line["sql_suite"].get("failed"))
    print(json.dumps(line))
    return 1 if failed_any else 0


def join_microbench(smoke: bool = False):
    """Kernel-level join-spine microbench: the same unique-int-key probe
    through three formulations, value-checked against each other before any
    timing —

      - ``pallas``: hash_join_build + hash_join_probe
        (ops/pallas_kernels.py; interpret-mode off-TPU, Mosaic on chip)
      - ``searchsorted``: sorted build + two searchsorted (the engine's
        fast-path probe, exec/joins._probe_batch_fast mode "two")
      - ``laxsort_rank``: join_ranks + probe (the general rank path —
        ops/joining.py; the multi-key `lax.sort` spine)

    Median-of-reps wall per formulation, in ms. Smoke mode (ci.sh gate)
    shrinks the data so the check runs in seconds."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import spark_rapids_tpu  # noqa: F401  (x64)
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.core import Col
    from spark_rapids_tpu.ops import joining as J
    from spark_rapids_tpu.ops import pallas_kernels as PK

    n_build = 4096 if smoke else 16384
    n_stream = (1 << 14) if smoke else (1 << 20)
    reps = 3 if smoke else 5
    rng = np.random.default_rng(20260804)
    bk = rng.permutation(
        np.arange(1, 8 * n_build + 1, 8)[:n_build]).astype(np.int64)
    sk = np.concatenate([
        rng.choice(bk, n_stream // 2),
        rng.integers(0, 8 * n_build, n_stream - n_stream // 2),
    ]).astype(np.int64)
    bkj, skj = jnp.asarray(bk), jnp.asarray(sk)
    b_valid = jnp.ones((n_build,), jnp.bool_)
    H = PK.hash_join_buckets(n_build)

    # production shape (exec/joins._JoinCore): the build preps ONCE per
    # join, the probe runs per stream batch, and the rank path re-sorts
    # build+stream per batch — so prep is timed separately and the parity
    # comparison is per-batch probe cost
    @jax.jit
    def f_pallas_build(bkj):
        return PK.hash_join_build(bkj, b_valid, H)

    @jax.jit
    def f_pallas_probe(tk, tr, skj):
        pos, found = PK.hash_join_probe(tk, tr, skj, H)
        return jnp.sum(found.astype(jnp.int64)), pos, found

    @jax.jit
    def f_ss_build(bkj):
        return jax.lax.sort(bkj)

    @jax.jit
    def f_ss_probe(s, skj):
        lo = jnp.searchsorted(s, skj, side="left")
        hi = jnp.searchsorted(s, skj, side="right")
        return jnp.sum((hi - lo).astype(jnp.int64))

    @jax.jit
    def f_rank(bkj, skj):
        bcol = Col(bkj, b_valid, T.LONG)
        scol = Col(skj, jnp.ones((n_stream,), jnp.bool_), T.LONG)
        b_ranks, s_ranks = J.join_ranks([bcol], n_build, n_build,
                                        [scol], n_stream, n_stream)
        _, lo, hi = J.probe(b_ranks, s_ranks)
        return jnp.sum((hi - lo).astype(jnp.int64))

    # value check once, off the clock: all three agree on the match count,
    # and every pallas hit points at a build row holding the probed key
    tk, tr, ok = jax.block_until_ready(f_pallas_build(bkj))
    assert bool(ok), "hash build refused unique keys"
    m_pallas, pos, found = jax.block_until_ready(f_pallas_probe(tk, tr, skj))
    sorted_bk = jax.block_until_ready(f_ss_build(bkj))
    m_ss = int(f_ss_probe(sorted_bk, skj))
    m_rank = int(f_rank(bkj, skj))
    assert int(m_pallas) == m_ss == m_rank, (int(m_pallas), m_ss, m_rank)
    pos_h, found_h = np.asarray(pos), np.asarray(found)
    assert (bk[pos_h[found_h]] == sk[found_h]).all()

    def timed(f, *args):
        jax.block_until_ready(f(*args))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts) * 1000

    pallas_build_ms = timed(f_pallas_build, bkj)
    pallas_ms = timed(f_pallas_probe, tk, tr, skj)
    ss_build_ms = timed(f_ss_build, bkj)
    ss_ms = timed(f_ss_probe, sorted_bk, skj)
    rank_ms = timed(f_rank, bkj, skj)
    return {
        "metric": "join_microbench",
        "n_build": n_build, "n_stream": n_stream, "reps": reps,
        "matches": m_ss,
        "pallas_probe_ms": round(pallas_ms, 2),
        "pallas_build_ms": round(pallas_build_ms, 2),
        "searchsorted_probe_ms": round(ss_ms, 2),
        "searchsorted_build_ms": round(ss_build_ms, 2),
        "laxsort_rank_ms": round(rank_ms, 2),
        "pallas_vs_laxsort": round(rank_ms / pallas_ms, 2),
        "parity_ok": pallas_ms <= rank_ms,
    }


def _latency_percentiles():
    """p50/p95/p99 end-to-end latency per priority class plus the admission
    queue-wait distribution, from the fixed-bucket histograms every
    completed action observes into (runtime/metrics.py; the serving STATS
    endpoint exposes the same families)."""
    from spark_rapids_tpu.runtime import metrics as M
    out = {}
    for name in sorted(M.histograms_snapshot()):
        if name.startswith("query.latency.priority"):
            key = "priority" + name[len("query.latency.priority"):]
        elif name == "admission.wait":
            key = "admission_wait"
        else:
            continue
        pct = M.histogram_percentiles(name)
        if pct is not None:
            out[key] = pct
    return out


def concurrent_bench(n: int, query: str = "q18", reps: int = 2,
                     endpoint: bool = False, replicas: int = 1):
    """Multi-tenant aggregate-throughput mode (``--concurrent N``): N copies
    of one TPC-H query run back-to-back (sequential) and then fanned out on
    N threads through the driver-side QueryScheduler (concurrent), value-
    checked and bit-identity-checked against each other. Prints one JSON
    line with the aggregate throughput ratio plus per-query isolation
    evidence: every query's SCOPED resilience counters (all zero with no
    faults — a peer's retries can no longer leak into another query's
    scope) and its distinct query id. On <2 cores the measurement still
    runs but the line carries ``gate_skipped`` so ci.sh can skip its
    >=1.2x assertion with the reason logged.

    ``--endpoint`` routes every submission through the Arrow-over-TCP
    serving endpoint (runtime/endpoint.py) instead of in-process collects:
    each worker is a real EndpointClient speaking SQL over a socket, the
    per-query isolation evidence comes from the wire's summary frame, and
    the line additionally embeds the process-wide resilience snapshot
    (ci.sh asserts it all-zero — serving through the front door with no
    faults must be invisible to every recovery ladder). Endpoint mode uses
    the official SQL text, so the query must be one of q1/q3/q5."""
    import threading
    from spark_rapids_tpu.runtime import compile_cache
    compile_cache.enable()
    import spark_rapids_tpu  # noqa: F401  (enables x64)
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.session import TpuSession

    cores = os.cpu_count() or 1
    paths = tpch.generate(TPCH_SF, DATA_DIR)
    conf = {
        "spark.rapids.tpu.sql.format.parquet.reader.type": "COALESCING",
        "spark.rapids.tpu.pipeline.enabled": True,
        "spark.rapids.tpu.scheduler.maxConcurrent": n,
    }
    spark = TpuSession(conf)

    if endpoint:
        return _endpoint_concurrent_bench(spark, paths, n, query, reps, cores,
                                          replicas=replicas)

    def build_df():
        dfs = tpch.load(spark, paths, files_per_partition=4)
        return getattr(tpch, query)(dfs)

    warm = build_df()
    baseline = warm.collect().to_pylist()    # warm: compiles cached after

    # sequential: n runs back to back, per-rep median
    seq_ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n):
            rows = build_df().collect().to_pylist()
            assert rows == baseline, "sequential run diverged"
        seq_ts.append(time.perf_counter() - t0)
    sequential_s = statistics.median(seq_ts)

    # concurrent: n threads, each its own DataFrame (own collector), one
    # barrier start; wall = slowest finisher
    def run_concurrent():
        results = [None] * n
        errors = []
        barrier = threading.Barrier(n + 1)

        def worker(i):
            df = build_df()
            try:
                barrier.wait()
                rows = df.collect().to_pylist()
                qm = df._last_collector
                results[i] = {
                    "query_id": qm.query_id,
                    "wall_s": round(qm.wall_s, 4),
                    "rows_ok": rows == baseline,
                    "resilience_nonzero": {
                        k: v for k, v in qm.query_resilience().items() if v},
                }
            except BaseException as e:  # noqa: BLE001
                errors.append(repr(e)[:200])

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(n)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, results, errors

    conc_ts, results, errors = [], None, None
    for _ in range(reps):
        wall, results, errors = run_concurrent()
        if errors:
            break
        conc_ts.append(wall)
    concurrent_s = statistics.median(conc_ts) if conc_ts else 0.0

    line = {
        "metric": f"tpch_sf{TPCH_SF}_{query}_concurrent{n}",
        "n": n, "query": query, "reps": reps, "cores": cores,
        "sequential_s": round(sequential_s, 4),
        "concurrent_s": round(concurrent_s, 4),
        "throughput_x": (round(sequential_s / concurrent_s, 3)
                         if concurrent_s else 0.0),
        "per_query": results,
        "isolation_ok": bool(results) and all(
            r and r["rows_ok"] and not r["resilience_nonzero"]
            and len({x["query_id"] for x in results}) == n
            for r in results),
        # per-priority latency distribution across every run this process
        # made (sequential + concurrent): the serving tier's SLO numbers
        "latency": _latency_percentiles(),
    }
    if errors:
        line["errors"] = errors
    if cores < 2:
        line["gate_skipped"] = (
            f"{cores} core(s): concurrent queries cannot overlap on one "
            "core; throughput gate needs >=2")
    return line


def _endpoint_concurrent_bench(spark, paths, n, query, reps, cores,
                               replicas=1):
    """The --endpoint half of concurrent_bench: n clients over TCP."""
    import threading
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.runtime import metrics as M
    from spark_rapids_tpu.runtime.endpoint import EndpointClient
    from spark_rapids_tpu.sql.tpch_queries import SQL_QUERIES

    assert query in SQL_QUERIES, \
        f"--endpoint needs official SQL text; {query} not in {sorted(SQL_QUERIES)}"
    sql = SQL_QUERIES[query]
    tpch.load(spark, paths, files_per_partition=4)   # registers temp views
    baseline = spark.sql(sql).collect().to_pylist()  # warm + value oracle
    if replicas > 1:
        return _fleet_concurrent_bench(baseline, sql, n, query, reps, cores,
                                       replicas)
    ep = spark.serve()
    addr = ("127.0.0.1", ep.port)
    try:
        # sequential: n wire submissions back to back, per-rep median
        seq_ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                cli = EndpointClient(addr, timeout_s=300)
                rows = cli.submit(sql).to_pylist()
                assert rows == baseline, "sequential endpoint run diverged"
            seq_ts.append(time.perf_counter() - t0)
        sequential_s = statistics.median(seq_ts)

        def run_concurrent():
            results = [None] * n
            errors = []
            barrier = threading.Barrier(n + 1)

            def worker(i):
                cli = EndpointClient(addr, timeout_s=300)
                try:
                    barrier.wait()
                    rows = cli.submit(sql).to_pylist()
                    s = cli.last_summary or {}
                    results[i] = {
                        "query_id": s.get("query"),
                        "wall_s": s.get("wall_s"),
                        "rows_ok": rows == baseline,
                        "resilience_nonzero": s.get("resilience") or {},
                    }
                except BaseException as e:  # noqa: BLE001
                    errors.append(repr(e)[:200])

            threads = [threading.Thread(target=worker, args=(i,),
                                        daemon=True) for i in range(n)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            return time.perf_counter() - t0, results, errors

        conc_ts, results, errors = [], None, None
        for _ in range(reps):
            wall, results, errors = run_concurrent()
            if errors:
                break
            conc_ts.append(wall)
        concurrent_s = statistics.median(conc_ts) if conc_ts else 0.0
    finally:
        ep.shutdown(grace_s=5)

    line = {
        "metric": f"tpch_sf{TPCH_SF}_{query}_endpoint_concurrent{n}",
        "n": n, "query": query, "reps": reps, "cores": cores,
        "endpoint": True,
        "sequential_s": round(sequential_s, 4),
        "concurrent_s": round(concurrent_s, 4),
        "throughput_x": (round(sequential_s / concurrent_s, 3)
                         if concurrent_s else 0.0),
        "per_query": results,
        "isolation_ok": bool(results) and all(
            r and r["rows_ok"] and not r["resilience_nonzero"]
            and len({x["query_id"] for x in results}) == n
            for r in results),
        # serving with no faults must be invisible to every recovery
        # ladder — including the endpoint's own disconnect counter
        "resilience": M.resilience_snapshot(),
        "latency": _latency_percentiles(),
    }
    if errors:
        line["errors"] = errors
    if cores < 2:
        line["gate_skipped"] = (
            f"{cores} core(s): concurrent queries cannot overlap on one "
            "core; throughput gate needs >=2")
    return line


def _fleet_concurrent_bench(baseline, sql, n, query, reps, cores, replicas):
    """The --replicas R half of endpoint mode: R real replica PROCESSES
    (tools/fleet_replica.py) registered in one fleet directory and sharing
    one compiled-stage cache — replica 0 compiles the workload, the rest
    replay its shapes warm. Sequential = n wire submissions through ONE
    replica; concurrent = n clients fanned across the fleet, worker i
    leading with replica i %% R and carrying the rest as its failover
    chain. The line embeds the client-side resilience snapshot (with no
    faults, spreading load across replicas must count ZERO failovers) plus
    the serving-latency trajectory: per-replica journey counts
    (served/failover/cached) and client-observed fleet p50/p95/p99."""
    import signal
    import threading
    import jax
    from spark_rapids_tpu.runtime import metrics as M
    from spark_rapids_tpu.runtime.endpoint import EndpointClient

    # the replicas are CPU processes (a chip belongs to one process, and this
    # parent already holds JAX's device): timing them under an accelerator
    # parent would report CPU replicas as if the chip had served them. A
    # fleet of one-chip replicas needs a parent that stays off JAX.
    if jax.devices()[0].platform != "cpu":
        sys.exit("bench.py --replicas starts CPU replica processes; run it "
                 "with JAX_PLATFORMS=cpu")

    work = f"/tmp/srt_fleet_bench_{os.getpid()}"
    fleet_dir = os.path.join(work, "fleet")
    cache_dir = os.path.join(work, "stage_cache")
    for d in (fleet_dir, cache_dir):
        os.makedirs(d, exist_ok=True)
    repl_script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "tools", "fleet_replica.py")
    procs, addrs = [], []
    try:
        for r in range(replicas):
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            proc = subprocess.Popen(
                [sys.executable, repl_script,
                 "--fleet-dir", fleet_dir,
                 "--data-dir", DATA_DIR, "--sf", str(TPCH_SF),
                 "--stage-cache-dir", cache_dir,
                 # generous lease: a GIL stall during a compile burst must
                 # not expire a LIVE replica mid-benchmark
                 "--lease-timeout", "10", "--heartbeat", "1",
                 "--max-concurrent", str(n)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env)
            port = None
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                ln = proc.stdout.readline()
                if ln.startswith("READY "):
                    port = int(ln.split()[1])
                    break
                if proc.poll() is not None:
                    break
            assert port is not None, f"fleet replica {r} never became READY"
            threading.Thread(target=proc.stdout.read, daemon=True).start()
            procs.append(proc)
            addrs.append(("127.0.0.1", port))

        # warm each replica once; replica 0 compiles into the shared stage
        # cache first, so the rest start from its compiled shapes
        for a in addrs:
            rows = EndpointClient(a, timeout_s=600).submit(sql).to_pylist()
            assert rows == baseline, "fleet replica warm-up diverged"

        # sequential: n wire submissions back to back through one replica
        seq_ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                rows = EndpointClient(
                    addrs[0], timeout_s=600).submit(sql).to_pylist()
                assert rows == baseline, "sequential fleet run diverged"
            seq_ts.append(time.perf_counter() - t0)
        sequential_s = statistics.median(seq_ts)

        def run_concurrent():
            results = [None] * n
            errors = []
            barrier = threading.Barrier(n + 1)

            def worker(i):
                order = addrs[i % replicas:] + addrs[:i % replicas]
                cli = EndpointClient(order, timeout_s=600)
                retries = []
                try:
                    barrier.wait()
                    t0 = time.perf_counter()
                    rows = cli.submit_with_retry(
                        sql,
                        on_retry=lambda a, d: retries.append(a)).to_pylist()
                    client_s = time.perf_counter() - t0
                    s = cli.last_summary or {}
                    results[i] = {
                        "query_id": s.get("query"),
                        # the SERVING replica's identity from the summary
                        # frame (the journey plane stamps it), so failovers
                        # attribute the serve to where it actually landed
                        "replica": s.get("replica")
                        or f"{cli.address[0]}:{cli.address[1]}",
                        "journey": cli.last_journey,
                        "failovers": len(retries),
                        "cached": bool(s.get("cached")),
                        "wall_s": s.get("wall_s"),
                        "client_s": round(client_s, 4),
                        "rows_ok": rows == baseline,
                        "resilience_nonzero": s.get("resilience") or {},
                    }
                except BaseException as e:  # noqa: BLE001
                    errors.append(repr(e)[:200])

            threads = [threading.Thread(target=worker, args=(i,),
                                        daemon=True) for i in range(n)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            return time.perf_counter() - t0, results, errors

        conc_ts, results, errors, all_results = [], None, None, []
        for _ in range(reps):
            wall, results, errors = run_concurrent()
            if errors:
                break
            conc_ts.append(wall)
            all_results.extend(r for r in results if r)
        concurrent_s = statistics.median(conc_ts) if conc_ts else 0.0

        # per-replica journey counts across every rep: where each serve
        # landed, how many arrived via failover, how many were cache hits
        journeys = {}
        for r in all_results:
            d = journeys.setdefault(
                r["replica"], {"served": 0, "failover": 0, "cached": 0})
            d["cached" if r["cached"] else "served"] += 1
            d["failover"] += r["failovers"]
        lats = sorted(r["client_s"] for r in all_results
                      if r.get("client_s") is not None)

        def _pct(p):
            return (round(lats[min(len(lats) - 1,
                                   int(p / 100.0 * len(lats)))], 4)
                    if lats else None)

        fleet_latency = {"p50": _pct(50), "p95": _pct(95), "p99": _pct(99)}
    finally:
        for proc in procs:
            try:
                proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=90)
            except Exception:   # noqa: BLE001
                proc.kill()

    line = {
        "metric": f"tpch_sf{TPCH_SF}_{query}_endpoint{replicas}r_concurrent{n}",
        "n": n, "query": query, "reps": reps, "cores": cores,
        "endpoint": True, "replicas": replicas,
        "sequential_s": round(sequential_s, 4),
        "concurrent_s": round(concurrent_s, 4),
        "throughput_x": (round(sequential_s / concurrent_s, 3)
                         if concurrent_s else 0.0),
        "per_query": results,
        "isolation_ok": bool(results) and all(
            r and r["rows_ok"] and not r["resilience_nonzero"]
            and len({x["query_id"] for x in results}) == n
            for r in results),
        # CLIENT-side registry: a no-faults fleet run must count zero
        # replicaFailovers — load spreading is routing, not recovery
        "resilience": M.resilience_snapshot(),
        "latency": _latency_percentiles(),
        # serving-latency trajectory: per-replica journey outcome counts +
        # client-observed (submit -> last row) percentiles across every
        # rep — bench_compare.py diffs these between runs
        "journeys": journeys,
        "fleet_latency": fleet_latency,
    }
    if errors:
        line["errors"] = errors
    if cores < 2:
        line["gate_skipped"] = (
            f"{cores} core(s): replicas cannot overlap on one core; "
            "fleet throughput gate needs >=2")
    return line


if __name__ == "__main__":
    if "--join-micro" in sys.argv:
        # standalone kernel microbench (ci.sh smoke gate): one JSON line
        print(json.dumps(join_microbench(smoke="--smoke" in sys.argv)))
    elif "--concurrent" in sys.argv:
        # multi-tenant aggregate-throughput mode: one JSON line;
        # --endpoint routes every submission over the Arrow-over-TCP
        # serving endpoint (SQL text, so q1/q3/q5 only)
        i = sys.argv.index("--concurrent")
        n = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 else 4
        ep_mode = "--endpoint" in sys.argv
        q = (sys.argv[sys.argv.index("--query") + 1]
             if "--query" in sys.argv else ("q5" if ep_mode else "q18"))
        # --replicas R (endpoint mode only): R real replica processes
        # behind one fleet directory + shared stage cache
        r = (int(sys.argv[sys.argv.index("--replicas") + 1])
             if "--replicas" in sys.argv else 1)
        print(json.dumps(concurrent_bench(n, q, endpoint=ep_mode,
                                          replicas=r)))
    else:
        sys.exit(bench_main(dryrun_cpu="--dryrun-cpu" in sys.argv))
