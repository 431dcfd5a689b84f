#!/usr/bin/env python3
"""The quickest proof that the engine still starts on the chip.

One process, the entry points a user calls. Default phase (one TPU chip):
TPC-H SF 1 parquet on disk -> TpuSession -> session.sql() for q1/q3/q5, each
cold and hot, each checked against the NumPy oracle; then q1 twice over
lineitem cached on the device tier (df.cache()), the second run with no byte
at a scan site; then the same SQL through session.serve() and an
EndpointClient, checked against the in-process result; then a graceful
shutdown. The DataFrame form of q18 runs when --queries names
it: with an empty compile cache the chip's compiler needs longer over q18's
programs than the 1200 s this script is given leave room for.

    python chip_smoke.py              # one chip; fails without a TPU
    python chip_smoke.py --queries q1,q3,q5,q18
    python chip_smoke.py --chips 4    # the mesh data plane only, four chips
    python chip_smoke.py --rehearse   # CPU platform, SF 0.01, never says tpu

Any phase that raises ends the script with a non-zero exit. The lines before
the last are notes for whoever reads the run (seconds, compiles, which scan
path and which Pallas kernels ran); they are not a benchmark. The last line
of a chip run is {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

READER_CONF = {
    "spark.rapids.tpu.device.eagerInit": "true",
    "spark.rapids.tpu.sql.format.parquet.reader.type": "COALESCING",
    "spark.rapids.tpu.pipeline.enabled": True,
    "spark.rapids.tpu.sql.stageFusion.enabled": True,
    # a broadcast build compiles its programs inside the wait: minutes on a
    # cold cache (q18's build side passed the default 300 s on the chip)
    "spark.rapids.tpu.sql.broadcast.timeout": 3600.0,
}
SQL_NAMES = ("q1", "q3", "q5")


def note(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _memory_stats(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {"bytes_limit": stats.get("bytes_limit"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def _native_build_note() -> None:
    """Build the C++ host libraries now, loudly, and say whether this run
    built them (a checkout holds no .so) and from which sources."""
    from spark_rapids_tpu import native
    libs = {"libtpulz4.so": "lz4.cpp", "libtpuparquet.so": "parquet_host.cpp"}
    ndir = os.path.dirname(native.__file__)
    before = {so: os.path.exists(os.path.join(ndir, so)) for so in libs}
    native.lz4_lib()
    native.parquet_lib()
    note(phase="native", built_in_this_run=[so for so, had in before.items()
                                            if not had],
         sources={so: os.path.join("spark_rapids_tpu/native", src)
                  for so, src in libs.items()})


def _pallas_note(phase: str) -> None:
    from spark_rapids_tpu.ops import pallas_kernels as PK
    note(phase=phase,
         pallas_on=sorted(k for k, why in PK.KERNELS.items() if why is None),
         pallas_off=sorted(k for k, why in PK.KERNELS.items() if why),
         pallas_traced=PK.traced())


_XLA = {"compile_seconds": 0.0, "persistent_cache_hits": 0}


def _watch_compiles() -> None:
    """Sum what JAX reports of its own compiles: seconds inside the backend
    compile (or the load from the persistent cache) and the cache's hits, so
    a cold query's seconds can be read apart from its compilation."""
    from jax import monitoring

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _XLA["compile_seconds"] += seconds

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _XLA["persistent_cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def _h2d_sites():
    """h2d bytes by metering SITE from the global movement ledger (the
    per-query collector mirror aggregates by link only)."""
    from spark_rapids_tpu.runtime import movement as MV
    out: dict = {}
    for (edge, link, site), rec in MV.snapshot().items():
        if edge == "h2d":
            out[site] = out.get(site, 0) + rec["bytes"]
    return out


def _timed_collect(spark, df):
    sites0, xla0 = _h2d_sites(), dict(_XLA)
    t0 = time.perf_counter()
    res = df.collect()
    secs = time.perf_counter() - t0
    sites = {k: v - sites0.get(k, 0) for k, v in _h2d_sites().items()
             if v - sites0.get(k, 0) > 0}
    cm = dict(spark.last_query_metrics().compile_metrics(),
              **{k: _XLA[k] - xla0[k] for k in _XLA})
    return res, secs, sites, cm


def _run_checked(spark, name, make_df, expected, phase="query",
                 runs=("cold", "hot")):
    """One query twice, both runs checked against the oracle's rows and
    against each other: (the second run's result, its h2d bytes by site)."""
    from spark_rapids_tpu.benchmarks.tpch import CHECKS
    out = {}
    for run in runs:
        res, secs, sites, cm = _timed_collect(spark, make_df())
        CHECKS[name](res.to_pylist(), expected)   # wrong answer -> raises
        out[run] = res
        note(phase=phase, query=name, run=run, seconds=secs,
             h2d_sites=sites, rows=res.num_rows, **cm)
    first, second = (out[run] for run in runs)
    if first.to_pylist() != second.to_pylist():
        raise AssertionError(
            f"{name}: {runs[1]} result differs from {runs[0]} result")
    return second, sites


def _resident_q1(spark, dfs, expected) -> None:
    """``df.cache()`` on the device tier: lineitem cached and registered as
    the view, Q1's text twice. The first run fills the cache from the scan;
    the second has to match its rows and move no host-to-device byte at a
    scan site. The view and the retained bytes go back afterwards."""
    from spark_rapids_tpu.sql.tpch_queries import SQL_QUERIES
    cached = dfs["lineitem"].cache()
    spark.create_or_replace_temp_view("lineitem", cached)
    try:
        _res, sites = _run_checked(
            spark, "q1", lambda: spark.sql(SQL_QUERIES["q1"]), expected,
            phase="resident", runs=("fill", "resident"))
        scanned = {k: v for k, v in sites.items()
                   if k.startswith("scan.") or k == "batch.from_arrow"}
        if scanned:
            raise AssertionError(
                f"q1 over the cached view moved bytes at a scan site: {scanned}")
    finally:
        spark.create_or_replace_temp_view("lineitem", dfs["lineitem"])
        cached.unpersist()


def single_chip(args) -> None:
    import jax
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.runtime.endpoint import (EndpointClient,
                                                   parse_stats_text)
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.sql.tpch_queries import SQL_QUERIES

    t0 = time.perf_counter()
    paths = tpch.generate(args.sf,
                          os.path.join(args.workdir, f"tpch_sf{args.sf}"))
    tb = tpch.load_np(paths)
    note(phase="data", sf=args.sf, seconds=time.perf_counter() - t0,
         lineitem_rows=len(tb["lineitem"]["l_orderkey"]),
         orders_rows=len(tb["orders"]["o_orderkey"]),
         customer_rows=len(tb["customer"]["c_custkey"]))

    spark = TpuSession(dict(READER_CONF))
    dfs = tpch.load(spark, paths, files_per_partition=4)  # + temp views
    queries = [q for q in ("q1", "q3", "q5", "q18") if q in args.queries]
    in_process = {}
    for name in queries:
        expected = getattr(tpch, f"np_{name}")(tb)
        if name in SQL_NAMES:
            make_df = lambda name=name: spark.sql(SQL_QUERIES[name])
        else:
            make_df = lambda name=name: tpch.QUERIES[name](dfs)
        in_process[name], _ = _run_checked(spark, name, make_df, expected)
    if "q1" in queries:
        _resident_q1(spark, dfs, tpch.np_q1(tb))
    _pallas_note("pallas.after_queries")
    note(phase="memory", **_memory_stats(jax.devices()[0]))

    # the same text through the serving endpoint; the client is a thread of
    # this process (it needs no device)
    ep = spark.serve(port=0)
    addr = ("127.0.0.1", ep.port)
    for name in (q for q in SQL_NAMES if q in queries):
        want = in_process[name].to_pylist()
        for i in range(2):
            t0 = time.perf_counter()
            cli = EndpointClient(addr, timeout_s=600)
            got = cli.submit(SQL_QUERIES[name]).to_pylist()
            if got != want:
                raise AssertionError(
                    f"endpoint {name} request {i}: streamed result differs "
                    "from the in-process result")
            note(phase="endpoint", query=name, request=i,
                 seconds=time.perf_counter() - t0,
                 cached=bool((cli.last_summary or {}).get("cached")))
    stats = parse_stats_text(EndpointClient(addr, timeout_s=60).stats())
    if not stats:
        raise AssertionError("endpoint stats request returned nothing")
    note(phase="endpoint.stats", families=len(stats))
    drain = ep.shutdown()
    note(phase="endpoint.shutdown", drain=drain)


def _rows_agree(name, got, want) -> None:
    """Mesh rows against single-device rows: exact columns identical, f64
    sums within the oracle comparers' 1e-6 (the reduction order differs)."""
    if len(got) != len(want):
        raise AssertionError(f"{name}: {len(got)} rows vs {len(want)}")
    for g, w in zip(got, want):
        for k, wv in w.items():
            gv = g[k]
            same = (abs(gv - wv) <= 1e-6 * max(1.0, abs(wv))
                    if isinstance(wv, float) else gv == wv)
            if not same:
                raise AssertionError(f"{name}: mesh row {g} vs single {w}")


def _partitions_on_their_chips(plan, devs) -> list:
    """Run the plan's leaf mesh exchanges (those over no other) until one has
    given every device rows, and see that partition d's batches lie on
    device d and no other (columnar.batch.batch_devices, as
    tests/test_mesh_placement.py does): replicated data is held everywhere,
    so "every device held data" cannot tell. Returns that one's rows a
    device."""
    from spark_rapids_tpu.columnar.batch import batch_devices

    def leaf_exchanges(node):
        below = [x for child in node.children for x in leaf_exchanges(child)]
        if below or type(node).__name__ != "MeshExchangeExec":
            return below
        return [node]

    rows = []
    for exchange in leaf_exchanges(plan):
        rows = [0] * len(devs)
        for d, dev in enumerate(devs):
            for b in exchange.execute_partition(d):
                if batch_devices(b) != {dev}:
                    raise AssertionError(
                        f"partition {d} of {exchange.args_string()} lies on "
                        f"{sorted(x.id for x in batch_devices(b))}, not on "
                        f"device {dev.id} alone")
                rows[d] += b.num_rows
        if all(rows):
            return rows
    raise AssertionError(f"no leaf exchange gave every device rows: {rows}")


def four_chips(args) -> None:
    """The mesh data plane and what it is compared with, nothing else."""
    import jax
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    from spark_rapids_tpu.session import TpuSession

    devs = jax.devices()[:4]
    idle = [_memory_stats(d)["peak_bytes_in_use"] for d in devs]
    paths = tpch.generate(args.sf,
                          os.path.join(args.workdir, f"tpch_sf{args.sf}"))
    tb = tpch.load_np(paths)
    single = TpuSession(dict(READER_CONF))
    mesh = TpuSession(dict(READER_CONF, **{
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.rapids.tpu.mesh.devices": "4"}))
    for name in (q for q in ("q18", "q3") if q in args.queries):
        expected = getattr(tpch, f"np_{name}")(tb)
        results = {}
        # the mesh session first: a run that is cut short has then said the
        # most about the path only four chips can show
        for label, spark in (("mesh", mesh), ("single", single)):
            df = tpch.QUERIES[name](tpch.load(spark, paths,
                                              files_per_partition=1))
            if label == "mesh":
                plan = TpuOverrides(spark.conf).apply(df._plan)
                tree = repr(plan)
                n_ex = tree.count("MeshExchangeExec")
                if n_ex < 3:
                    raise AssertionError(
                        f"{name}: mesh plan holds {n_ex} MeshExchangeExec, "
                        f"expected at least 3:\n{tree}")
                note(phase="mesh.placement", query=name,
                     rows_by_device=_partitions_on_their_chips(plan, devs))
            res, secs, sites, cm = _timed_collect(spark, df)
            tpch.CHECKS[name](res.to_pylist(), expected)
            results[label] = res.to_pylist()
            note(phase="mesh" if label == "mesh" else "mesh.compared_with",
                 query=name, seconds=secs, h2d_sites=sites, **cm)
            if label == "mesh":
                note(phase="mesh.devices.so_far", peak_bytes=[
                    _memory_stats(d)["peak_bytes_in_use"] for d in devs])
        _rows_agree(name, results["mesh"], results["single"])
    peaks = [_memory_stats(d)["peak_bytes_in_use"] for d in devs]
    note(phase="mesh.devices", idle_peak_bytes=idle, peak_bytes=peaks)
    if any(p is None for p in peaks):
        if not args.rehearse:
            raise AssertionError("a device reports no peak_bytes_in_use")
    elif not all(p > i for p, i in zip(peaks, idle)):
        raise AssertionError(
            f"not every mesh device held data: idle {idle}, peak {peaks}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1; 0.01 with "
                         "--rehearse)")
    ap.add_argument("--workdir", default=os.path.join(HERE,
                                                      "chip_smoke_work"))
    ap.add_argument("--queries", default=None,
                    type=lambda s: s.split(","),
                    help="default q1,q3,q5 (q18,q3 with --chips 4); q18 is "
                         "left out of the default run for its cold compile "
                         "time")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: skip the TPU assertion, small SF")
    args = ap.parse_args()
    if args.sf is None:
        args.sf = 0.01 if args.rehearse else 1.0
    if args.queries is None:
        args.queries = ["q18", "q3"] if args.chips == 4 else list(SQL_NAMES)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse:
        if device["platform"] == "tpu":
            print("--rehearse is for the CPU platform; run without it on a "
                  "chip", file=sys.stderr)
            return 2
    elif device["platform"] != "tpu":
        print(f"chip_smoke needs a TPU; JAX found {device}", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices; JAX found "
              f"{device}", file=sys.stderr)
        return 2

    from spark_rapids_tpu.runtime import compile_cache
    note(phase="start", device=device, rehearse=args.rehearse,
         chips=args.chips, compile_cache=compile_cache.enable(),
         **_memory_stats(devs[0]))
    os.makedirs(args.workdir, exist_ok=True)
    _watch_compiles()
    _native_build_note()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args)
    else:
        single_chip(args)
    note(phase="done", seconds=time.perf_counter() - t0)
    last = {"ok": True, "device": device}
    if args.rehearse:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
