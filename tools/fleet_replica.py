"""One serving-fleet replica as a standalone process.

Spawns a TpuSession + QueryEndpoint wired into a shared fleet directory
(runtime/fleet.py) and the shared warm-state stores (compiled-stage cache,
plan-history), prints ``READY <port>`` once the endpoint is listening, and
serves until SIGTERM (graceful drain) — or SIGKILL, which is the point: the
parent harness (tools/fleet_chaos.py, tests/test_fleet.py) kills replicas mid-stream to drive the failover/adoption
contracts.

Data catalog, one of:
  --data-dir DIR [--sf F]   TPC-H views from (pre-generated) parquet
  --synthetic N             one deterministic in-memory table 't'
                            (k=i%%50 int64, v=i float64, 2 partitions) —
                            identical in every replica, so results are
                            bit-identical across the fleet

Usage:
  python tools/fleet_replica.py --fleet-dir D --synthetic 200 \
      [--port 0] [--stage-cache-dir D] [--history-dir D] [--eventlog-dir D]
      [--lease-timeout 3] [--heartbeat 0.5] [--request-timeout 0]
      [--slo-target 0]
      [--max-concurrent 4] [--result-cache] [--faults SPEC [--faults-seed N]]
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="fleet_replica.py", description=__doc__)
    p.add_argument("--fleet-dir", required=True)
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--data-dir")
    p.add_argument("--sf", type=float, default=0.01)
    p.add_argument("--synthetic", type=int, default=0,
                   help="rows of the deterministic synthetic table 't'")
    p.add_argument("--stage-cache-dir")
    p.add_argument("--history-dir")
    p.add_argument("--eventlog-dir")
    p.add_argument("--lease-timeout", type=float, default=3.0)
    p.add_argument("--heartbeat", type=float, default=0.5)
    p.add_argument("--request-timeout", type=float, default=0.0)
    p.add_argument("--slo-target", type=float, default=0.0,
                   help="endpoint.slo.latencyTargetSeconds: latency SLO "
                        "accounted per served query (0 disables)")
    p.add_argument("--max-concurrent", type=int, default=4)
    p.add_argument("--result-cache", action="store_true")
    p.add_argument("--stream-source", action="append", default=[],
                   metavar="NAME:DIR",
                   help="register a streaming source (streaming/source.py) "
                        "over the shared batch-log DIR; repeatable. Clients "
                        "APPEND through any replica and query through any "
                        "other — the shared fleet catalog epoch keeps every "
                        "replica's result cache honest")
    p.add_argument("--faults", default=None,
                   help="chaos fault spec armed in THIS replica "
                        "(runtime/faults.py), e.g. slow:agg.update:8")
    p.add_argument("--faults-seed", type=int, default=3)
    p.add_argument("--drain-grace", type=float, default=30.0)
    args = p.parse_args(argv)
    if not args.data_dir and not args.synthetic:
        p.error("one of --data-dir / --synthetic is required")

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import spark_rapids_tpu  # noqa: F401  (enables x64)
    from spark_rapids_tpu.runtime import eventlog
    from spark_rapids_tpu.session import TpuSession

    conf = {
        "spark.rapids.tpu.sql.format.parquet.reader.type": "COALESCING",
        "spark.rapids.tpu.pipeline.enabled": True,
        "spark.rapids.tpu.scheduler.maxConcurrent": args.max_concurrent,
        "spark.rapids.tpu.fleet.dir": args.fleet_dir,
        "spark.rapids.tpu.fleet.lease.timeoutSeconds": args.lease_timeout,
        "spark.rapids.tpu.fleet.heartbeat.intervalSeconds": args.heartbeat,
        "spark.rapids.tpu.endpoint.requestTimeoutSeconds":
            args.request_timeout,
        "spark.rapids.tpu.endpoint.slo.latencyTargetSeconds":
            args.slo_target,
        "spark.rapids.tpu.endpoint.drain.graceSeconds": args.drain_grace,
    }
    if args.stage_cache_dir:
        conf["spark.rapids.tpu.sql.stage.cache.enabled"] = True
        conf["spark.rapids.tpu.sql.stage.cache.dir"] = args.stage_cache_dir
    if args.history_dir:
        conf["spark.rapids.tpu.stats.history.dir"] = args.history_dir
    if args.eventlog_dir:
        conf["spark.rapids.tpu.eventLog.dir"] = args.eventlog_dir
    if args.result_cache:
        conf["spark.rapids.tpu.endpoint.resultCache.enabled"] = True
    spark = TpuSession(conf)

    if args.data_dir:
        from spark_rapids_tpu.benchmarks import tpch
        paths = tpch.generate(args.sf, args.data_dir)
        tpch.load(spark, paths, files_per_partition=4)
    else:
        import pyarrow as pa
        n = args.synthetic
        tbl = pa.table({"k": pa.array([i % 50 for i in range(n)],
                                      type=pa.int64()),
                        "v": pa.array([float(i) for i in range(n)],
                                      type=pa.float64())})
        spark.create_or_replace_temp_view(
            "t", spark.create_dataframe(tbl, num_partitions=2))

    for spec in args.stream_source:
        name, _, sdir = spec.partition(":")
        if not sdir:
            p.error(f"--stream-source wants NAME:DIR, got {spec!r}")
        spark.create_stream_source(name, sdir)

    if args.faults:
        from spark_rapids_tpu.runtime import faults
        faults.configure(args.faults, seed=args.faults_seed)

    ep = spark.serve(host=args.host, port=args.port)
    ep.install_signal_handlers(grace_s=args.drain_grace)
    print(f"READY {ep.port}", flush=True)
    # serve until the SIGTERM drain closes the listener (SIGKILL never
    # reaches this loop — that replica's lease expires and a peer adopts it)
    while ep._thread.is_alive():
        time.sleep(0.1)
    eventlog.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
