"""TPU-numeric-regime correctness subset (VERDICT r3 item 2).

Runs a marked subset of the equivalence suite ON THE ACCELERATOR — cast edge
cases, Spark murmur3 hashing, float64 aggregation, join keys with
NaN/subnormals — and records the MEASURED float64-emulation divergence
(the real chip emulates f64 as f32 pairs, ~49-bit mantissa; see
docs/compatibility.md) instead of predictions.

One process, tiny shapes only (batch cap <= 2048). It fails without an
accelerator unless --dryrun-cpu, which runs the same checks on the CPU
platform (ci.sh's control-flow gate; not a statement about the chip).

Usage: python tools/tpu_correctness.py [--out TPU_CORRECTNESS.json]
Exit 0 and writes the artifact when every check passes; exit 1 otherwise.
"""

import argparse
import json
import os
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def run_checks(dryrun_cpu: bool) -> dict:
    import numpy as np
    import jax
    import jax.numpy as jnp
    import pyarrow as pa
    import spark_rapids_tpu  # noqa: F401  (x64)
    from spark_rapids_tpu.session import TpuSession
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu import types as T

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not dryrun_cpu:
        sys.exit(f"tpu_correctness needs an accelerator; JAX found {dev} "
                 "(--dryrun-cpu runs the checks on the CPU platform)")
    results = {"platform": dev.platform,
               "device_kind": getattr(dev, "device_kind", "?"),
               "checks": {}}

    def record(name, ok, detail=""):
        results["checks"][name] = {"ok": bool(ok), "detail": str(detail)[:300]}
        print(f"  {'OK ' if ok else 'FAIL'} {name}: {detail}")

    spark = TpuSession()

    # 1. int64 arithmetic is exact on TPU (ints are not emulated)
    t = pa.table({"x": pa.array([2**53 + 1, -2**53 - 1, 2**62, -(2**62)],
                                pa.int64())})
    got = spark.create_dataframe(t).select(
        (F.col("x") + 1).alias("y")).collect().column("y").to_pylist()
    exp = [2**53 + 2, -2**53, 2**62 + 1, -(2**62) + 1]
    record("int64_exact", got == exp, f"{got} vs {exp}")

    # 2. Spark murmur3 hash — bit-exact integers end-to-end
    t = pa.table({"k": pa.array([0, 1, -1, 2**31 - 1, None], pa.int32()),
                  "s": pa.array(["", "a", "spark", "é中", None])})
    df = spark.create_dataframe(t).select(
        F.hash(F.col("k")).alias("hk"), F.hash(F.col("s")).alias("hs"))
    got = df.collect()
    exp = df.collect_host()
    record("murmur3_bit_exact", got.equals(exp),
           f"{got.to_pylist()} vs {exp.to_pylist()}")

    # 3. cast edge cases: float->int truncation + JVM saturation + NaN->0
    t = pa.table({"f": pa.array([1.9, -1.9, 3e19, -3e19, float("nan")])})
    got = spark.create_dataframe(t).select(
        F.cast(F.col("f"), T.LONG).alias("i")).collect().column(
        "i").to_pylist()
    exp = [1, -1, 9223372036854775807, -9223372036854775808, 0]
    record("cast_double_to_long_edges", got == exp, f"{got} vs {exp}")

    # 4. float64 aggregation divergence (the emulated-f64 measurement)
    rng = np.random.default_rng(7)
    vals = rng.uniform(-1e6, 1e6, 1500)
    t = pa.table({"g": pa.array((np.arange(1500) % 7).astype(np.int64)),
                  "v": pa.array(vals)})
    df = (spark.create_dataframe(t).group_by(F.col("g"))
          .agg(F.sum(F.col("v")).alias("s"), F.avg(F.col("v")).alias("a")))
    got = {r["g"]: (r["s"], r["a"]) for r in df.collect().to_pylist()}
    host = {}
    for g in range(7):
        sel = vals[np.arange(1500) % 7 == g]
        host[g] = (sel.sum(), sel.mean())
    max_ulps = 0.0
    for g in range(7):
        for a, b in zip(got[g], host[g]):
            ulp = abs(a - b) / max(np.spacing(abs(b)), 5e-324)
            max_ulps = max(max_ulps, ulp)
    # f64-emulation (~49-bit mantissa) can diverge ~2^4 ulps on summation
    results["f64_sum_max_ulps_vs_host"] = max_ulps
    record("f64_aggregation_divergence", max_ulps < 1e6,
           f"max {max_ulps:.1f} ulps vs host numpy")

    # 5. join keys with NaN / subnormal / -0.0 (Spark: NaN==NaN, -0.0==0.0;
    #    subnormals flush to zero on TPU — measure whether they still match)
    sub = 5e-324
    lt = pa.table({"k": pa.array([float("nan"), -0.0, sub, 1.0]),
                   "lv": pa.array([0, 1, 2, 3], pa.int32())})
    rt = pa.table({"k2": pa.array([float("nan"), 0.0, sub]),
                   "rv": pa.array([10, 11, 12], pa.int32())})
    from spark_rapids_tpu.plan import nodes as NN
    from spark_rapids_tpu.expr import core as EE
    from spark_rapids_tpu.session import DataFrame
    jn = NN.JoinNode(spark.create_dataframe(lt)._plan,
                     spark.create_dataframe(rt)._plan,
                     [EE.col("k")], [EE.col("k2")], "inner", None)
    got = sorted((r["lv"], r["rv"])
                 for r in DataFrame(jn, spark).collect().to_pylist())
    # hard Spark semantics: NaN==NaN and -0.0==0.0 match; 1.0 matches nothing
    core_ok = ((0, 10) in got and (1, 11) in got
               and not any(lv == 3 for lv, _ in got))
    # subnormal handling is a MEASUREMENT (the device join key path may
    # quantize 5e-324 to 0.0; on TPU subnormals flush in hardware)
    sub_matches_zero = (2, 11) in got
    results["join_subnormal_matches_zero"] = sub_matches_zero
    record("join_nan_negzero_core", core_ok,
           f"{got} (subnormal==0.0: {sub_matches_zero})")

    # 6. TPC-DS q3 end-to-end tiny on the accelerator vs host oracle
    from spark_rapids_tpu.benchmarks import tpcds
    paths = tpcds.generate(0.003, "/tmp/tpcds_tpu_sf0.003")
    dfs = tpcds.load(spark, paths)
    tb = tpcds.load_np(paths)
    got = [tuple(r.values()) for r in tpcds.QUERIES["q3"](dfs)
           .collect().to_pylist()]
    exp = [tuple(r) for r in tpcds.NP_QUERIES["q3"](tb)]
    try:
        tpcds.check_rows(got, exp, tpcds.FLOAT_COLS["q3"], rel=1e-6)
        record("tpcds_q3_end_to_end", True, f"{len(got)} rows, rel 1e-6")
    except AssertionError as e:
        record("tpcds_q3_end_to_end", False, e)

    # 7. round-5 SQL surfaces, tiny + bounded: set operations (null-safe
    #    semi/anti + row_number ALL forms), the general multi-DISTINCT
    #    Expand rewrite, grouping sets, and exact decimal multiply/divide —
    #    each device result vs the host interpreter
    t = pa.table({"x": pa.array([1, 1, 2, 3, None, None], pa.int64()),
                  "y": pa.array(["a", "a", "b", "c", "d", None])})
    spark.create_or_replace_temp_view("r5a", spark.create_dataframe(t))
    t2 = pa.table({"x": pa.array([1, 2, 2, None, 5], pa.int64()),
                   "y": pa.array(["a", "b", "b", None, "e"])})
    spark.create_or_replace_temp_view("r5b", spark.create_dataframe(t2))
    r5 = [
        "select x, y from r5a intersect select x, y from r5b",
        "select x, y from r5a except select x, y from r5b",
        "select x, y from r5a intersect all select x, y from r5b",
        "select x, y from r5a except all select x, y from r5b",
        "select count(distinct x) cx, count(distinct y) cy, sum(x) s "
        "from r5a",
        "select y, count(distinct x) c from r5a group by rollup (y)",
        "select cast(1 as decimal(5,2)) / cast(3 as decimal(5,2)) v, "
        "cast(1.5 as decimal(5,2)) * cast(2.5 as decimal(5,2)) w",
    ]
    ok_all, detail = True, []
    for q in r5:
        # per-statement try: one device-side failure must record a FAIL,
        # not abort the child and lose checks 1-6's measurements
        try:
            df = spark.sql(q)
            g = sorted((tuple(r.values())
                        for r in df.collect().to_pylist()), key=repr)
            e = sorted((tuple(r.values())
                        for r in df.collect_host().to_pylist()), key=repr)
            if g != e:
                ok_all = False
                detail.append(f"{q[:40]}: {g} vs {e}")
        except Exception as exc:  # noqa: BLE001
            ok_all = False
            detail.append(f"{q[:40]}: {exc!r:.120}")
    record("r5_setops_distinct_decimal", ok_all,
           "; ".join(detail) if detail else f"{len(r5)} statements match")

    results["ok"] = all(c["ok"] for c in results["checks"].values())
    print(json.dumps(results))
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(REPO / "TPU_CORRECTNESS.json"))
    ap.add_argument("--dryrun-cpu", action="store_true",
                    help="CI gate: run the same checks on the CPU platform, "
                         "so an import or API regression never meets the "
                         "chip first")
    args = ap.parse_args()
    if args.dryrun_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"      # before JAX is imported
    sys.path.insert(0, str(REPO))
    results = run_checks(args.dryrun_cpu)
    pathlib.Path(args.out).write_text(json.dumps(results, indent=1))
    sys.exit(0 if results["ok"] else 1)


if __name__ == "__main__":
    main()
