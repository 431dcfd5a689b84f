#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the chip it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name:
BENCHMARK.json names them, and the files under benchmark/ hold them (see
benchmark/README.md). Set-up makes the data from the seed anew, opens the session,
and runs each text of the cell once (compile, or load from the persistent
cache). Then the window: closed-loop clients drive the configuration's entry
point for ``--seconds``; no query starts after that, those in flight finish
and count, and the window closes at the last completion. After the window
every result it returned is compared with the plain reference.

Lines before the last are notes for whoever reads a failed run. The last line
of standard output is the result. Without a TPU, or with fewer chips than the
cell asks for, the exit code is 2 and no result is printed.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse
import contextlib
import gc
import importlib
import itertools
import json
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKDIR = os.path.join(ROOT, "benchmark_work")
CLIENT_TIMEOUT_S = 900.0


def note(**kw) -> None:
    print(json.dumps(kw, default=str), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_config(path: str) -> dict:
    """A configuration's file, laid over the file it names under ``shared``
    (``configs/shared/<name>.json``) if it names one; a dict of both is
    merged one level deep."""
    config = load_json(ROOT, path)
    if "shared" not in config:
        return config
    merged = load_json(HERE, "configs", "shared", config["shared"] + ".json")
    for k, v in config.items():
        if isinstance(v, dict) and isinstance(merged.get(k), dict):
            v = {**merged[k], **v}
        merged[k] = v
    return merged


def find_cell(name: str) -> tuple:
    """(cell, config entry, config file, traffic, whole BENCHMARK.json)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_config(entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if traffic.get("loop") != "closed":
        raise SystemExit(
            f"traffic {cell['traffic']!r} asks for loop "
            f"{traffic.get('loop')!r}: only the closed loop has a generator "
            "yet (an open loop needs a rate the system sustains, found by a "
            "sweep, and a window that holds some hundreds of requests)")
    return cell, entry, config, traffic, bench


def device_or_exit(chips: int, rehearsal: bool) -> dict:
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearsal:
        if device["platform"] != "cpu":
            print("a rehearsal is for the CPU platform", file=sys.stderr)
            raise SystemExit(2)
    elif device["platform"] != "tpu" or len(devs) < chips:
        print(f"the cell needs {chips} TPU chip(s); JAX found {device}",
              file=sys.stderr)
        raise SystemExit(2)
    return device


class XlaWatch:
    """What JAX reports of its own compiles: backend compiles (or loads from
    the persistent cache), their seconds, and the cache's hits."""

    def __init__(self):
        from jax import monitoring
        self.counts = {"backend_compiles": 0, "compile_seconds": 0.0,
                       "persistent_cache_hits": 0}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1
            self.counts["compile_seconds"] += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["persistent_cache_hits"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def h2d_by_site() -> dict:
    from spark_rapids_tpu.runtime import movement
    out: dict = {}
    for (edge, _link, site), rec in movement.snapshot().items():
        if edge == "h2d":
            out[site] = out.get(site, 0) + rec["bytes"]
    return out


def counters(xla: XlaWatch) -> dict:
    from spark_rapids_tpu.ops import pallas_kernels
    from spark_rapids_tpu.runtime import fuse
    return {"h2d_by_site": h2d_by_site(), "fuse": fuse.stage_metrics(),
            "xla": xla.snapshot(), "pallas_traced": pallas_kernels.traced()}


class SqlEntry:
    """``session.sql(text).collect()``, or a DataFrame built from the views
    where the query comes as ``queries/<name>.py``."""

    def __init__(self, session, views):
        self.session, self.views = session, views

    def run(self, query: dict) -> tuple:
        t0 = time.perf_counter()
        if query["build"] is not None:
            df = query["build"](self.views)
        else:
            df = self.session.sql(query["text"])
        lower_s = time.perf_counter() - t0
        return df.collect().to_pylist(), {"sql_lower_s": lower_s}

    def stats_text(self):
        return None

    def close(self):
        return None


class EndpointEntry:
    """``EndpointClient.submit(text)`` against ``session.serve()``; a client
    is a thread of this process, since the chip belongs to one process."""

    def __init__(self, session, views):
        from spark_rapids_tpu.runtime.endpoint import EndpointClient
        self._client = EndpointClient
        self.endpoint = session.serve(port=0)
        self.address = ("127.0.0.1", self.endpoint.port)

    def run(self, query: dict) -> tuple:
        if query["build"] is not None:
            raise ValueError(f"query {query['name']} has no text to submit")
        cli = self._client(self.address, timeout_s=CLIENT_TIMEOUT_S)
        rows = cli.submit(query["text"]).to_pylist()
        if (cli.last_summary or {}).get("cached"):
            raise RuntimeError("the reply came from a result cache; the cell "
                               "measures the engine")
        return rows, {}

    def stats_text(self):
        return self._client(self.address, timeout_s=60).stats()

    def close(self):
        return self.endpoint.shutdown()


ENTRIES = {"sql": SqlEntry, "endpoint": EndpointEntry}


def load_query(name: str) -> dict:
    sql = os.path.join(HERE, "queries", name + ".sql")
    if os.path.exists(sql):
        with open(sql) as f:
            return {"name": name, "text": f.read(), "build": None}
    mod = importlib.import_module(f"benchmark.queries.{name}")
    with open(mod.__file__) as f:
        return {"name": name, "text": f.read(), "build": mod.dataframe}


def rotation(traffic: dict, client: int, seed: int):
    """The client's endless order of query names: the traffic's list, each
    client starting at a place of its own drawn from the seed."""
    names = traffic["queries"]
    return itertools.islice(itertools.cycle(names),
                            (seed + client * 7919) % len(names), None)


class Window:
    """Closed-loop clients against one entry for ``seconds``."""

    def __init__(self, entry, queries, traffic, seed, seconds, trace_dir):
        self.entry, self.queries, self.traffic = entry, queries, traffic
        self.seed, self.seconds, self.trace_dir = seed, seconds, trace_dir
        self.done: list = []       # one dict a finished query
        self.failures: list = []
        self.attempted = 0
        self.traced_span = None    # (start, end) on this window's clock
        self._lock = threading.Lock()

    def _one(self, client: int, name: str, traced: bool) -> bool:
        import jax
        from benchmark.trace_reduce import WINDOW
        with self._lock:
            self.attempted += 1
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        t0 = time.perf_counter()
        try:
            with (jax.profiler.TraceAnnotation(WINDOW) if traced
                  else contextlib.nullcontext()):
                rows, spans = self.entry.run(self.queries[name])
        except Exception as e:   # noqa: BLE001 - a failed query is counted
            with self._lock:
                self.failures.append(f"client {client} {name}: "
                                     f"{type(e).__name__}: {e}"[:2000])
            return False
        finally:
            t1 = time.perf_counter()
            if traced:
                self.traced_span = (t0 - self.t_open, t1 - self.t_open)
                jax.profiler.stop_trace()
        with self._lock:
            self.done.append({"client": client, "query": name, "rows": rows,
                              "start": t0 - self.t_open,
                              "end": t1 - self.t_open, **spans})
        return True

    def _client(self, client: int) -> None:
        order = rotation(self.traffic, client, self.seed)
        traced = self.trace_dir is not None and client == 0
        while time.perf_counter() - self.t_open < self.seconds:
            ok = self._one(client, next(order), traced)
            traced = False
            if not ok:
                break

    def run(self) -> None:
        n = int(self.traffic["clients"])
        threads = [threading.Thread(target=self._client, args=(c,),
                                    name=f"client-{c}") for c in range(n)]
        self.t_open = time.perf_counter()
        self.t_open_wall = time.time()
        for t in threads[1:]:
            t.start()
        if self.trace_dir is not None and n > 1:
            # the trace covers client 0's first query; the other clients'
            # queries are in flight when it opens
            time.sleep(0.5)
        threads[0].start()
        for t in threads:
            t.join()
        self.length = max((d["end"] for d in self.done), default=0.0)


def read_tables(paths: dict, columns: dict) -> dict:
    """{table: {column: numpy array}} through pyarrow; dates as epoch days."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    out = {}
    for table, cols in columns.items():
        t = pq.read_table(paths[table], columns=cols)
        out[table] = {}
        for c in t.column_names:
            col = t.column(c)
            if pa.types.is_date32(col.type):
                col = col.cast(pa.int32())
            out[table][c] = col.to_numpy(zero_copy_only=False)
    return out


def references_for(names, paths: dict, **kw) -> dict:
    """{query: the plain reference's rows}; ``dtype=`` for the control."""
    refs = {}
    for name in sorted(set(names)):
        mod = importlib.import_module(f"benchmark.reference.{name}")
        refs[name] = mod.reference(read_tables(paths, mod.COLUMNS), **kw)
    return refs


def read_metrics(kind: str, bench: dict, cell: str, ctx: dict) -> dict:
    """Each metric of ``bench[kind]`` that lists this cell (or lists none)
    through its reader ``benchmark/metrics_<kind>/<name>.py``; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench[kind]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        mod = importlib.import_module(
            f"benchmark.metrics_{kind}.{m['name']}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: bool = False, scale=None, workdir: str = WORKDIR,
             t_start: float = None) -> dict:
    t_start = time.time() if t_start is None else t_start
    cell, _entry, config, traffic, bench = find_cell(cell_name)
    device = device_or_exit(int(cell["chips"]), rehearsal)
    import jax
    from spark_rapids_tpu import native
    from spark_rapids_tpu.ops import pallas_kernels
    from spark_rapids_tpu.runtime import compile_cache
    from spark_rapids_tpu.session import TpuSession
    from benchmark import compare, query_bytes, trace_reduce
    generator = importlib.import_module(
        f"benchmark.datagen.{config['generator']}")

    peaks = load_json(HERE, "peaks.json").get(device["kind"])
    if peaks is None and not rehearsal:
        raise SystemExit(f"device kind {device['kind']!r} is not in "
                         "benchmark/peaks.json")
    sf = config["scale_factor"] if scale is None else scale
    note(phase="start", cell=cell_name, seed=seed, seconds=seconds,
         trace=trace, device=device, scale_factor=sf,
         compile_cache=compile_cache.enable())
    xla = XlaWatch()
    t0 = time.perf_counter()
    native.lz4_lib()
    native.parquet_lib()
    t_native = time.perf_counter() - t0
    os.makedirs(workdir, exist_ok=True)
    t0 = time.perf_counter()
    paths = generator.generate(sf, seed, config["tables"], workdir)
    footers = query_bytes.table_footers(paths)
    note(phase="data", native_build_seconds=t_native,
         seconds=time.perf_counter() - t0,
         rows={t: sum(md.num_rows for md in mds)
               for t, mds in footers.items()})

    conf = dict(config["session_conf"])
    if trace:
        conf.update(config.get("trace_conf", {}))
    session = TpuSession(conf)
    views = {}
    for table, path in paths.items():
        views[table] = session.read_parquet(
            path, files_per_partition=config["files_per_partition"])
        session.create_or_replace_temp_view(table, views[table])
    entry = ENTRIES[config["entry"]](session, views)
    queries = {n: load_query(n) for n in sorted(set(traffic["queries"]))}
    for q in queries.values():
        q["input_rows"] = query_bytes.input_rows(q["text"], footers)
        q["column_bytes"] = query_bytes.column_bytes(q["text"], footers)
        t0 = time.perf_counter()
        entry.run(q)
        note(phase="warmup", query=q["name"],
             seconds=time.perf_counter() - t0, input_rows=q["input_rows"],
             column_bytes=q["column_bytes"], **xla.snapshot())
    trace_dir = None
    if trace:
        trace_dir = os.path.join(workdir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()

    before = counters(xla)
    stats_before = entry.stats_text()
    window = Window(entry, queries, traffic, seed, seconds, trace_dir)
    window.run()
    setup_s = window.t_open_wall - t_start
    after = counters(xla)
    stats_after = entry.stats_text()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.devices()[:int(cell["chips"])])
    drain = entry.close()
    note(phase="window", length_s=window.length, setup_s=setup_s,
         completed=len(window.done), attempted=window.attempted,
         failed=len(window.failures), endpoint_drain=drain,
         query_seconds=[round(d["end"] - d["start"], 4)
                        for d in window.done],
         h2d_by_site={k: v - before["h2d_by_site"].get(k, 0)
                      for k, v in after["h2d_by_site"].items()},
         fuse={k: v - before["fuse"].get(k, 0)
               for k, v in after["fuse"].items()},
         xla_in_setup=before["xla"],
         xla_in_window={k: v - before["xla"][k]
                        for k, v in after["xla"].items()},
         pallas_on=sorted(k for k, why in pallas_kernels.KERNELS.items()
                          if why is None),
         pallas_off=sorted(k for k, why in pallas_kernels.KERNELS.items()
                           if why),
         pallas_reached=after["pallas_traced"], memory_peak_bytes=memory_peak)

    ctx = {"cell": cell, "config": config, "traffic": traffic, "peaks": peaks,
           "queries": queries, "done": window.done,
           "window_s": window.length, "setup_s": setup_s,
           "before": before, "after": after, "stats_before": stats_before,
           "stats_after": stats_after, "memory_peak_bytes": memory_peak,
           "traced_span": window.traced_span, "trace": None}
    device_out = dict(device, memory_peak_bytes=memory_peak)
    breakdown = None
    if trace:
        t0 = time.perf_counter()
        xplane = trace_reduce.find_xplane(trace_dir)
        reduced = trace_reduce.reduce_file(xplane, int(cell["chips"]))
        ctx["trace"] = reduced
        note(phase="trace", xplane_bytes=os.path.getsize(xplane),
             reduce_seconds=time.perf_counter() - t0,
             traced_span=window.traced_span, **reduced)
        metrics = read_metrics("per_layer", bench, cell_name, ctx)
        device_out.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = read_metrics("end_to_end", bench, cell_name, ctx)

    # the program's state goes before the reference is worked out
    del entry, session, views, window.entry
    gc.collect()
    t0 = time.perf_counter()
    refs = references_for([d["query"] for d in window.done] or
                          traffic["queries"], paths)
    correct, compared = compare.compare_all(
        [(d["query"], d["rows"]) for d in window.done], refs,
        float(config["compare"]["float_gap_limit"]), len(window.failures))
    note(phase="reference", seconds=time.perf_counter() - t0)
    result = {"correct": bool(correct), "attempted": window.attempted,
              "failed": len(window.failures), "metrics": metrics,
              "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared      # each number beside its limit, last
    for line in window.failures:
        print("failed: " + line, file=sys.stderr)
    for name, (reading, limit) in compared.items():
        print(f"compared {name}: {reading!r} limit {limit!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
