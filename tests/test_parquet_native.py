"""Device parquet decode (stage one): thrift page parsing, RLE/bit-packed
hybrid, device bit-unpack + dictionary gather, per-column arrow fallback
(reference GpuParquetScan.scala:1235 device decode role)."""

import functools

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu import config as CFG
from spark_rapids_tpu.io import parquet_native as PN
from spark_rapids_tpu.session import TpuSession


def mixed_table(n=4000, seed=1):
    r = np.random.default_rng(seed)
    return pa.table({
        "i": pa.array([None if v % 13 == 0 else int(v)
                       for v in r.integers(0, 300, n)], pa.int32()),
        "l": pa.array([int(v) for v in r.integers(-10**9, 10**9, n)],
                      pa.int64()),
        "d": pa.array([None if v < 0.05 else float(round(v * 100, 4))
                       for v in r.random(n)]),
        "f": pa.array([float(np.float32(v)) for v in r.normal(0, 5, n)],
                      pa.float32()),
        "s": pa.array([None if v % 17 == 0 else f"cat{v % 43}"
                       for v in r.integers(0, 1000, n)]),
    })


@pytest.fixture
def unc_file(tmp_path):
    t = mixed_table()
    p = tmp_path / "unc"
    p.mkdir()
    pq.write_table(t, p / "part-0.parquet", compression="NONE",
                   use_dictionary=True, data_page_size=16 << 10)
    return str(p), t


def test_row_group_device_roundtrip(unc_file):
    path, t = unc_file
    import os
    f = os.path.join(path, "part-0.parquet")
    schema = T.StructType.from_arrow(t.schema)
    out = PN.read_row_group_device(f, 0, schema).to_arrow()
    for name in t.column_names:
        assert out.column(name).to_pylist() == t.column(name).to_pylist(), name


def test_multi_page_and_row_groups(tmp_path):
    t = mixed_table(3000, seed=7)
    f = str(tmp_path / "multi.parquet")
    pq.write_table(t, f, compression="NONE", use_dictionary=True,
                   data_page_size=2 << 10, row_group_size=700)
    schema = T.StructType.from_arrow(t.schema)
    md = pq.ParquetFile(f).metadata
    outs = [PN.read_row_group_device(f, rg, schema).to_arrow()
            for rg in range(md.num_row_groups)]
    got = pa.concat_tables(outs)
    for name in t.column_names:
        assert got.column(name).to_pylist() == t.column(name).to_pylist(), name


def test_snappy_chunks_decode_on_device(tmp_path):
    """Stage 1.5: snappy page bodies decompress on host (arrow C codec) and
    the decode still runs on device; results identical to the source."""
    t = mixed_table(1000, seed=3)
    f = str(tmp_path / "snappy.parquet")
    pq.write_table(t, f, compression="SNAPPY", use_dictionary=True)
    schema = T.StructType.from_arrow(t.schema)
    # the chunk parser itself accepts the compressed chunk (no fallback)
    pages = PN.read_chunk_pages(f, 0, 0)
    assert pages.num_values == 1000
    out = PN.read_row_group_device(f, 0, schema).to_arrow()
    for name in t.column_names:
        assert out.column(name).to_pylist() == t.column(name).to_pylist(), name


@pytest.mark.parametrize("codec", ["GZIP", "ZSTD"])
def test_gzip_zstd_chunks_decode_on_device(tmp_path, codec):
    t = mixed_table(800, seed=6)
    f = str(tmp_path / f"{codec.lower()}.parquet")
    pq.write_table(t, f, compression=codec, use_dictionary=True)
    assert PN.read_chunk_pages(f, 0, 0).num_values == 800
    schema = T.StructType.from_arrow(t.schema)
    out = PN.read_row_group_device(f, 0, schema).to_arrow()
    for name in t.column_names:
        assert out.column(name).to_pylist() == t.column(name).to_pylist(), name


def test_unsupported_codec_falls_back_per_column(tmp_path):
    t = mixed_table(500, seed=4)
    f = str(tmp_path / "brotli.parquet")
    pq.write_table(t, f, compression="BROTLI", use_dictionary=True)
    with pytest.raises(NotImplementedError):
        PN.read_chunk_pages(f, 0, 0)
    schema = T.StructType.from_arrow(t.schema)
    out = PN.read_row_group_device(f, 0, schema).to_arrow()  # arrow path
    for name in t.column_names:
        assert out.column(name).to_pylist() == t.column(name).to_pylist(), name


def test_session_scan_uses_device_decode(unc_file):
    path, t = unc_file
    import spark_rapids_tpu.functions as F
    spark = TpuSession()
    got = (spark.read_parquet(path)
           .group_by(F.col("s"))
           .agg(F.count(F.col("i")).alias("c"),
                F.sum(F.col("d")).alias("sd"))
           .collect().to_pylist())
    exp = {}
    for s, i, d in zip(t.column("s").to_pylist(), t.column("i").to_pylist(),
                       t.column("d").to_pylist()):
        c, sd = exp.get(s, (0, 0.0))
        exp[s] = (c + (i is not None), sd + (d or 0.0))
    assert len(got) == len(exp)
    for r in got:
        c, sd = exp[r["s"]]
        assert r["c"] == c
        assert (r["sd"] or 0.0) == pytest.approx(sd, rel=1e-9)


def test_device_decode_conf_off_matches(unc_file):
    path, t = unc_file
    on = TpuSession({"spark.rapids.tpu.sql.parquet.deviceDecode.enabled":
                      "true"}).read_parquet(path).collect()
    off = TpuSession({CFG.PARQUET_DEVICE_DECODE.key: "false"}) \
        .read_parquet(path).collect()
    for name in t.column_names:
        assert on.column(name).to_pylist() == off.column(name).to_pylist()


def _pack_bits(vals, bw):
    """Value i at bits [i*bw, (i+1)*bw), little-endian bit order."""
    bits = np.zeros(len(vals) * bw, dtype=np.uint8)
    for i, v in enumerate(vals):
        for b in range(bw):
            bits[i * bw + b] = (int(v) >> b) & 1
    return np.packbits(bits, bitorder="little")


@pytest.mark.parametrize("bw", range(1, 33))
def test_unpack_bits_widths(bw):
    """Device bit-unpack against a numpy reference, one case a width 1..32
    (what the chip runs for every bit-packed page), 300 values into a
    capacity of 512 whose tail is zero."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.parquet_decode import unpack_bits_device
    n, cap = 300, 512
    vals = np.random.default_rng(bw).integers(0, 1 << bw, n, dtype=np.int64)
    got = np.asarray(unpack_bits_device(
        jnp.asarray(_pack_bits(vals, bw)), bw, n, cap))
    want = np.zeros(cap, dtype=np.int64)
    want[:n] = vals
    assert (got.astype(np.uint32) == want.astype(np.uint32)).all()


def test_unpack_bits_tiny_run():
    """Fewer values than one byte-aligned group of any width: 8 of width 4."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.parquet_decode import unpack_bits_device
    vals = np.array([3, 9, 15, 0, 7, 1, 2, 4], dtype=np.int64)
    got = np.asarray(unpack_bits_device(
        jnp.asarray(_pack_bits(vals, 4)), 4, len(vals), 16))
    assert list(got[:8]) == list(vals)
    assert (got[8:] == 0).all()


def test_native_scanner_matches_python_parser(tmp_path, monkeypatch):
    """The C scanner (native/parquet_host.cpp) and the Python parser must
    produce identical ChunkPages structures — same pages, def levels, run
    segmentation, and dictionary."""
    from spark_rapids_tpu import native as N
    N.parquet_lib()  # a missing toolchain is an error on this installation
    t = mixed_table(3000, seed=7)
    f = str(tmp_path / "m.parquet")
    pq.write_table(t, f, compression="NONE", use_dictionary=True,
                   data_page_size=4096, row_group_size=1500)
    md = pq.ParquetFile(f).metadata

    def parse_all():
        out = []
        for rg in range(md.num_row_groups):
            for c in range(md.num_columns):
                try:
                    out.append(PN.read_chunk_pages(f, rg, c, md=md))
                except NotImplementedError:
                    out.append(None)
        return out

    native = parse_all()

    def boom(*a, **k):
        raise NotImplementedError("forced python parser")
    monkeypatch.setattr(N, "scan_chunk_native", boom)
    python = parse_all()

    # a scanner that cannot be built is an error, not "parse in Python"
    def no_toolchain(*a, **k):
        raise N.NativeBuildError("no toolchain")
    monkeypatch.setattr(N, "scan_chunk_native", no_toolchain)
    with pytest.raises(N.NativeBuildError):
        parse_all()

    assert len(native) == len(python)
    for cn, cp in zip(native, python):
        assert (cn is None) == (cp is None)
        if cn is None:
            continue
        assert cn.physical_type == cp.physical_type
        assert cn.num_values == cp.num_values
        if isinstance(cn.dict_values, list):
            assert cn.dict_values == cp.dict_values
        else:
            assert (cn.dict_values == cp.dict_values).all()
        assert len(cn.index_segments) == len(cp.index_segments)
        for pn_, pp in zip(cn.index_segments, cp.index_segments):
            nv_n, dl_n, bw_n, pb_n, vo_n, segs_n = pn_
            nv_p, dl_p, bw_p, pb_p, vo_p, segs_p = pp
            assert nv_n == nv_p and bw_n == bw_p and vo_n == vo_p
            assert pb_n == pb_p
            assert (dl_n == dl_p).all()
            assert np.array_equal(segs_n, segs_p)


@pytest.mark.parametrize("codec", ["NONE", "snappy"])
def test_v2_data_pages_device_path(tmp_path, codec):
    """DATA_PAGE_V2 (data_page_version='2.0'): uncompressed level prefix +
    optionally-compressed values section, def levels without the v1 length
    prefix — decodes on the device path, nulls included."""
    t = mixed_table(3000, seed=11)
    f = str(tmp_path / "v2.parquet")
    pq.write_table(t, f, compression=codec, use_dictionary=True,
                   data_page_version="2.0", data_page_size=4 << 10)
    schema = T.StructType.from_arrow(t.schema)
    md = pq.ParquetFile(f).metadata
    outs = [PN.read_row_group_device(f, rg, schema).to_arrow()
            for rg in range(md.num_row_groups)]
    got = pa.concat_tables(outs)
    for name in t.column_names:
        assert got.column(name).to_pylist() == t.column(name).to_pylist(), name


def test_multi_page_chunks_fold_into_one_page(tmp_path):
    """A chunk cut into many all-packed pages of one bit width folds into
    ONE page (so it rides the single-page decode program, which a consumer
    can fuse into itself), and decodes to the same rows; a page whose present
    count is not a whole number of 8-value groups is not folded, and the
    chunk takes the segment-table program: one program either way."""
    n = 40000
    r = np.random.default_rng(3)
    t = pa.table({
        "q": pa.array(r.integers(1, 51, n).astype(np.float64)),
        "s": pa.array(np.array([f"cat{i}" for i in range(40)])[
            r.integers(0, 40, n)]),
        "nul": pa.array([None if v % 13 == 0 else int(v)
                         for v in r.integers(0, 300, n)], pa.int32()),
    })
    f = str(tmp_path / "pages.parquet")
    pq.write_table(t, f, compression="NONE", use_dictionary=True,
                   data_page_size=2048)
    md = pq.ParquetFile(f).metadata
    folded = {}
    for c, name in enumerate(t.column_names):
        pages = PN.read_chunk_pages(f, 0, c, md=md)
        assert len(pages.index_segments) > 4, name
        merged = PN._merge_packed_pages(pages)
        folded[name] = len(merged.index_segments) == 1
        if folded[name]:
            nv, dl, _bw, _packed, _off, segs = merged.index_segments[0]
            assert nv == n and len(dl) == n
            assert segs[:, PN.RUN_COUNT].tolist() == [int(dl.sum())]
    assert folded["q"] and folded["s"]
    assert not folded["nul"]        # present counts not multiples of 8
    schema = T.StructType.from_arrow(t.schema)
    out, cols = _read_traced(f, 0, schema)
    for name in t.column_names:
        assert out.column(name).to_pylist() == t.column(name).to_pylist(), name
    assert {c: s["decode"] for c, s in cols.items()} == {
        "q": "packed", "s": "packed", "nul": "runs"}
    assert all(s["path"] == "fused" for s in cols.values())
    # a table row a page, but where a page happens to end on a group
    assert 1 < cols["nul"]["segments"] <= cols["nul"]["pages"]
    assert cols["nul"]["packed"] > cols["nul"]["pages"]


# -- the segment-table decode: RLE runs, bit widths that differ by page -------

def _read_traced(f, rg, schema):
    """(arrow table, {column: counts of its scan.column span}) of one row
    group through the device decode; no chunk may open a scan.page span,
    only its read and its stage (or fallback)."""
    from spark_rapids_tpu.runtime import tracing
    tracing.drain()
    tracing.set_enabled(True)
    try:
        out = PN.read_row_group_device(f, rg, schema).to_arrow()
        spans = tracing.recorded()
    finally:
        tracing.set_enabled(False)
        tracing.drain()
    assert {s["name"] for s in spans} - {"gc"} <= {
        "scan.column", "scan.read", "scan.stage", "scan.fallback"}
    return out, {s["counts"]["column"]: s["counts"] for s in spans
                 if s["name"] == "scan.column"}


def _with_runs(r, n, card, runs=6):
    """Random values below `card` with `runs` stretches of one value, each a
    few 8-value groups long at least: the hybrid encoder writes RLE there."""
    v = r.integers(0, card, n)
    for at in r.integers(0, max(n - 600, 1), runs):
        v[at:at + int(r.integers(64, 600))] = r.integers(0, card)
    return v


def _growing_keys(r, n_keys):
    """Ascending keys, each one to seven times, as l_orderkey is: the
    dictionary grows through the row group, and the bit width with it."""
    return np.repeat(np.arange(n_keys, dtype=np.int64) * 4 + 1,
                     r.integers(1, 8, n_keys))


@functools.lru_cache(maxsize=None)
def _runs_cases():
    r = np.random.default_rng(27)
    n = 30000
    nulls = _with_runs(r, n, 5).astype(object)
    nulls[::13] = None
    nulls[4000:4300] = None            # a run of nulls between value runs
    words = np.array([f"w{i:02d}" for i in range(12)])
    mixed = {
        "s": pa.array(words[_with_runs(r, n, 12)]),
        "l": pa.array(_with_runs(r, n, 7).astype(np.int64) * 10**10 - 5),
        "d": pa.array(_with_runs(r, n, 40) / 8.0),
        "nul": pa.array(list(nulls), pa.int32()),
    }
    return {
        # name: (columns, write options, columns that must decode by runs)
        "bw1": ({"x": pa.array(_with_runs(r, n, 2), pa.int32())}, {}, "x"),
        "bw2": ({"x": pa.array(_with_runs(r, n, 3), pa.int32())}, {}, "x"),
        "bw4": ({"x": pa.array(_with_runs(r, n, 11), pa.int32())}, {}, "x"),
        "constant": ({"x": pa.array(np.full(70000, 7), pa.int32())}, {}, "x"),
        "growing_bit_width": (
            {"k": pa.array(_growing_keys(r, 100000))}, {}, "k"),
        "nulls_between_runs": ({"nul": mixed["nul"]}, {}, "nul"),
        "string": ({"s": mixed["s"]}, {}, "s"),
        "int64": ({"l": mixed["l"]}, {}, "l"),
        "double": ({"d": mixed["d"]}, {}, "d"),
        "all_null": ({"x": pa.array([None] * 3000, pa.int32()),
                      "s": pa.array([None] * 3000, pa.string())}, {}, "xs"),
        "page_v2": (mixed, {"data_page_version": "2.0"}, "sld"),
        "snappy": (mixed, {"compression": "SNAPPY"}, "sld"),
        "many_small_pages": (mixed, {"data_page_size": 2048}, "sld"),
    }


@pytest.mark.parametrize("case", [
    "all_null", "bw1", "bw2", "bw4", "constant", "double",
    "growing_bit_width", "int64", "many_small_pages", "nulls_between_runs",
    "page_v2", "snappy", "string"])
def test_runs_decode_matches_pyarrow(tmp_path, case):
    """A chunk with RLE runs, or pages of different bit widths, decodes in
    the segment-table program to what pyarrow reads from the same file."""
    columns, options, by_runs = _runs_cases()[case]
    f = str(tmp_path / "runs.parquet")
    pq.write_table(pa.table(columns), f, use_dictionary=True,
                   **{"compression": "NONE", **options})
    want = pq.read_table(f)
    schema = T.StructType.from_arrow(want.schema)
    md = pq.ParquetFile(f).metadata
    outs, widths = [], set()
    for rg in range(md.num_row_groups):
        out, cols = _read_traced(f, rg, schema)
        outs.append(out)
        for name, counts in cols.items():
            assert counts["path"] == "fused", (name, counts)
            # `nul`: a page of nulls ends inside an 8-value group
            if name[0] in by_runs or name == "nul":
                assert counts["decode"] == "runs", (name, counts)
                assert 1 <= counts["segments"] <= \
                    counts["packed"] + counts["rle"] or case == "all_null"
        widths |= {p[2] for c in range(md.num_columns) for p in
                   PN.read_chunk_pages(f, rg, c, md=md).index_segments}
    got = pa.concat_tables(outs)
    for name in want.column_names:
        assert got.column(name).to_pylist() == \
            want.column(name).to_pylist(), name
    if case == "growing_bit_width":
        assert {13, 14, 15, 16, 17} <= widths
        assert cols["k"]["rle"] == 0
    if case == "constant":
        assert cols["x"]["packed"] == 0
        assert cols["x"]["rle"] == cols["x"]["pages"] > 1
    if case == "all_null":
        assert cols["x"]["segments"] == 0
    if case == "many_small_pages":
        assert min(c["pages"] for c in cols.values()) > 4


def _segments_array(segs):
    """parse_rle_hybrid's list as the scan keeps a page's segments."""
    return np.array([(s.kind == "packed", s.count, s.value, s.byte_off,
                      s.byte_len) for s in segs], np.int64).reshape(-1, 5)


def _hybrid_stream(r, bw, total):
    """(bytes, values): a random RLE / bit-packed hybrid stream of `total`
    values `bw` bits wide, written as a Parquet encoder may write one."""
    out, vals = bytearray(), []
    while len(vals) < total:
        left = total - len(vals)
        if r.random() < 0.4:
            run, v = int(r.integers(1, 700)), int(r.integers(0, 1 << bw))
            h = run << 1
            while h >= 0x80:
                out.append((h & 0x7F) | 0x80)
                h >>= 7
            out.append(h)
            out += v.to_bytes((bw + 7) // 8, "little")
            vals += [v] * min(run, left)
        else:
            groups = int(r.integers(1, 64))
            v = r.integers(0, 1 << bw, groups * 8, dtype=np.int64)
            out.append((groups << 1) | 1)
            bits = ((v[:, None] >> np.arange(bw)) & 1).astype(np.uint8)
            out += np.packbits(bits.reshape(-1), bitorder="little").tobytes()
            vals += [int(x) for x in v[:left]]
    return bytes(out), vals


@pytest.mark.parametrize("seed", range(8))
def test_unpack_runs_matches_host_decode(seed):
    """The traceable body over a segment table built from random hybrid
    streams (pages of random bit widths, runs of both kinds, the last run of
    a page cut short) against decode_rle_host page by page."""
    import jax.numpy as jnp
    from spark_rapids_tpu import native as N
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.ops import parquet_decode as PD
    r = np.random.default_rng(1000 + seed)
    pages, want = [], []
    for _ in range(int(r.integers(1, 9))):
        bw, total = int(r.integers(1, 21)), int(r.integers(0, 3000))
        buf, vals = _hybrid_stream(r, bw, total)
        segs = PN.parse_rle_hybrid(buf, 0, len(buf), bw, total)
        assert sum(s.count for s in segs) == total
        want.append(PN.decode_rle_host(buf, 0, len(buf), bw, total))
        assert want[-1].tolist() == vals
        assert np.array_equal(N.scan_hybrid_native(buf, 0, len(buf), bw, total),
                              _segments_array(segs))
        pages.append((total, np.ones(total, np.int32), bw, buf, 0,
                      _segments_array(segs)))
    want = np.concatenate(want)
    packed, table, max_bw = PN._segment_table(
        PN.ChunkPages("INT32", [], pages, len(want)))
    assert table.shape[1] <= sum(len(p[5]) for p in pages)
    pcap = bucket_capacity(max(len(want), 1))
    scap = bucket_capacity(table.shape[1])
    table_h = PN._padded(table, scap)
    table_h[PD.SEG_START, table.shape[1]:] = pcap + np.arange(
        scap - table.shape[1])
    got = PD.unpack_runs_device(
        jnp.asarray(PN._padded(packed, bucket_capacity(max(len(packed), 1)))),
        jnp.asarray(table_h), max_bw, pcap)
    assert np.asarray(got)[:len(want)].tolist() == want.tolist()


def test_a_mixed_chunk_is_one_dispatch_and_no_page_span(tmp_path):
    """Runs and packed segments over several pages: exactly one call_fused
    (the `dispatches` counter), under the program's own name, and the
    bookkeeping of the movement ledger the page path had."""
    from spark_rapids_tpu.runtime import fuse, movement
    r = np.random.default_rng(5)
    t = pa.table({"x": pa.array(_with_runs(r, 50000, 3), pa.int32())})
    f = str(tmp_path / "one.parquet")
    pq.write_table(t, f, compression="NONE", use_dictionary=True)
    schema = T.StructType.from_arrow(t.schema)
    _read_traced(f, 0, schema)                  # compiled
    before = fuse.stage_metrics()
    movement.reset()
    out, cols = _read_traced(f, 0, schema)      # asserts: scan.column only
    after = fuse.stage_metrics()
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["traces"] == before["traces"]
    assert cols["x"]["pages"] > 1 and cols["x"]["rle"] >= 1
    assert cols["x"]["packed"] > cols["x"]["segments"] - cols["x"]["rle"]
    assert any(k[0] == "pq_runs_decode" for k in fuse._kernels
               if isinstance(k, tuple))
    moved = {k[2]: v["bytes"] for k, v in movement.snapshot().items()
             if k[0] == "h2d"}
    assert moved == {"scan.device": cols["x"]["decoded_bytes"]}
    assert out.column("x").to_pylist() == t.column("x").to_pylist()
