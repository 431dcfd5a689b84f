"""Device parquet decode (stage one): thrift page parsing, RLE/bit-packed
hybrid, device bit-unpack + dictionary gather, per-column arrow fallback
(reference GpuParquetScan.scala:1235 device decode role)."""

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


def test_unpack_bits_widths():
    """Device bit-unpack against a numpy reference for every width 1..32."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.parquet_decode import unpack_bits_device
    r = np.random.default_rng(0)
    for bw in [1, 2, 3, 5, 7, 8, 12, 16, 20, 24, 31, 32]:
        n = 256
        vals = r.integers(0, 1 << min(bw, 31), n, dtype=np.int64)
        bits = np.zeros(n * bw, dtype=np.uint8)
        for i, v in enumerate(vals):
            for b in range(bw):
                bits[i * bw + b] = (int(v) >> b) & 1
        packed = np.packbits(bits, bitorder="little")
        got = np.asarray(unpack_bits_device(
            jnp.asarray(packed), bw, n, 256))[:n]
        assert (got == vals.astype(np.int32)).all(), bw


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
            assert segs_n == segs_p


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
    ONE page (so it rides the single fused decode program, compiled once),
    and decodes to the same rows; a page whose present count is not a whole
    number of 8-value groups keeps the chunk on the per-page path."""
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
            assert nv == n and len(dl) == n and segs[0].count == int(dl.sum())
    assert folded["q"] and folded["s"]
    assert not folded["nul"]        # present counts not multiples of 8
    schema = T.StructType.from_arrow(t.schema)
    out = PN.read_row_group_device(f, 0, schema).to_arrow()
    for name in t.column_names:
        assert out.column(name).to_pylist() == t.column(name).to_pylist(), name
