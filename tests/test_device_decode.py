"""Device-side Parquet decode (round 16): parity of the encoded-upload +
Pallas-decode path against pyarrow's host decode, across encodings
(plain / dictionary / RLE / bit-packed / delta), null densities (none /
sparse / dense / all-null), exact bucket-boundary row counts, ANSI modes,
per-column fallback mixing, and row-group pruning composition.

Unit layer: io/encoded.py -> ops/pallas_decode.py round trip checked
column-by-column (data, validity, zero-filled padded tails). Session
layer: read_parquet with spark.rapids.sql.decode.device.enabled flipped
must be byte-identical (the decode path may not change a single value).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.io import encoded as E
from spark_rapids_tpu.ops import pallas_decode as PD
from spark_rapids_tpu.sql.session import TpuSession
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.expr.core import col, lit


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------

def _col(rng, n, kind):
    """(arrow array, engine dtype) for one column flavor."""
    if kind == "i32_dict":       # low-cardinality: dictionary-encodes
        return (pa.array(rng.choice([3, 7, 11, 42, -5], n).astype(np.int32)),
                T.Int32Type())
    if kind == "i64_plain":      # high-entropy 64-bit: stays PLAIN
        return (pa.array(rng.integers(-2**40, 2**40, n).astype(np.int64)),
                T.Int64Type())
    if kind == "f64":
        return pa.array(rng.normal(size=n)), T.Float64Type()
    if kind == "f32":
        return pa.array(rng.normal(size=n).astype(np.float32)), T.Float32Type()
    if kind == "bool":
        return pa.array(rng.random(n) < 0.5), T.BooleanType()
    if kind == "i32_wide":       # full-range 32-bit: wide bit-packed codes
        return (pa.array(rng.integers(-2**30, 2**30, n).astype(np.int32)),
                T.Int32Type())
    if kind == "i64_delta":      # monotone: what DELTA_BINARY_PACKED is for
        return (pa.array(np.cumsum(rng.integers(0, 50, n)).astype(np.int64)),
                T.Int64Type())
    raise AssertionError(kind)


def _with_nulls(rng, arr, density):
    if density == "none":
        return arr
    frac = {"sparse": 0.1, "dense": 0.9, "all": 1.0}[density]
    mask = rng.random(len(arr)) < frac if frac < 1.0 \
        else np.ones(len(arr), bool)
    return pa.Array.from_pandas(
        np.ma.masked_array(arr.to_numpy(zero_copy_only=False), mask),
        type=arr.type)


def _unit_roundtrip(table, fields, path, **write_kw):
    """Write, read encoded, decode on device, compare every column to the
    pyarrow host decode: data under validity, the validity plane itself,
    and the padded tail (downstream bounds-trusting kernels require
    zero-filled slots past num_rows)."""
    pq.write_table(table, path, **write_kw)
    pf = pq.ParquetFile(path)
    groups = list(range(pf.metadata.num_row_groups))
    seen = 0
    for hb in E.read_encoded_batches(path, pf.metadata, groups, fields,
                                     batch_rows=1 << 20):
        assert not hb.fallback, hb.fallback
        cb = PD.decode_batch(E.upload(hb, {}))
        n = hb.num_rows
        seen += n
        for fi, fld in enumerate(fields):
            cv = cb.columns[fi]
            host = table.column(fld.name).combine_chunks()
            hvalid = np.ones(n, bool) if host.null_count == 0 else \
                ~np.asarray(host.is_null())
            fill = False if pa.types.is_boolean(host.type) else 0
            filled = host.fill_null(fill)
            if pa.types.is_timestamp(host.type):
                filled = filled.cast(pa.int64())
            hdata = np.asarray(filled)
            ddata = np.asarray(cv.data)[:n]
            dvalid = np.ones(n, bool) if cv.validity is None else \
                np.asarray(cv.validity)[:n]
            assert np.array_equal(dvalid, hvalid), fld.name
            if hdata.dtype != ddata.dtype:
                hdata = hdata.astype(ddata.dtype)
            assert np.array_equal(np.where(hvalid, hdata, 0),
                                  np.where(dvalid, ddata, 0)), fld.name
            tail = np.asarray(cv.data)[n:]
            assert tail.size == 0 or not np.any(tail), \
                f"{fld.name}: nonzero padded tail"
    assert seen == table.num_rows


# ---------------------------------------------------------------------------
# unit layer
# ---------------------------------------------------------------------------

MIXED_KINDS = ("i32_dict", "i64_plain", "f64", "f32", "bool", "i32_wide")


# Tier-1 keeps sparse (the realistic density) and all (the degenerate
# fully-null corner); none/dense ride tools/slow_rehomed.txt (ci_check)
# since the round-18 headroom squeeze.
@pytest.mark.parametrize("nulls", [
    pytest.param("none", marks=pytest.mark.slow), "sparse",
    pytest.param("dense", marks=pytest.mark.slow), "all"])
def test_unit_parity_null_densities(tmp_path, nulls):
    rng = np.random.default_rng(7)
    n = 5000
    cols, fields = {}, []
    for kind in MIXED_KINDS:
        arr, dt = _col(rng, n, kind)
        cols[kind] = _with_nulls(rng, arr, nulls)
        fields.append(T.StructField(kind, dt))
    # small pages + small row groups: multi-page def-level splicing and
    # per-page dictionary index widths are all exercised
    _unit_roundtrip(pa.table(cols), fields, str(tmp_path / "m.parquet"),
                    compression="SNAPPY", row_group_size=2000,
                    use_dictionary=["i32_dict"], data_page_size=4096,
                    data_page_version="1.0")


@pytest.mark.parametrize("n", [8, 127, 128, 1024, 4095, 4096, 4097])
def test_unit_bucket_boundary_row_counts(tmp_path, n):
    # exact bucket-ladder boundaries (pow2) and their +/-1 neighbours:
    # the padded region is 0, 1, or bucket-1 slots wide
    rng = np.random.default_rng(n)
    arr, dt = _col(rng, n, "i64_plain")
    arr = _with_nulls(rng, arr, "sparse")
    b, bt = _col(rng, n, "bool")
    _unit_roundtrip(pa.table({"v": arr, "b": b}),
                    [T.StructField("v", dt), T.StructField("b", bt)],
                    str(tmp_path / "b.parquet"), use_dictionary=False,
                    data_page_version="1.0")


@pytest.mark.parametrize("nulls", ["none", "sparse"])
def test_unit_delta_binary_packed(tmp_path, nulls):
    rng = np.random.default_rng(3)
    arr, dt = _col(rng, 20000, "i64_delta")
    arr = _with_nulls(rng, arr, nulls)
    # tiny pages: each page restarts its own delta stream (first value in
    # the page header) — the per-stream cumsum restart is the hard part
    _unit_roundtrip(pa.table({"d": arr}), [T.StructField("d", dt)],
                    str(tmp_path / "d.parquet"), use_dictionary=False,
                    column_encoding={"d": "DELTA_BINARY_PACKED"},
                    row_group_size=8000, data_page_size=2048,
                    data_page_version="1.0")


def test_unit_bool_rle(tmp_path):
    rng = np.random.default_rng(5)
    # long runs so RLE actually RLEs, plus a random tail of bit-packed runs
    runs = np.repeat(rng.random(40) < 0.5, 200)
    mix = rng.random(1000) < 0.5
    arr = pa.array(np.concatenate([runs, mix]))
    _unit_roundtrip(pa.table({"b": arr}), [T.StructField("b", T.BooleanType())],
                    str(tmp_path / "r.parquet"), use_dictionary=False,
                    column_encoding={"b": "RLE"}, data_page_version="1.0")


def test_unit_date_timestamp(tmp_path):
    rng = np.random.default_rng(11)
    n = 3000
    days = rng.integers(8000, 12000, n).astype(np.int32)
    us = rng.integers(0, 2**48, n).astype(np.int64)
    t = pa.table({
        "d": pa.array(days, pa.date32()),
        "ts": pa.array(us, pa.timestamp("us")),
    })
    _unit_roundtrip(t, [T.StructField("d", T.DateType()),
                        T.StructField("ts", T.TimestampType())],
                    str(tmp_path / "t.parquet"), data_page_version="1.0")


def test_unit_fallback_reasons(tmp_path):
    # unsupported columns come back as None + reason; supported columns in
    # the SAME file still device-decode
    t = pa.table({"s": pa.array(["a", "bb", None] * 100),
                  "i": pa.array(np.arange(300, dtype=np.int64))})
    fields = [T.StructField("s", T.StringType()),
              T.StructField("i", T.Int64Type())]
    path = str(tmp_path / "fb.parquet")
    pq.write_table(t, path)
    pf = pq.ParquetFile(path)
    hbs = list(E.read_encoded_batches(path, pf.metadata, [0], fields, 1 << 20))
    assert len(hbs) == 1
    assert hbs[0].columns[0] is None and "s" in hbs[0].fallback
    assert "StringType" in hbs[0].fallback["s"]
    assert hbs[0].columns[1] is not None
    # the static footer probe agrees with the execute-time screen
    probe = E.probe_support(path, fields)
    assert set(probe) == {"s"}


# ---------------------------------------------------------------------------
# run-table expansion against a plain numpy expansion of the same table
# ---------------------------------------------------------------------------

def _pack_bits(vals, w):
    """LSB-first bit packing in groups of 8 values, as a bit-packed run."""
    v = np.asarray(vals, np.uint64)
    v = np.concatenate([v, np.zeros((-len(v)) % 8, np.uint64)])
    bits = (v[:, None] >> np.arange(w, dtype=np.uint64)) & np.uint64(1)
    return np.packbits(bits.astype(np.uint8).reshape(-1),
                       bitorder="little").tobytes()


def _run_table(spec):
    """spec: [("rle", n, value, width, base) | ("packed", values, width,
    base)] -> (io/encoded._Runs, the values it encodes as wrapped int32)."""
    runs, want = E._Runs(), []
    for r in spec:
        if r[0] == "rle":
            _, n, value, w, base = r
            runs.add_rle(n, value, w, base)
            want.append(np.full(n, value + base, np.int64))
        else:
            _, vals, w, base = r
            runs.add_packed(len(vals), _pack_bits(vals, w), w, base)
            want.append(np.asarray(vals, np.int64) + base)
    flat = np.concatenate(want) if want else np.zeros(0, np.int64)
    return runs, flat.astype(np.uint32).view(np.int32)


def _mixed_spec(rng, w, n_runs, base=0):
    """Alternating RLE and bit-packed runs of width `w`, odd lengths
    included (a page's last bit-packed run is cut short of its groups)."""
    spec = []
    for k in range(n_runs):
        n = int(rng.integers(1, 70))
        if k % 2:
            spec.append(("rle", n, int(rng.integers(0, 1 << min(w, 31))),
                         w, base))
        else:
            spec.append(("packed", rng.integers(0, 1 << w, n), w, base))
    return spec


def _level_spec(valid):
    """Definition levels (width 1) of a validity vector: RLE over the
    stretches of 64 equal rows, bit-packed elsewhere."""
    spec = []
    for k in range(0, len(valid), 64):
        blk = valid[k: k + 64].astype(np.int64)
        if blk.min() == blk.max():
            spec.append(("rle", len(blk), int(blk[0]), 1, 0))
        else:
            spec.append(("packed", blk, 1, 0))
    return spec


def _expand_cases():
    rng = np.random.default_rng(27)
    cases = {
        "rle_only": [("rle", 40, 5, 3, 0), ("rle", 9, 2, 3, 0),
                     ("rle", 1, 7, 3, 0)],
        "packed_only": [("packed", rng.integers(0, 8, 64), 3, 0),
                        ("packed", rng.integers(0, 8, 21), 3, 0)],
        "mixed": _mixed_spec(rng, 12, 20),
        "one_run": [("packed", rng.integers(0, 1 << 12, 100), 12, 0)],
        "one_rle_run": [("rle", 100, 9, 12, 0)],
        "no_runs": [],
        # three row groups, each with its own dictionary (vocab base)
        "pages_with_bases": _mixed_spec(rng, 5, 6) + _mixed_spec(
            rng, 7, 6, base=32) + _mixed_spec(rng, 3, 6, base=160),
        # a page whose values are all null leaves a run of no rows
        "empty_run_between": [("rle", 10, 1, 4, 0),
                              ("packed", np.zeros(0, np.int64), 4, 0),
                              ("packed", rng.integers(0, 16, 12), 4, 0)],
        # 7 runs and the closing entry fill the bucket of 8; 8 spill to 16
        "bucket_edge_7": _mixed_spec(rng, 12, 7),
        "bucket_edge_8": _mixed_spec(rng, 12, 8),
        "bucket_edge_15": _mixed_spec(rng, 12, 15),
    }
    for w in (1, 3, 12, 17, 32):
        cases[f"width_{w}"] = _mixed_spec(rng, w, 12)
    for name, frac in (("nulls_0", 0.0), ("nulls_50", 0.5),
                       ("nulls_100", 1.0)):
        valid = rng.random(700) >= frac
        valid[100:300] = valid[100]  # a stretch that run-length encodes
        cases[f"levels_{name}"] = _level_spec(valid)
    return cases


_EXPAND_CASES = _expand_cases()


@pytest.mark.parametrize("fit", ["tail", "exact"])
@pytest.mark.parametrize("case", sorted(_EXPAND_CASES))
def test_expand_runs_matches_numpy(case, fit):
    runs, want = _run_table(_EXPAND_CASES[case])
    prefix = "d_" if case.startswith("levels_") else ""
    planes = E._run_planes(runs, prefix=prefix)
    assert planes[prefix + "a"].dtype == np.int32
    # a capacity past the encoded total (its tail is exact 0), and one
    # that the values fill to the last row (the closing entry falls away)
    vcap = len(want) + 37 if fit == "tail" else max(len(want), 1)
    got = np.asarray(PD.expand_runs(
        {k: jnp.asarray(v) for k, v in planes.items()}, prefix, vcap))
    assert got.dtype == np.int32 and got.shape == (vcap,)
    assert np.array_equal(got[: len(want)], want)
    assert not np.any(got[len(want):])


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("shape", [(5,), (1024,), (1025,), (3000,),
                                   (2, 5000), (1 << 15,)])
def test_two_level_cumsum_matches_numpy(shape, dtype):
    # blocks of _SCAN_BLOCK rows, their edge, a ragged last block, a
    # leading axis; wrap-around included (the sums are modular)
    x = np.random.default_rng(sum(shape)).integers(
        -2**30, 2**30, shape).astype(dtype)
    got = np.asarray(PD._cumsum(jnp.asarray(x)))
    assert got.dtype == dtype
    assert np.array_equal(got, np.cumsum(x, axis=-1, dtype=dtype))


def test_expand_runs_wide_bit_offsets():
    # a pool past 2**31 bits ships its offsets as int64; same values
    runs, want = _run_table(_EXPAND_CASES["width_17"])
    planes = E._run_planes(runs)
    planes["a"] = planes["a"].astype(np.int64)
    got = np.asarray(PD.expand_runs(
        {k: jnp.asarray(v) for k, v in planes.items()}, "", len(want) + 5))
    assert np.array_equal(got[: len(want)], want)
    assert not np.any(got[len(want):])


@pytest.mark.parametrize("page_bytes", [512, 2048, 1 << 20])
def test_expand_delta_matches_numpy(tmp_path, page_bytes):
    # several pages: each restarts its own stream (first value, miniblocks)
    rng = np.random.default_rng(page_bytes)
    vals = np.cumsum(rng.integers(-20, 50, 6000)).astype(np.int64)
    path = str(tmp_path / "d.parquet")
    pq.write_table(pa.table({"d": pa.array(vals)}), path,
                   use_dictionary=False, data_page_size=page_bytes,
                   column_encoding={"d": "DELTA_BINARY_PACKED"},
                   data_page_version="1.0")
    pf = pq.ParquetFile(path)
    hb, = E.read_encoded_batches(path, pf.metadata, [0],
                                 [T.StructField("d", T.Int64Type())], 1 << 20)
    ec = hb.columns[0]
    assert ec.kind == "delta", hb.fallback
    meta = dict(ec.meta)
    n_streams = int(np.sum(ec.planes["s_start"] < len(vals)))
    assert (n_streams > 1) == (page_bytes < 1 << 20)
    got = np.asarray(PD._expand_delta(
        {k: jnp.asarray(v) for k, v in ec.planes.items()},
        meta["vcap"], meta["vpm"]))
    assert np.array_equal(got[: len(vals)], vals)


# ---------------------------------------------------------------------------
# session layer: the decode flag may not change a single byte
# ---------------------------------------------------------------------------

def _write_mixed(tmp_path, n=4000, seed=13):
    rng = np.random.default_rng(seed)
    cols, _ = {}, None
    for kind in MIXED_KINDS:
        arr, _dt = _col(rng, n, kind)
        cols[kind] = arr
    cols["i64_plain"] = _with_nulls(rng, cols["i64_plain"], "sparse")
    cols["f64"] = _with_nulls(rng, cols["f64"], "sparse")
    cols["s"] = pa.array(  # string: always a per-column host fallback
        np.array(["aa", "bb", "cc", None], object)[rng.integers(0, 4, n)])
    path = str(tmp_path / "mixed.parquet")
    pq.write_table(pa.table(cols), path, row_group_size=1500,
                   compression="SNAPPY", data_page_version="1.0")
    return path


def _flip(path, q, extra_conf=None):
    """Run q under decode.device on and off; return both sorted tables."""
    out = []
    for flag in ("true", "false"):
        conf = {"spark.rapids.sql.decode.device.enabled": flag}
        conf.update(extra_conf or {})
        tbl = q(TpuSession(conf)).collect()
        out.append(tbl.sort_by([(c, "ascending") for c in tbl.column_names]))
    return out


def test_session_parity_scan_filter_agg(tmp_path):
    path = _write_mixed(tmp_path)
    for q in (
        lambda s: s.read_parquet(path),
        lambda s: s.read_parquet(path).filter(col("i64_plain") > lit(0)),
        lambda s: (s.read_parquet(path).group_by("i32_dict")
                   .agg(F.sum(col("i64_plain")), F.sum(col("f64")),
                        F.count(col("bool")))),
        lambda s: s.read_parquet(path).select(
            (col("i32_wide") + col("i32_dict")).alias("w"), col("s")),
    ):
        dev, host = _flip(path, q)
        assert dev.equals(host)  # byte-identical, not approx


@pytest.mark.parametrize("ansi", ["true", "false"])
def test_session_parity_ansi_modes(tmp_path, ansi):
    path = _write_mixed(tmp_path, n=2000)
    dev, host = _flip(
        path,
        lambda s: (s.read_parquet(path)
                   .filter(col("i64_plain") % lit(7) == lit(0))
                   .agg(F.sum(col("i64_plain")), F.avg(col("f64")))),
        extra_conf={"spark.sql.ansi.enabled": ansi})
    assert dev.equals(host)


def test_session_fallback_mixing_visible(tmp_path):
    # string column host-falls-back INSIDE a device-decoded batch; the
    # reason is visible in the stage explain BEFORE the query runs
    path = _write_mixed(tmp_path, n=1000)
    s = TpuSession({"spark.rapids.sql.decode.device.enabled": "true"})
    df = s.read_parquet(path).filter(col("bool"))
    stages = df.explain("stages")
    assert "DeviceDecodeScanExec" in stages
    assert "host-fallback{s: " in stages
    dev, host = _flip(path, lambda s: s.read_parquet(path).filter(col("bool")))
    assert dev.equals(host)


def test_session_pruning_composes_with_device_decode(tmp_path):
    # regression (satellite 2): pruned row groups are never uploaded, and
    # pruning+device == unpruned host, byte-identical
    n = 2000
    t = pa.table({
        "i": pa.array(np.arange(n, dtype=np.int64)),
        "f": pa.array(np.linspace(-5.0, 5.0, n)),
    })
    path = str(tmp_path / "sorted.parquet")
    pq.write_table(t, path, row_group_size=200, data_page_version="1.0")

    def q(s):
        return s.read_parquet(path).filter(col("i") >= lit(1500))

    sdev = TpuSession({"spark.rapids.sql.decode.device.enabled": "true"})
    dev = q(sdev).collect()
    m = sdev.last_metrics()
    scan = next(v for k, v in m.items()
                if k.startswith("EncodedParquetSourceExec"))
    assert scan.get("numRowGroupsPruned", 0) >= 7  # groups 0..6 refuted
    # rows uploaded = kept groups only, not the whole file
    assert scan.get("numOutputRows", 0) <= 600

    shost = TpuSession({"spark.rapids.sql.decode.device.enabled": "false",
                        "spark.rapids.sql.parquet.pruning.enabled": "false"})
    host = q(shost).collect()
    key = [("i", "ascending")]
    assert dev.sort_by(key).equals(host.sort_by(key))


def test_session_disabled_path_unchanged(tmp_path):
    # decode.device off restores the exact pre-round-16 plan shape
    path = _write_mixed(tmp_path, n=500)
    s = TpuSession({"spark.rapids.sql.decode.device.enabled": "false"})
    df = s.read_parquet(path)
    stages = df.explain("stages")
    assert "ParquetScanExec" in stages
    assert "DeviceDecodeScanExec" not in stages


def test_session_fused_single_dispatch(tmp_path):
    # decode + filter + project fuse into ONE dispatch per batch
    path = _write_mixed(tmp_path, n=3000)
    s = TpuSession({"spark.rapids.sql.decode.device.enabled": "true"})
    df = (s.read_parquet(path)
          .select((col("i64_plain") + col("i32_dict")).alias("v"))
          .filter(col("v") % lit(3) == lit(0)))
    stages = df.explain("stages")
    assert "FusedStageExec" in stages and "DeviceDecodeScan" in stages
    df.collect()
    m = s.last_metrics()
    fused = next(v for k, v in m.items() if k.startswith("FusedStageExec"))
    batches = fused.get("numOutputBatches", 0)
    dispatches = fused.get("numDeviceDispatches",
                           fused.get("numDispatches", 0))
    if dispatches:
        assert dispatches <= max(batches, 1)


def test_dict_decode_has_no_search_loop(monkeypatch):
    # the compiled decode of a dictionary column: no `while` (a per-row
    # binary search is one), and three per-row gathers at most: the two
    # pool words and the dictionary. The Pallas bit-slice runs as a grid
    # loop in the CPU's interpreter, so its lax twin stands in for it.
    import re
    monkeypatch.setattr(PD.PK, "enabled", lambda: False)
    rng = np.random.default_rng(1)
    runs, _ = _run_table(_mixed_spec(rng, 12, 40))
    vcap = 4096
    planes = {k: jnp.asarray(v) for k, v in E._run_planes(runs).items()}
    planes["vocab"] = jnp.asarray(rng.normal(size=4096))
    planes["nnz"] = jnp.asarray([runs.total], jnp.int64)
    ec = E.EncodedColumn("dict", T.Float64Type(), planes,
                         (("vcap", vcap), ("nulls", False)))
    text = jax.jit(PD.decode_batch).lower(
        E.EncodedBatch([ec], runs.total, vcap)).compile().as_text()
    assert not re.search(r"\bwhile\(", text)
    per_row = re.findall(r"= \w+\[%d[,\]][^ ]* gather\(" % vcap, text)
    assert 1 <= len(per_row) <= 3, per_row
