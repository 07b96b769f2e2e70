"""Pallas sorted-window segmented-reduction tests (interpret mode on the
CPU sim — the same kernel code that runs on hardware; an early round
read 1.9x over the scatter path on a v5e, not re-measured on today's
chip)."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql.session import TpuSession
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.expr.core import col, lit

from asserts import assert_tpu_and_cpu_are_equal_collect


@pytest.fixture
def session():
    return TpuSession()


def _tbl(n=8192, span=3000, seed=5, null_p=0.1):
    rng = np.random.default_rng(seed)
    v = rng.uniform(-1000, 1000, n)
    vals = [None if rng.random() < null_p else float(x) for x in v]
    return pa.table({
        "k": pa.array(rng.integers(0, span, n).astype(np.int64)),
        "v": pa.array(vals, pa.float64()),
        "w": pa.array(np.round(rng.uniform(0, 10, n), 3)),
    })


def _eligible_spy(monkeypatch):
    """Assert the pallas path was actually taken (not silently skipped)."""
    from spark_rapids_tpu.exec.tpu_nodes import _AggKernels
    taken = []
    orig = _AggKernels._pallas_seg_agg

    def spy(self, *a, **k):
        taken.append(True)
        return orig(self, *a, **k)

    monkeypatch.setattr(_AggKernels, "_pallas_seg_agg", spy)
    return taken


def test_pallas_segsum_groupby(session, monkeypatch):
    taken = _eligible_spy(monkeypatch)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_tbl()).group_by("k")
        .agg(F.sum(col("v")).alias("sv"), F.count(col("v")).alias("cv"),
             F.sum(col("w")).alias("sw")),
        session, approx_float=1e-9)
    assert taken, "pallas segsum path was not exercised"


def test_pallas_segsum_with_filter_mask(session, monkeypatch):
    taken = _eligible_spy(monkeypatch)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_tbl(seed=9)).filter(
            col("w") > lit(2.0)).group_by("k")
        .agg(F.sum(col("v")).alias("sv"), F.count(col("k")).alias("ck")),
        session, approx_float=1e-9)
    assert taken


def test_pallas_overflow_falls_back(session, monkeypatch):
    # force the in-graph fallback: a tiny MAX_GROUP_ROWS makes every
    # group "deep", so the scatter branch must produce the results
    from spark_rapids_tpu.ops import pallas_segsum as PS
    taken = _eligible_spy(monkeypatch)
    monkeypatch.setattr(PS, "MAX_GROUP_ROWS", 2)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_tbl(span=3000, seed=3)).group_by("k")
        .agg(F.sum(col("v")).alias("sv")),
        session, approx_float=1e-9)
    assert taken


def test_pallas_ineligible_shapes_still_correct(session):
    # strings keys / avg states stay on the scatter or sort paths
    t = _tbl(n=4096, span=50)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(t).group_by("k")
        .agg(F.avg(col("v")).alias("av"), F.min(col("w")).alias("mw")),
        session, approx_float=1e-9)


def test_pallas_nan_inf_falls_back(session, monkeypatch):
    # NaN/Inf inputs must take the scatter path (digit encoding with an
    # Inf-derived scale would zero every group) and still match the CPU
    # interpreter's Spark semantics
    taken = _eligible_spy(monkeypatch)
    rng = np.random.default_rng(17)
    n = 8192
    v = rng.uniform(-100, 100, n)
    v[5] = float("inf")
    v[77] = float("-inf")
    v[123] = float("nan")
    t = pa.table({"k": pa.array(rng.integers(0, 3000, n).astype(np.int64)),
                  "v": pa.array(v)})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(t).group_by("k")
        .agg(F.sum(col("v")).alias("sv")),
        session, approx_float=1e-9)
    assert taken


def _chunk_spy(monkeypatch):
    """Assert the CHUNKED pallas path was actually taken."""
    from spark_rapids_tpu.exec.tpu_nodes import _AggKernels
    taken = []
    orig = _AggKernels._chunked_pallas_agg

    def spy(self, *a, **k):
        taken.append(True)
        return orig(self, *a, **k)

    monkeypatch.setattr(_AggKernels, "_chunked_pallas_agg", spy)
    return taken


def _big_tbl(n, span, seed=21, null_p=0.08):
    rng = np.random.default_rng(seed)
    v = rng.uniform(-1000, 1000, n)
    mask = rng.random(n) < null_p
    va = pa.array(np.round(v, 3), pa.float64(), mask=mask)
    return pa.table({
        "k": pa.array(rng.integers(0, span, n).astype(np.int64)),
        "v": va,
    })


def test_chunked_pallas_groupby(session, monkeypatch):
    # cap 32768 = 2 chunks of a shrunken CHUNK_ROWS; span 1600 -> 11
    # packed bits -> nb 2048, so the 2*2048-row partial merge is cheap
    from spark_rapids_tpu.ops import pallas_segsum as PS
    monkeypatch.setattr(PS, "CHUNK_ROWS", 16384)
    taken = _chunk_spy(monkeypatch)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_big_tbl(32768, 1600)).group_by("k")
        .agg(F.sum(col("v")).alias("sv"), F.count(col("v")).alias("cv"),
             F.count(lit(1)).alias("ca")),
        session, approx_float=1e-9, ignore_order=True)
    assert taken, "chunked pallas path was not exercised"


def test_chunked_pallas_four_chunks_filter_mask(session, monkeypatch):
    # 4 chunks: span 1600 packs to 12 bits -> nb 4096, so the merge-cost
    # gate (k * nb <= CHUNK_ROWS) needs CHUNK_ROWS >= 16384
    from spark_rapids_tpu.ops import pallas_segsum as PS
    monkeypatch.setattr(PS, "CHUNK_ROWS", 16384)
    taken = _chunk_spy(monkeypatch)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_big_tbl(65536, 1600, seed=4))
        .filter(col("v") > lit(-500.0)).group_by("k")
        .agg(F.sum(col("v")).alias("sv"), F.count(col("k")).alias("ck")),
        session, approx_float=1e-9, ignore_order=True)
    assert taken


def test_chunked_pallas_nan_chunk_falls_back(session, monkeypatch):
    # NaN in ONE chunk: that chunk takes its scatter fallback, the other
    # chunks stay on the kernel; merged result still matches the CPU tier
    from spark_rapids_tpu.ops import pallas_segsum as PS
    monkeypatch.setattr(PS, "CHUNK_ROWS", 16384)
    taken = _chunk_spy(monkeypatch)
    rng = np.random.default_rng(11)
    n = 32768
    v = rng.uniform(-100, 100, n)
    v[20000] = float("nan")
    v[20001] = float("inf")
    t = pa.table({"k": pa.array(rng.integers(0, 1600, n).astype(np.int64)),
                  "v": pa.array(v)})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(t).group_by("k")
        .agg(F.sum(col("v")).alias("sv"), F.count(col("v")).alias("cv")),
        session, approx_float=1e-9, ignore_order=True)
    assert taken


def test_chunked_pallas_dict_string_key(session, monkeypatch):
    # dict-encoded string keys share one vocab across chunk partials;
    # vocab must exceed the tiny-bucket MXU limit (4096) to reach the
    # packed-radix path, and 5000 keys pack to 14 bits -> nb 16384
    from spark_rapids_tpu.ops import pallas_segsum as PS
    monkeypatch.setattr(PS, "CHUNK_ROWS", 32768)
    taken = _chunk_spy(monkeypatch)
    rng = np.random.default_rng(7)
    n = 65536
    vocab = [f"key_{i:04d}" for i in range(5000)]
    keys = [vocab[i] for i in rng.integers(0, len(vocab), n)]
    t = pa.table({"k": pa.array(keys),
                  "v": pa.array(np.round(rng.uniform(0, 50, n), 3))})
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(t).group_by("k")
        .agg(F.sum(col("v")).alias("sv")),
        session, approx_float=1e-9, ignore_order=True)
    assert taken
