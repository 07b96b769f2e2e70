"""The keyed in-core sort (exec/tpu_nodes._sort_in_core): ORDER BY as one
program a batch, against a plain Python sort of the same rows, and the
mechanism itself: one keyed dispatch, no device read-back, the string
key's width settled on the host."""
import datetime
import math

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import batch as B
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, LazyRowCount,
                                             from_arrow, to_arrow)
from spark_rapids_tpu.exec import fuse
from spark_rapids_tpu.exec import tpu_nodes as N
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.plan import nodes as P
from spark_rapids_tpu.plan.nodes import SortOrder
from spark_rapids_tpu.runtime import obs
from spark_rapids_tpu.runtime.obs import phases
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession

N_ROWS = 200


def _table(n=N_ROWS, seed=11) -> pa.Table:
    rng = np.random.default_rng(seed)

    def nulled(vals, every):
        return [None if i % every == 0 else v for i, v in enumerate(vals)]

    flags = ["A", "N", "R", "", "NO", "Ab"]
    floats = rng.choice([1.5, -2.25, float("nan"), -0.0, 0.0, float("inf"),
                         float("-inf"), 1e300, -1e-300], n)
    # flat layout needs more distinct values than max(64, n // 2)
    words = ["%s%03d" % ("xy" * int(rng.integers(0, 9)), i % 150)
             for i in rng.permutation(n)]
    return pa.table({
        "ds": pa.array(nulled(list(rng.choice(flags, n)), 7)),
        "fs": pa.array(nulled(words, 13)),
        "i": pa.array(nulled([int(v) for v in rng.integers(-5, 6, n)], 5),
                      pa.int64()),
        "d": pa.array(nulled([datetime.date(1995, 1, 1)
                              + datetime.timedelta(int(v))
                              for v in rng.integers(-400, 400, n)], 11)),
        "f": pa.array(nulled(list(floats), 9), pa.float64()),
        "rid": pa.array(list(range(n)), pa.int32()),
    })


def _value_key(v):
    """Spark's order of one non-null value: NaN above +inf, -0.0 = 0.0,
    strings by their UTF-8 bytes."""
    if isinstance(v, float):
        return (1, 0.0) if math.isnan(v) else (0, v + 0.0)
    if isinstance(v, str):
        return v.encode("utf-8")
    return v


def _plain_sort(rows, orders):
    """Stable multi-key sort, last key first; `orders` holds
    (column, ascending, nulls_first)."""
    rows = list(rows)
    for name, asc, nulls_first in reversed(orders):
        nulls = [r for r in rows if r[name] is None]
        vals = sorted((r for r in rows if r[name] is not None),
                      key=lambda r: _value_key(r[name]), reverse=not asc)
        rows = nulls + vals if nulls_first else vals + nulls
    return rows


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for k in w:
            a, b = g[k], w[k]
            if isinstance(b, float) and math.isnan(b):
                assert isinstance(a, float) and math.isnan(a), (k, g, w)
            else:
                assert a == b, (k, g, w)
        # -0.0 and 0.0 tie, so the row id says which one came first
        assert g["rid"] == w["rid"]


def _orders(spec):
    return [SortOrder(col(n), ascending=a, nulls_first=nf)
            for n, a, nf in spec]


@pytest.fixture(scope="module")
def table():
    return _table()


@pytest.mark.parametrize("nulls_first", [True, False])
@pytest.mark.parametrize("asc", [True, False])
@pytest.mark.parametrize("key", ["ds", "fs", "i", "d", "f"])
def test_one_key_matches_plain_sort(table, key, asc, nulls_first):
    spec = [(key, asc, nulls_first)]
    s = TpuSession()
    df = s.create_dataframe(table).order_by(*_orders(spec))
    assert "SortExec" in df.explain("stages")
    _same(df.collect().to_pylist(), _plain_sort(table.to_pylist(), spec))


@pytest.mark.parametrize("spec", [
    [("ds", True, True), ("f", False, False)],
    [("i", False, True), ("fs", True, False), ("d", False, True)],
    [("f", True, False), ("ds", False, True), ("i", True, True)],
    [("d", True, True), ("ds", True, False)],
], ids=["dict_double", "int_flat_date", "double_dict_int", "date_dict"])
def test_many_keys_match_plain_sort(table, spec):
    s = TpuSession()
    df = s.create_dataframe(table).order_by(*_orders(spec))
    _same(df.collect().to_pylist(), _plain_sort(table.to_pylist(), spec))


@pytest.mark.parametrize("case", ["masked_small", "masked_large", "empty",
                                  "computed_string_key"])
def test_shapes_of_input(case):
    """A masked batch sorted in the program (small) or compacted first
    (capacity above _SORT_COMPACT_ABOVE), an empty one, and a string key
    that is computed, whose width is read back."""
    n = 40000 if case == "masked_large" else N_ROWS
    table = _table(n)
    rows = table.to_pylist()
    s = TpuSession()
    df = s.create_dataframe(table)
    if case == "empty":
        df = df.filter(col("i") > lit(100))
        rows = []
    elif case.startswith("masked"):
        df = df.filter(col("i") > lit(0))
        rows = [r for r in rows if r["i"] is not None and r["i"] > 0]
    if case == "computed_string_key":
        df = df.with_column("u", F.upper(col("fs")))
        rows = [dict(r, u=None if r["fs"] is None else r["fs"].upper())
                for r in rows]
        spec = [("u", False, False), ("i", True, True)]
    else:
        spec = [("ds", True, False), ("f", False, True)]
    got = df.order_by(*_orders(spec)).collect().to_pylist()
    _same(got, _plain_sort(rows, spec))


def _bound(spec, schema):
    return P.Sort(_orders(spec), _Leaf(schema)).orders


class _Leaf(P.PlanNode):
    def __init__(self, schema):
        self.children = []
        self._schema = schema

    @property
    def schema(self):
        return self._schema


def _schema_of(table):
    from spark_rapids_tpu import types as T
    return T.Schema.of(*[(f.name, T.from_arrow(f.type))
                         for f in table.schema])


@pytest.mark.parametrize("count", ["host", "lazy", "lazy_masked"])
def test_row_count_rides_through(table, count):
    """The batch goes in and comes out with the row count it had: a host
    int stays one, a lazy count stays lazy and is not forced."""
    spec = [("fs", True, True), ("i", False, False)]
    b = from_arrow(table)
    rows = table.to_pylist()
    if count == "lazy":
        b = ColumnarBatch(b.columns, LazyRowCount(jnp.int32(N_ROWS)))
    elif count == "lazy_masked":
        mask = jnp.arange(b.capacity) % 3 == 1
        mask = mask & (jnp.arange(b.capacity) < N_ROWS)
        b = ColumnarBatch(b.columns,
                          LazyRowCount(jnp.sum(mask.astype(jnp.int32))), mask)
        rows = [r for r in rows if r["rid"] % 3 == 1]
    wait0 = phases.device_wait_ns
    out = N._sort_in_core(_bound(spec, _schema_of(table)), b)
    assert phases.device_wait_ns == wait0
    assert out.row_mask is None and out.capacity == b.capacity
    if count == "host":
        assert out.num_rows == N_ROWS and isinstance(out.num_rows, int)
    else:
        assert out.num_rows is b.num_rows
        assert not out.num_rows.is_materialized
    _same(to_arrow(out, table.schema.names).to_pylist(),
          _plain_sort(rows, spec))


def _q1_shape():
    """What reaches Q1's SortExec: capacity 1,024, 4 rows, 10 columns, the
    two keys dictionary strings whose width no operator stamped."""
    t = pa.table({
        "l_returnflag": ["R", "N", "A", "N"],
        "l_linestatus": ["F", "O", "F", "F"],
        **{f"m{i}": pa.array([1.0 * i, 2.0, 3.0, 4.0]) for i in range(7)},
        "count_order": pa.array([4, 3, 2, 1], pa.int64()),
    })
    small = from_arrow(t)
    idx = jnp.where(jnp.arange(1024) < 4, jnp.arange(1024), -1)
    b = K.gather_batch(small, idx.astype(jnp.int32), 4)
    for c in b.columns:
        c.str_width = None
    return t, b


def test_q1_shape_is_one_keyed_dispatch_and_no_read_back(monkeypatch):
    t, b = _q1_shape()
    assert b.capacity == 1024 and b.num_cols == 10
    assert b.columns[0].is_dict and b.columns[1].is_dict
    spec = [("l_returnflag", True, True), ("l_linestatus", True, True)]
    orders = _bound(spec, _schema_of(t))

    def no_read_back(dev):
        raise AssertionError("host_int on the sort's path")
    monkeypatch.setattr(K, "host_int", no_read_back)
    monkeypatch.setattr(B, "host_int", no_read_back)
    keys = []
    fuse.set_dispatch_hook(keys.append)
    try:
        n0, wait0 = phases.keyed_dispatches, phases.device_wait_ns
        out = N._sort_in_core(orders, b)
        assert phases.keyed_dispatches == n0 + 1
        assert phases.device_wait_ns == wait0
    finally:
        fuse.set_dispatch_hook(None)
    assert len(keys) == 1 and keys[0][0] == "sort"
    assert keys[0][2] == (1, 1)  # one 8-byte chunk a key, from the host
    got = to_arrow(out, t.schema.names).to_pydict()
    assert got["l_returnflag"] == ["A", "N", "N", "R"]
    assert got["l_linestatus"] == ["F", "F", "O", "F"]
    assert got["count_order"] == [2, 1, 3, 4]


def test_query_account_counts_one_keyed_sort(table):
    """Through a session: the ORDER BY is the query's one keyed program
    (`counters.keyed_dispatches`), its time is in `timers_ns.sortTime`,
    and `explain("stages")` marks the stage."""
    s = TpuSession()
    df = s.create_dataframe(table).order_by(
        *_orders([("ds", True, True), ("fs", False, False)]))
    assert "[keyed: sort]" in df.explain("stages")
    df.collect()
    rec = obs.recent_queries(1)[0]
    assert rec["counters"]["keyed_dispatches"] == 1
    assert rec["timers_ns"]["sortTime"] > 0


@pytest.mark.parametrize("case", ["stamp_dict", "stamp_flat", "tiny_vocab",
                                  "gathered", "concat", "unknown"])
def test_string_width_comes_from_the_host(case, monkeypatch):
    """`static_string_chunks`: the width stamped at upload (carried over
    gathers and concats), else a vocabulary of at most 8 bytes; else
    None, and `string_chunk_count` reads the device once."""
    long_words = ["a" * 17, "b", "c" * 9] * 50        # 3 entries: dict
    flat_words = ["w%05d" % i for i in range(300)]    # all distinct: flat
    calls = []
    real = K.host_int
    monkeypatch.setattr(K, "host_int",
                        lambda d: calls.append(1) or real(d))
    if case == "stamp_dict":
        c = from_arrow(pa.table({"s": long_words})).columns[0]
        assert c.is_dict and c.str_width == 17
        want = 4                                       # 3 chunks -> 4
    elif case == "stamp_flat":
        c = from_arrow(pa.table({"s": flat_words})).columns[0]
        assert not c.is_dict and c.str_width == 6
        want = 1
    elif case == "tiny_vocab":
        c = from_arrow(pa.table({"s": ["A", "N", "R"] * 10})).columns[0]
        c.str_width = None
        want = 1
    elif case == "gathered":
        b = from_arrow(pa.table({"s": long_words}))
        idx = jnp.arange(b.capacity, dtype=jnp.int32)[::-1]
        c = K.gather_batch(b, idx, 150).columns[0]
        want = 4
    elif case == "concat":
        b1 = from_arrow(pa.table({"s": long_words}))
        b2 = from_arrow(pa.table({"s": ["d" * 40, "e"] * 20}))
        c = K.concat_batches([b1, b2]).columns[0]
        assert c.str_width == 40
        want = 8                                       # 5 chunks -> 8
    else:
        c = from_arrow(pa.table({"s": long_words})).columns[0]
        c.str_width = None
        assert K.static_string_chunks(c) is None
        assert K.string_chunk_count(c) == 4 and calls == [1]
        return
    assert K.static_string_chunks(c) == want
    assert K.string_chunk_count(c) == want and not calls
