"""Distinct aggregates on the SQL text path (plan/distinct.py: Spark's
one-distinct plan, a distinct level grouped by the keys plus the value and
a merge level over it) against a plain reference over the same seeded rows:
count, sum and avg of the distinct values beside aggregates over the same
column and over others, NULL in the value and in the keys, -0.0, 0.0 and
NaN in a double value, empty input and an all-NULL value, HAVING over a
distinct count, three distinct aggregates over one column, the rejection of
two distinct inputs, and query 28's shape: a cross join of six one-row
aggregates."""
import math

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.expr.core import SparkException
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession

N = 300
X_POOL = [-0.0, 0.0, float("nan"), 1.5, 2.25, -3.0, 7.0, 1e300, None]


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(20261017)
    pick = rng.integers(0, len(X_POOL), N)
    return {
        "k": [None if v == 3 else int(v) for v in rng.integers(0, 4, N)],
        "g": [["a", "b", None][v] for v in rng.integers(0, 3, N)],
        "x": [X_POOL[v] for v in pick],
        "i": [None if v < 2 else int(v) for v in rng.integers(0, 12, N)],
        "y": [float(v) for v in rng.integers(-50, 50, N)],
        "q": [int(v) for v in rng.integers(1, 31, N)],
    }


def _table(rows):
    return pa.table({"k": pa.array(rows["k"], pa.int64()),
                     "g": pa.array(rows["g"], pa.string()),
                     "x": pa.array(rows["x"], pa.float64()),
                     "i": pa.array(rows["i"], pa.int64()),
                     "y": pa.array(rows["y"], pa.float64()),
                     "q": pa.array(rows["q"], pa.int32())})


@pytest.fixture(scope="module")
def sess(rows):
    s = TpuSession()
    s.create_or_replace_temp_view("t", s.create_dataframe(_table(rows))
                                  .cache())
    return s


# -- the reference: plain Python over the rows --------------------------------

def _distinct(vals):
    """The distinct non-NULL values as Spark's distinct sees them: -0.0
    is 0.0, and every NaN is one value."""
    out = {}
    for v in vals:
        if v is None:
            continue
        if isinstance(v, float):
            v = v + 0.0                     # -0.0 + 0.0 is 0.0
        out.setdefault("nan" if v != v else v, v)
    return list(out.values())


def _sum(vals):
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return math.fsum(vals) if isinstance(vals[0], float) else sum(vals)


def _avg(vals):
    vals = [v for v in vals if v is not None]
    return _sum(vals) / len(vals) if vals else None


REF = {
    "count": lambda v: sum(v_ is not None for v_ in v),
    "sum": _sum,
    "avg": _avg,
    "min": lambda v: min((x for x in v if x is not None), default=None),
    "max": lambda v: max((x for x in v if x is not None), default=None),
}


def reference(rows, keys, aggs, keep=lambda r: True):
    """{key tuple: [aggregate values]}; aggs are (fn, column, distinct),
    column None for count(*)."""
    n = len(rows["x"])
    groups = {}
    for j in range(n):
        r = {c: rows[c][j] for c in rows}
        if keep(r):
            groups.setdefault(tuple(r[k] for k in keys), []).append(r)
    if not keys and not groups:
        groups[()] = []
    out = {}
    for key, rs in groups.items():
        vals = []
        for fn, c, dist in aggs:
            if c is None:
                vals.append(len(rs))
                continue
            col = [r[c] for r in rs]
            vals.append(REF[fn](_distinct(col) if dist else col))
        out[key] = vals
    return out


def _same(a, b):
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) or isinstance(b, float):
        if math.isnan(a) or math.isnan(b):
            return math.isnan(a) and math.isnan(b)
        return math.isclose(a, b, rel_tol=1e-12, abs_tol=1e-12)
    return a == b


def check(sess, rows, keys, aggs, where="", keep=lambda r: True,
          having=None):
    sel = [f"{fn}({'distinct ' if d else ''}{c or '*'})" for fn, c, d in aggs]
    sql = (f"select {', '.join(keys + sel)} from t {where}"
           + (f" group by {', '.join(keys)}" if keys else "")
           + (f" having {having[0]}" if having else ""))
    got = sess.sql(sql).to_pydict()
    cols = list(got.values())
    nk = len(keys)
    got_rows = {tuple(c[j] for c in cols[:nk]): [c[j] for c in cols[nk:]]
                for j in range(len(cols[0]))}
    want = reference(rows, keys, aggs, keep)
    if having:
        want = {k: v for k, v in want.items() if having[1](v)}
    assert set(got_rows) == set(want), sql
    for key, vals in want.items():
        assert all(_same(g, w) for g, w in zip(got_rows[key], vals)), \
            (sql, key, got_rows[key], vals)
    tree = sess._last_exec.tree_string()
    assert "[distinct level]" in tree and "[merge level]" in tree
    assert "HashAggregateExec" in tree and "Cpu" not in tree
    return got


#: what stands beside the distinct aggregate: over the same column and over
#: others
BESIDE = [("avg", "x", False), ("count", "x", False), ("sum", "i", False),
          ("min", "y", False), ("max", "i", False), ("count", None, False)]


@pytest.mark.parametrize("keys", [[], ["k"]], ids=["global", "grouped"])
@pytest.mark.parametrize("fn", ["count", "sum", "avg"])
def test_distinct_beside_others(sess, rows, keys, fn):
    check(sess, rows, keys, [(fn, "x", True)] + BESIDE)


#: an integer value's distinct aggregates beside a double's sum, and the
#: rows they are taken over: all, only those whose value is NULL, none
INT_AGGS = [("count", "i", True), ("sum", "i", True), ("avg", "i", True),
            ("sum", "y", False)]
WHERE = {"all": ("", lambda r: True),
         "all_null_value": ("where i is null", lambda r: r["i"] is None),
         "empty": ("where q > 100", lambda r: False)}


@pytest.mark.parametrize("keys", [[], ["g"]], ids=["global", "grouped"])
@pytest.mark.parametrize("rows_of", sorted(WHERE))
def test_distinct_of_an_integer_column(sess, rows, keys, rows_of):
    where, keep = WHERE[rows_of]
    got = check(sess, rows, keys, INT_AGGS, where=where, keep=keep)
    if rows_of == "empty" and not keys:
        # over no rows the counts are 0, the sums and averages NULL
        assert list(got.values()) == [[0], [None], [None], [None]]


def test_having_a_distinct_count(sess, rows):
    # two nullable keys; 8 of the 12 groups hold all 7 distinct x
    check(sess, rows, ["g", "k"], [("count", "x", True), ("sum", "y", False)],
          having=("count(distinct x) > 6", lambda v: v[0] > 6))


def test_dataframe_api_over_partitions(sess, rows):
    """Three partitions: each level a partial aggregate, an exchange by
    its keys and a final one."""
    df = sess.create_dataframe(_table(rows), num_partitions=3).group_by(
        "k").agg(F.count_distinct("i").alias("cd"),
                 F.sum_distinct("i").alias("sd"))
    got = df.to_pydict()
    want = reference(rows, ["k"], [("count", "i", True), ("sum", "i", True)])
    assert {(k,): [c, s] for k, c, s in zip(*got.values())} == want
    tree = sess._last_exec.tree_string()
    assert tree.count("ShuffleExchangeExec") == 2, tree
    assert "[merge level]" in tree and "[distinct level]" in tree


def test_two_distinct_inputs_are_rejected(sess):
    with pytest.raises(SparkException, match="more than one input"):
        sess.sql("select count(distinct x), count(distinct i) from t")
    with pytest.raises(SparkException, match="DISTINCT inside max"):
        sess.sql("select max(distinct x) from t")


def test_six_bands_cross_joined(sess, rows):
    """Query 28's shape: six one-row aggregates, each over a band of q and
    an OR of two ranges, cross joined into one row."""
    bands = [(1, 5, 0, 2), (6, 10, 1, 3), (11, 15, -10, 0), (16, 20, 5, 9),
             (21, 25, 2, 40), (26, 30, -3, 1)]
    parts, want = [], {}
    for b, (lo, hi, ylo, xhi) in enumerate(bands, 1):
        parts.append(
            f"(select avg(x) B{b}_LP, count(x) B{b}_CNT, "
            f"count(distinct x) B{b}_CNTD from t where q between {lo} and "
            f"{hi} and (y between {ylo} and {ylo} + 10 or x between 0 and "
            f"{xhi})) B{b}")

        def keep(r, lo=lo, hi=hi, ylo=ylo, xhi=xhi):
            x = r["x"]
            return lo <= r["q"] <= hi and (
                ylo <= r["y"] <= ylo + 10
                or (x is not None and x == x and 0 <= x <= xhi))
        (vals,) = reference(rows, [], [("avg", "x", False),
                                       ("count", "x", False),
                                       ("count", "x", True)], keep).values()
        want.update(zip([f"B{b}_LP", f"B{b}_CNT", f"B{b}_CNTD"], vals))
    got = sess.sql("select * from " + " cross join ".join(parts)
                   + " limit 100").to_pydict()
    assert list(got) == list(want)
    assert all(_same(got[c][0], w) for c, w in want.items()), (got, want)
