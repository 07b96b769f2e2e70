"""An ON clause is its equi pairs and the rest: the rest filters the input
it reads where the join's kind allows (plan/prune._push_on), or rides on
the join as its condition; a shape with no key pair is rejected with its
text. And TPC-H Q13, which is why: a LIKE over the null-supplying side of
a left outer join, the orders counted a key below the join."""
import os
import sys

import pyarrow as pa
import pytest

from spark_rapids_tpu.expr.core import SparkException
from spark_rapids_tpu.runtime import compile_cache as CC
from spark_rapids_tpu.runtime import obs
from spark_rapids_tpu.sql.session import TpuSession

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture()
def sess():
    s = TpuSession()
    a = pa.table({"ak": pa.array([1, 2, 3, 4, None], pa.int64()),
                  "av": pa.array([10, 20, 30, 40, 50], pa.int64())})
    b = pa.table({"bk": pa.array([1, 1, 2, 4, 4, 5, None], pa.int64()),
                  "bv": pa.array([1, 2, 3, 4, None, 6, 7], pa.int64()),
                  "bs": pa.array(["x", "keep", "keep", "x", "keep", "keep",
                                  "keep"], pa.string())})
    s.create_or_replace_temp_view("a", s.create_dataframe(a))
    s.create_or_replace_temp_view("b", s.create_dataframe(b))
    return s


def _rows(s, sql):
    d = s.sql(sql).to_pydict()
    return sorted(zip(*d.values()), key=repr)


#: (join kind, residual, the hand-built answer as (ak, av, bk, bv) rows)
_CASES = {
    # a conjunct over the null-supplying side filters that side: a left
    # row whose partners all fail it comes out once, null-filled
    "left_residual_on_right": ("left join", "bs = 'keep'", [
        (1, 10, 1, 2), (2, 20, 2, 3), (3, 30, None, None), (4, 40, 4, None),
        (None, 50, None, None)]),
    # a conjunct over the PRESERVED side is not a filter of it: the row
    # stays and finds no partner
    "left_residual_on_left": ("left join", "av > 15", [
        (1, 10, None, None), (2, 20, 2, 3), (3, 30, None, None),
        (4, 40, 4, 4), (4, 40, 4, None), (None, 50, None, None)]),
    "inner_residual_on_right": ("join", "bv >= 2", [
        (1, 10, 1, 2), (2, 20, 2, 3), (4, 40, 4, 4)]),
    "inner_residual_on_left": ("join", "av <> 20", [
        (1, 10, 1, 1), (1, 10, 1, 2), (4, 40, 4, 4), (4, 40, 4, None)]),
    "inner_residual_on_both": ("join", "av + bv > 12 and bs like '%e%'", [
        (2, 20, 2, 3)]),
    "right_residual_on_left": ("right join", "av < 40", [
        (1, 10, 1, 1), (1, 10, 1, 2), (2, 20, 2, 3), (None, None, 4, 4),
        (None, None, 4, None), (None, None, 5, 6), (None, None, None, 7)]),
    "right_residual_on_right": ("right join", "bv is not null", [
        (1, 10, 1, 1), (1, 10, 1, 2), (2, 20, 2, 3), (4, 40, 4, 4),
        (None, None, 4, None), (None, None, 5, 6), (None, None, None, 7)]),
}


@pytest.mark.parametrize("name", sorted(_CASES))
def test_on_residual_against_hand_built_answer(sess, name):
    kind, residual, want = _CASES[name]
    got = _rows(sess, f"select ak, av, bk, bv from a {kind} b "
                      f"on ak = bk and {residual}")
    assert got == sorted(want, key=repr)
    # the keys may be written right side first
    assert _rows(sess, f"select ak, av, bk, bv from a {kind} b "
                       f"on bk = ak and {residual}") == got


def test_residual_is_pushed_only_where_the_join_allows(sess):
    def tree(sql):
        sess.sql(sql).to_pydict()
        return sess._last_exec.tree_string()

    pushed = tree("select ak, bv from a left join b on ak = bk "
                  "and bs = 'keep'")
    assert "FilterExec" in pushed.split("BroadcastHashJoinExec")[1]
    kept = tree("select ak, bv from a left join b on ak = bk and av > 15")
    assert "Filter" not in kept


@pytest.mark.parametrize("on", [
    "ak < bk", "av > 15", "ak = bk or av = bv",
    "ak = bk and bv in (select av from a)"])
def test_rejected_on_shapes_raise_with_their_text(sess, on):
    with pytest.raises(SparkException) as e:
        sess.sql(f"select ak from a join b on {on}")
    assert "ON" in str(e.value) and ("ak" in str(e.value)
                                     or "av" in str(e.value))


def test_derived_table_alias_with_column_list(sess):
    got = _rows(sess, "select k, n from (select ak, count(*) from a "
                      "group by ak) as t (k, n) where k is not null")
    assert got == [(1, 1), (2, 1), (3, 1), (4, 1)]
    with pytest.raises(SparkException, match="names 1 columns"):
        sess.sql("select k from (select ak, av from a) as t (k)")


@pytest.mark.parametrize("seed", [7, 2**31 + 13])
def test_q13_against_the_plain_reference(seed):
    sys.path.insert(0, _BENCH)
    try:
        import datagen_text
        from reference import q13 as ref
        import run as harness
    finally:
        sys.path.remove(_BENCH)
    tables = datagen_text.generate(0.003, seed)
    # the text falls as it falls: make sure both words meet in some orders
    comments = tables["orders"]["o_comment"].to_pylist()
    assert 0 < (~ref.kept(comments)).sum() < len(comments) // 10
    s = TpuSession()
    for name in ("customer", "orders"):
        s.create_or_replace_temp_view(name, s.create_dataframe(
            harness.plain_strings(tables[name])).cache())
    before = CC.stats()
    df = s.sql(harness.load_query("q13"))
    assert df.to_pydict() == ref.answer(tables)
    after = CC.stats()
    # traces, not dispatches: a second seed meets the first one's program
    assert after["like_plane_traced"] >= 1
    assert after["like_nfa_traced"] == before["like_nfa_traced"]
    # the planner's choice, as explain shows it: the orders are counted a
    # key BELOW the join, whose output is the customers; the aggregate
    # took the LIKE's Filter in, and runs it as a program of its own where
    # the comments come flat (they do: the match is timed and counted)
    plan = df.explain("stages")
    below = plan.split("BroadcastHashJoinExec")[1]
    assert "[counts below join] [string match: apart over a flat column]" \
        in below
    rec = obs.recent_queries(1)[0]
    assert rec["counters"]["join_output_rows"] == tables["customer"].num_rows
    assert rec["counters"]["string_match_bytes"] == \
        sum(len(c.encode()) for c in comments) + 4 * (len(comments) + 1)
    assert rec["timers_ns"]["stringMatchTime"] > 0
