"""Counts over a join's right side, computed a key below the join
(plan/prune._counts_below_join), against hand-built answers: duplicate left
keys, several counts over nullable columns, a key whose values are all
NULL, a left row without a partner; and the global aggregate, which is
left as it was (its count over no row is 0, where a sum of no counts would
be NULL)."""
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql.session import TpuSession


@pytest.fixture(scope="module")
def sess():
    s = TpuSession()
    a = pa.table({"ak": pa.array([1, 1, 2, 3, 4, None], pa.int64()),
                  "ag": pa.array(["p", "p", "q", "s", "r", "r"]),
                  "av": pa.array([10, 11, 20, 30, 40, 50], pa.int64())})
    b = pa.table({"bk": pa.array([1, 1, 2, 4, 4, 5, None], pa.int64()),
                  "bv": pa.array([1, 2, 3, None, None, 6, 7], pa.int64()),
                  "bw": pa.array([None, 2, 3, 4, None, 6, 7], pa.int64())})
    s.create_or_replace_temp_view("a", s.create_dataframe(a))
    s.create_or_replace_temp_view("b", s.create_dataframe(b))
    return s


def _run(s, sql):
    df = s.sql(sql)
    d = df.to_pydict()
    return sorted(zip(*d.values()), key=repr), df.explain("stages")


#: (join kind, select list and grouping, hand-built rows)
_GROUPED = {
    # key 1 is on two left rows of group p, each with two partners; key 4's
    # bv are all NULL; key 3 and the NULL key have no partner
    "left_two_counts": ("left join", [
        ("p", 4, 2), ("q", 1, 1), ("r", 0, 1), ("s", 0, 0)]),
    # the group of key 4 stays (its pairs exist, their bv is NULL: 0); the
    # group of key 3 goes with its row
    "inner_two_counts": ("join", [("p", 4, 2), ("q", 1, 1), ("r", 0, 1)]),
}


@pytest.mark.parametrize("name", sorted(_GROUPED))
def test_grouped_counts_below_join(sess, name):
    kind, want = _GROUPED[name]
    got, plan = _run(sess, f"select ag, count(bv), count(bw) from a {kind} b "
                           f"on ak = bk group by ag")
    assert got == sorted(want, key=repr)
    assert "[counts below join]" in plan


def test_grouped_by_the_join_key_with_duplicates(sess):
    got, plan = _run(sess, "select ak, count(bv) from a left join b "
                           "on ak = bk group by ak")
    assert got == sorted([(1, 4), (2, 1), (3, 0), (4, 0), (None, 0)],
                         key=repr)
    assert "[counts below join]" in plan


#: (join kind, WHERE, the one row of the global count(bv), count(bw))
_GLOBAL = {
    "inner": ("join", "", (5, 4)),
    "left": ("left join", "", (5, 4)),
    "inner_empty": ("join", "where av > 1000", (0, 0)),
    "left_empty": ("left join", "where av > 1000", (0, 0)),
    "inner_no_partner": ("join", "where ak = 3", (0, 0)),
    "left_no_partner": ("left join", "where ak = 3", (0, 0)),
}


@pytest.mark.parametrize("name", sorted(_GLOBAL))
def test_global_count_over_a_join_is_left_alone(sess, name):
    kind, where, want = _GLOBAL[name]
    got, plan = _run(sess, f"select count(bv), count(bw) from a {kind} b "
                           f"on ak = bk {where}")
    assert got == [want]
    assert "[counts below join]" not in plan


@pytest.mark.parametrize("sql", [
    # a count over the LEFT side, a sum, a count(*) and a group over the
    # right side are not this rewrite's
    "select ag, count(av) from a left join b on ak = bk group by ag",
    "select ag, sum(bv) from a left join b on ak = bk group by ag",
    "select ag, count(*) from a left join b on ak = bk group by ag",
    "select bw, count(bv) from a left join b on ak = bk group by bw",
    "select ag, count(bv) from a right join b on ak = bk group by ag"])
def test_other_shapes_are_left_alone(sess, sql):
    _, plan = _run(sess, sql)
    assert "[counts below join]" not in plan
