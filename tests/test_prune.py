"""Column pruning pass: Project-over-Join/Window pushes used columns
below the operator, and a Parquet scan reads only the columns the plan
above it can reach (plan/prune.py); results stay identical."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.plan import nodes as P
from spark_rapids_tpu.plan import prune as PR
from spark_rapids_tpu.runtime import obs
from spark_rapids_tpu.sql.session import TpuSession
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.expr.core import col, lit


def test_join_prune_plan_shape_and_result():
    s = TpuSession()
    left = s.create_dataframe({"k": [1, 2, 3, 4], "a": [10, 20, 30, 40],
                               "b": [1.0, 2.0, 3.0, 4.0],
                               "unused1": [0, 0, 0, 0]})
    right = s.create_dataframe({"rk": [2, 3, 5], "c": [200, 300, 500],
                                "unused2": [9, 9, 9]})
    j = left.join(right, on=[(col("k"), col("rk"))], how="inner")
    out = j.select(col("k"), col("c"))
    from spark_rapids_tpu.plan.prune import prune_plan
    import spark_rapids_tpu.plan.nodes as P
    pruned = prune_plan(out.plan)
    # the join's children should now carry only the used subsets
    join_node = pruned.children[0]
    assert isinstance(join_node, P.Join)
    assert join_node.children[0].schema.names == ["k"]
    assert set(join_node.children[1].schema.names) == {"rk", "c"}
    d = out.to_pydict()
    assert sorted(zip(d["k"], d["c"])) == [(2, 200), (3, 300)]


def test_join_prune_with_condition_result():
    s = TpuSession()
    left = s.create_dataframe({"k": [1, 1, 2], "x": [5, 6, 7],
                               "dead": [0, 0, 0]})
    right = s.create_dataframe({"rk": [1, 2], "y": [5, 9],
                                "dead2": [1, 1]})
    j = left.join(right, on=(col("k") == col("rk")) & (col("x") > col("y")),
                  how="inner")
    out = j.select(col("k"), col("x"), col("y"))
    d = out.to_pydict()
    rows = sorted(zip(d["k"], d["x"], d["y"]))
    assert rows == [(1, 6, 5)]


def test_window_prune_plan_shape_and_result():
    s = TpuSession()
    from spark_rapids_tpu.expr.window import Window
    t = pa.table({
        "g": pa.array([1, 1, 2, 2, 2], type=pa.int64()),
        "o": pa.array([3, 1, 2, 5, 4], type=pa.int64()),
        "v": pa.array([1.0, 2.0, 3.0, 4.0, 5.0]),
        "unused": pa.array([0, 0, 0, 0, 0], type=pa.int64()),
    })
    df = s.create_dataframe(t)
    w = Window.partition_by(col("g")).order_by(col("o"))
    out = df.select(col("g"), F.rank().over(w).alias("rk"))
    from spark_rapids_tpu.plan.prune import prune_plan
    import spark_rapids_tpu.plan.nodes as P
    pruned = prune_plan(out.plan)
    wn = pruned.children[0]
    assert isinstance(wn, P.WindowNode)
    assert set(wn.children[0].schema.names) == {"g", "o"}
    d = out.to_pydict()
    got = sorted(zip(d["g"], d["rk"]))
    assert got == [(1, 1), (1, 2), (2, 1), (2, 2), (2, 3)]


# ---------------------------------------------------------------------------
# the third rewrite: columns pruned into the Parquet scan
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _query_text(name: str) -> str:
    with open(os.path.join(_REPO, "benchmark", "queries", name + ".sql")) as f:
        return "\n".join(ln for ln in f.read().splitlines()
                         if not ln.lstrip().startswith("--"))


def _lineitem(n: int = 6000) -> pa.Table:
    rng = np.random.default_rng(29)
    day = np.sort(rng.integers(8000, 10600, n)).astype("int32")
    return pa.table({
        "l_orderkey": rng.integers(0, 600, n),
        "l_partkey": np.arange(n),
        "l_quantity": rng.integers(1, 50, n).astype("float64"),
        "l_extendedprice": np.round(rng.random(n) * 1000, 2),
        "l_discount": rng.integers(0, 11, n) / 100,
        "l_tax": rng.integers(0, 9, n) / 100,
        "l_returnflag": [("A", "N", "R")[i % 3] for i in range(n)],
        "l_linestatus": [("F", "O")[i % 2] for i in range(n)],
        "l_shipdate": pa.array(day, pa.int32()).cast(pa.date32()),
        "l_comment": [f"c{i % 97}" for i in range(n)],
    })


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    """lineitem (10 columns, three row groups in l_partkey order), orders,
    customer, and a hive-partitioned copy of orders."""
    d = tmp_path_factory.mktemp("lake")
    rng = np.random.default_rng(3)
    orders = pa.table({
        "o_orderkey": np.arange(600),
        "o_custkey": rng.integers(0, 60, 600),
        "o_orderdate": pa.array(rng.integers(8000, 10000, 600)
                                .astype("int32"), pa.int32())
        .cast(pa.date32()),
        "o_shippriority": np.zeros(600, "int32"),
        "o_comment": [f"x{i}" for i in range(600)],
    })
    customer = pa.table({
        "c_custkey": np.arange(60),
        "c_mktsegment": [("BUILDING", "AUTOMOBILE")[i % 2]
                         for i in range(60)],
        "c_name": [f"n{i}" for i in range(60)],
    })
    paths = {}
    for name, t in (("lineitem", _lineitem()), ("orders", orders),
                    ("customer", customer)):
        paths[name] = str(d / f"{name}.parquet")
        pq.write_table(t, paths[name], row_group_size=2000)
    paths["hive"] = str(d / "hive")
    TpuSession().create_dataframe(orders).write \
        .partition_by("o_shippriority").parquet(paths["hive"])
    return paths


def _views(s, lake, **columns):
    for name in ("lineitem", "orders", "customer"):
        s.create_or_replace_temp_view(
            name, s.read_parquet(lake[name], columns=columns.get(name)))


def _q6(s, lake):
    _views(s, lake)
    return s.sql(_query_text("q6"))


def _q3(s, lake):
    _views(s, lake)
    return s.sql(_query_text("q3"))


def _pushed_only(s, lake):
    # l_partkey is read by the pushed filter alone; the file is in its
    # order, so the footer's statistics refute two of three row groups
    _views(s, lake)
    return s.sql("select sum(l_quantity) as q from lineitem "
                 "where l_partkey < 1500")


def _count_star(s, lake):
    _views(s, lake)
    return s.sql("select count(*) as n from lineitem")


def _hive_partition(s, lake):
    return s.read_parquet(lake["hive"]).group_by("o_shippriority") \
        .agg(F.count(col("o_shippriority")).alias("n"))


def _caller_columns(s, lake):
    _views(s, lake, lineitem=["l_comment", "l_discount", "l_orderkey",
                              "l_quantity"])
    return s.sql("select sum(l_quantity) as q, max(l_orderkey) as k "
                 "from lineitem")


def _cached(s, lake):
    s.create_or_replace_temp_view(
        "lineitem", s.read_parquet(lake["lineitem"]).cache())
    return s.sql("select sum(l_quantity) as q from lineitem")


def _self_join(s, lake):
    _views(s, lake)
    a, b = s.table("orders"), s.table("orders")
    assert a.plan is b.plan  # one view, one scan object
    b = b.select(col("o_orderkey").alias("k2"), col("o_comment").alias("c2"))
    return a.join(b, on=[(col("o_orderkey"), col("k2"))], how="inner") \
        .filter(col("o_custkey") < lit(5)) \
        .select(col("o_orderkey"), col("o_custkey"), col("c2"))


#: case -> (query, the columns each ParquetScan leaf reads after pruning,
#: leaves left to right; None for a scan that must stay whole)
_SCAN_CASES = {
    "q6_shape": (_q6, [["l_quantity", "l_extendedprice", "l_discount",
                        "l_shipdate"]]),
    "q3_shape": (_q3, [["c_custkey", "c_mktsegment"],
                       ["o_orderkey", "o_custkey", "o_orderdate",
                        "o_shippriority"],
                       ["l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate"]]),
    "pushed_filter_only": (_pushed_only, [["l_partkey", "l_quantity"]]),
    # no column of the files is named: the narrowest one carries the rows
    "count_star": (_count_star, [["l_shipdate"]]),
    "hive_partition": (_hive_partition, [["o_orderdate", "o_shippriority"]]),
    "caller_columns": (_caller_columns, [["l_orderkey", "l_quantity"]]),
    "cached_relation": (_cached, [None]),
    "self_join": (_self_join, [["o_orderkey", "o_custkey"],
                               ["o_orderkey", "o_comment"]]),
}


def _scan_leaves(plan):
    if isinstance(plan, P.ParquetScan):
        return [plan]
    return [s for c in plan.children for s in _scan_leaves(c)]


def _rows(d):
    return sorted(zip(*d.values()), key=repr)


@pytest.mark.parametrize("device_decode", ["true", "false"])
@pytest.mark.parametrize("case", list(_SCAN_CASES))
def test_scan_columns_pruned_plan_and_result(case, device_decode, lake,
                                             monkeypatch):
    query, want = _SCAN_CASES[case]
    s = TpuSession({"spark.rapids.sql.decode.device.enabled": device_decode})
    df = query(s, lake)
    whole = [sc.schema.names for sc in _scan_leaves(df.plan)]
    out_schema = df.plan.schema
    pruned = PR.prune_plan(df.plan)
    assert pruned.schema == out_schema
    scans = _scan_leaves(pruned)
    assert len(scans) == len(want)
    for sc, names, all_names in zip(scans, want, whole):
        if names is None:
            assert sc.narrowed_from is None
            assert sc.schema.names == all_names
            continue
        assert sc.schema.names == names and sc.columns == names
        assert sc.narrowed_from is not None
        assert sc.describe() == (f"ParquetScan[{len(sc.paths)} files, "
                                 f"{len(names)} of {len(all_names)} columns]")
    got = query(s, lake).to_pydict()
    rec = obs.recent_queries(1)[0]["counters"]
    lm = s.last_metrics()
    assert rec["scan_columns_read"] == sum(
        len(n or a) for n, a in zip(want, whole))
    if case == "pushed_filter_only":
        assert sum(m.get("numRowGroupsPruned", 0) for m in lm.values()) == 2
    # the same query with the scans left whole
    monkeypatch.setattr(PR, "_narrow_scan", lambda sc, req: (sc, None))
    ref = query(s, lake)
    assert all(sc.narrowed_from is None
               for sc in _scan_leaves(PR.prune_plan(ref.plan)))
    assert _rows(ref.to_pydict()) == _rows(got)
    assert obs.recent_queries(1)[0]["counters"]["scan_columns_pruned"] == 0


def test_view_scan_is_never_narrowed_in_place(lake):
    s = TpuSession()
    _views(s, lake)
    view = s.table("lineitem").plan
    q1 = s.sql("select sum(l_quantity) as q from lineitem").to_pydict()
    q2 = s.sql("select max(l_comment) as c, min(l_tax) as t, count(*) as n "
               "from lineitem where l_partkey >= 0").to_pydict()
    assert isinstance(view, P.ParquetScan) and view.columns is None
    assert view.narrowed_from is None and len(view.schema.fields) == 10
    assert s.table("lineitem").plan is view
    t = _lineitem()
    assert q1["q"] == [float(np.sum(t["l_quantity"].to_numpy()))]
    assert q2 == {"c": ["c96"], "t": [0.0], "n": [t.num_rows]}


def test_prune_is_identity_without_a_parquet_scan():
    """The resident cells' plans (Q1 and Q6 over a cached in-memory table)
    come out as the bottom-up rewrites alone leave them, node for node,
    with the digests the tree before PR 29 gave them."""
    from spark_rapids_tpu.runtime.obs.history import plan_digest
    s = TpuSession()
    s.create_or_replace_temp_view(
        "lineitem", s.create_dataframe(_lineitem(1000)).cache())

    def nodes(p):
        return [p] + [n for c in p.children for n in nodes(c)]

    for q, digest in (("q1", "229c657c20ae44ca"), ("q6", "9bc0bfe03c3cfd19")):
        before = nodes(PR._prune_bottom_up(s.sql(_query_text(q)).plan))
        plan = s.sql(_query_text(q)).plan
        after = nodes(PR.prune_plan(plan))
        assert [type(n) for n in after] == [type(n) for n in before]
        assert after[0].tree_string() == before[0].tree_string()
        # nothing rebuilt: the nodes are the plan's own or the bottom-up
        # pass's, and a second pass finds them as they are
        assert [id(n) for n in nodes(PR.prune_plan(after[0]))] == \
            [id(n) for n in after]
        assert plan_digest(after[0]) == digest


def test_phase_account_reads_the_pruned_scan(lake, monkeypatch):
    s = TpuSession()
    _q6(s, lake).to_pydict()
    pruned = obs.recent_queries(1)[0]["counters"]
    monkeypatch.setattr(PR, "_narrow_scan", lambda sc, req: (sc, None))
    _q6(s, lake).to_pydict()
    whole = obs.recent_queries(1)[0]["counters"]
    assert (pruned["scan_columns_read"], pruned["scan_columns_pruned"]) \
        == (4, 6)
    assert (whole["scan_columns_read"], whole["scan_columns_pruned"]) \
        == (10, 0)
    assert 0 < pruned["upload_bytes"] < whole["upload_bytes"]
    lm = s.last_metrics()
    assert any(m.get("numScanColumns") == 10 for m in lm.values())
