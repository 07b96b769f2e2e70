"""The generator: the spec's row counts, domains and correlations; the same
seed gives the same tables; the Parquet written holds the tables cached."""
import numpy as np
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

import datagen
import run as harness

SF = 0.01


@pytest.fixture(scope="module")
def tables():
    return datagen.generate(SF, 2**31 + 11)


def test_row_counts_follow_the_spec(tables):
    assert tables["customer"].num_rows == 150_000 * SF
    assert tables["orders"].num_rows == 1_500_000 * SF
    n_l = tables["lineitem"].num_rows
    assert n_l == datagen.row_counts(SF)["lineitem"]
    # 1..7 lines an order in equal shares: 4 a order, as the spec's mean
    assert abs(n_l / tables["orders"].num_rows - 4.0) < 0.05
    lines = pc.value_counts(tables["lineitem"]["l_orderkey"])
    counts = np.asarray(lines.field("counts"))
    assert counts.min() == 1 and counts.max() == 7


def test_same_seed_same_tables(tables):
    again = datagen.generate(SF, 2**31 + 11)
    other = datagen.generate(SF, 5)
    for name in tables:
        assert tables[name].equals(again[name])
        assert not tables[name].equals(other[name])
        assert tables[name].num_rows == other[name].num_rows


def _np(table, col):
    c = table[col].combine_chunks()
    if str(c.type).startswith("date32"):
        c = c.cast("int32")
    return c.to_numpy(zero_copy_only=False)


def test_columns_and_domains(tables):
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    assert cu.column_names == [
        "c_custkey", "c_name", "c_address", "c_nationkey", "c_phone",
        "c_acctbal", "c_mktsegment", "c_comment"]
    assert od.column_names == [
        "o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
        "o_orderdate", "o_orderpriority", "o_clerk", "o_shippriority",
        "o_comment"]
    assert li.column_names == [
        "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
        "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
        "l_shipinstruct", "l_shipmode", "l_comment"]
    n_c = cu.num_rows
    okey = _np(od, "o_orderkey")
    assert len(np.unique(okey)) == len(okey) and (np.diff(okey) > 0).all()
    assert ((okey - 1) % 32 < 8).all()                  # sparse keys
    ocust = _np(od, "o_custkey")
    assert (ocust % 3 != 0).all() and ocust.min() >= 1 and ocust.max() <= n_c
    odate = _np(od, "o_orderdate")
    assert odate.min() >= datagen.EPOCH_1992_01_01
    assert odate.max() <= datagen.ORDERDATE_MAX
    assert set(pc.unique(cu["c_mktsegment"]).to_pylist()) == set(
        datagen.SEGMENTS)
    assert (_np(od, "o_shippriority") == 0).all()
    for col, lo, hi in (("l_quantity", 1, 50), ("l_discount", 0.0, 0.10),
                        ("l_tax", 0.0, 0.08), ("l_linenumber", 1, 7)):
        x = _np(li, col)
        assert x.min() == lo and x.max() == hi, col
    assert _np(cu, "c_acctbal").min() >= -999.99
    assert _np(cu, "c_acctbal").max() <= 9999.99
    lens = pc.utf8_length(li["l_comment"].cast("string")).to_numpy()
    assert lens.min() >= 10 and lens.max() <= 43
    lens = pc.utf8_length(od["o_comment"].cast("string")).to_numpy()
    assert lens.min() >= 19 and lens.max() <= 78
    assert cu["c_phone"][0].as_py()[2] == "-" and \
        len(cu["c_name"][0].as_py()) == 18


def test_correlations(tables):
    od, li = tables["orders"], tables["lineitem"]
    okey, odate = _np(od, "o_orderkey"), _np(od, "o_orderdate")
    lkey = _np(li, "l_orderkey")
    assert (np.diff(lkey) >= 0).all()                   # order-key order
    l_odate = odate[np.searchsorted(okey, lkey)]
    ship, commit, receipt = (_np(li, c) for c in (
        "l_shipdate", "l_commitdate", "l_receiptdate"))
    assert ((ship - l_odate >= 1) & (ship - l_odate <= 121)).all()
    assert ((commit - l_odate >= 30) & (commit - l_odate <= 90)).all()
    assert ((receipt - ship >= 1) & (receipt - ship <= 30)).all()
    flag = np.asarray(li["l_returnflag"].cast("string").to_pylist())
    status = np.asarray(li["l_linestatus"].cast("string").to_pylist())
    assert (flag[receipt > datagen.CURRENTDATE] == "N").all()
    assert np.isin(flag[receipt <= datagen.CURRENTDATE], ["R", "A"]).all()
    assert ((status == "O") == (ship > datagen.CURRENTDATE)).all()
    # l_extendedprice = l_quantity * the part's retail price, in cents
    pk = _np(li, "l_partkey")
    retail = (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100.0
    assert np.allclose(_np(li, "l_extendedprice"),
                       _np(li, "l_quantity") * retail, rtol=0, atol=0.005)
    # o_totalprice and o_orderstatus follow from the order's lines
    first = np.flatnonzero(np.r_[True, lkey[1:] != lkey[:-1]])
    total = np.add.reduceat(
        _np(li, "l_extendedprice") * (1 + _np(li, "l_tax"))
        * (1 - _np(li, "l_discount")), first)
    assert np.allclose(_np(od, "o_totalprice"), total, rtol=0, atol=0.006)
    n_open = np.add.reduceat((status == "O").astype(int), first)
    n_all = np.diff(np.r_[first, len(lkey)])
    want = np.where(n_open == n_all, "O", np.where(n_open == 0, "F", "P"))
    assert (np.asarray(od["o_orderstatus"].cast("string").to_pylist())
            == want).all()


def test_parquet_holds_the_tables_cached(tables, tmp_path):
    paths = datagen.write_parquet(tables, str(tmp_path / "d"), 16384)
    again = datagen.write_parquet(tables, str(tmp_path / "d"), 16384)
    assert paths == again                                # reused, not rewritten
    for name, table in tables.items():
        back = pq.read_table(paths[name])
        assert back.equals(harness.plain_strings(table)), name
        meta = pq.ParquetFile(paths[name]).metadata
        assert meta.row_group(0).num_rows <= 16384
        assert meta.row_group(0).column(0).compression == "SNAPPY"
