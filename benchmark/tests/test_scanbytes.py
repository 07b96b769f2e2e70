"""The byte-count function against figures worked out by hand."""
import datagen
import run as harness
import scanbytes

ROWS = {"customer": 150_000, "orders": 1_500_000, "lineitem": 5_999_712}


def _schemas():
    return {n: t.schema for n, t in datagen.generate(0.001, 1).items()}


def test_query_bytes_by_hand():
    schemas = _schemas()
    # Q6: l_extendedprice 8 + l_discount 8 + l_quantity 8 + l_shipdate 4
    assert scanbytes.query_bytes(harness.load_query("q6"), schemas, ROWS) \
        == 28 * 5_999_712
    # Q1: Q6's four + l_tax 8 + l_returnflag 4 + l_linestatus 4
    assert scanbytes.query_bytes(harness.load_query("q1"), schemas, ROWS) \
        == 44 * 5_999_712
    # Q3: c_custkey 8 + c_mktsegment 4; o_orderkey 8 + o_custkey 8 +
    # o_orderdate 4 + o_shippriority 4; l_orderkey 8 + l_extendedprice 8 +
    # l_discount 8 + l_shipdate 4
    assert scanbytes.query_bytes(harness.load_query("q3"), schemas, ROWS) \
        == 12 * 150_000 + 24 * 1_500_000 + 28 * 5_999_712
    assert scanbytes.query_rows(harness.load_query("q3"), schemas, ROWS) \
        == 150_000 + 1_500_000 + 5_999_712
    assert scanbytes.query_rows(harness.load_query("q6"), schemas, ROWS) \
        == 5_999_712


def test_comment_lines_name_no_column():
    # the header of q6.sql speaks of dates and discounts; only the query counts
    assert "--" not in harness.load_query("q6")
    named = scanbytes.columns_named(harness.load_query("q6"), _schemas())
    assert named == {"lineitem": ["l_quantity", "l_extendedprice",
                                  "l_discount", "l_shipdate"]}
