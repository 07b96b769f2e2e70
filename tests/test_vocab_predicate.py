"""A string compared with a literal is answered on the column's
dictionary: `=`, `<=>` and `in (...)` between a dict-encoded column and
a non-null string literal compare the vocabulary's entries once and map
the answer to the rows by code (expr/core.py `_vocab_eq_literal`), where
the parent flattened the column to capacity x vocabulary bytes first."""
import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnVector, column_from_arrow
from spark_rapids_tpu.expr.core import (
    BoundRef, EqualNullSafe, EqualTo, EvalCtx, Literal, col, lit,
)
from spark_rapids_tpu.runtime import compile_cache as CC
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession

from asserts import assert_tpu_and_cpu_are_equal_collect

_SEGMENTS = ["BUILDING", "AUTOMOBILE", "MACHINERY", "HOUSEHOLD", "FURNITURE",
             "", "building", "BUILDINGS", "naïve café 東京", None]


def _table(n=700, seed=11):
    rng = np.random.default_rng(seed)
    seg = np.array(_SEGMENTS, object)[rng.integers(0, len(_SEGMENTS), n)]
    return pa.table({"id": pa.array(np.arange(n, dtype=np.int64)),
                     "seg": pa.array(seg, pa.string()),
                     "n": pa.array(rng.integers(0, 100, n).astype(np.int64))})


def _frame(s, tmp_path):
    return s.create_dataframe(_table())


def _parquet_frame(s, tmp_path):
    path = str(tmp_path / "seg.parquet")
    pq.write_table(_table(), path)
    return s.read_parquet(path)


#: the predicate's three-valued answer per row, and the rows it keeps
_SHAPES = (lambda df, pred: df.select(col("id"), pred.alias("p")),
           lambda df, pred: df.filter(pred).select(col("id")))

_CASES = {
    "eq": (_frame, col("seg") == lit("BUILDING")),
    "null_safe_eq": (_frame, EqualNullSafe(col("seg"), lit("BUILDING"))),
    "in_list": (_frame, col("seg").isin("BUILDING", "", "FURNITURE", "nowhere")),
    "literal_on_the_left": (_frame, EqualTo(lit("MACHINERY"), col("seg"))),
    "null_safe_literal_on_the_left":
        (_frame, EqualNullSafe(lit("MACHINERY"), col("seg"))),
    "literal_absent_from_vocab": (_frame, col("seg") == lit("SHIPPING")),
    "empty_string_literal": (_frame, col("seg") == lit("")),
    # the stride loop compares 8 bytes a round: a second round, and a
    # prefix of the literal ('BUILDING') that is itself an entry
    "literal_longer_than_a_stride": (_frame, col("seg") == lit("BUILDINGS")),
    "multibyte_utf8_literal": (_frame, col("seg") == lit("naïve café 東京")),
    "negated": (_frame, ~(col("seg") == lit("HOUSEHOLD"))),
    # live rows at arbitrary positions: the first filter leaves a mask
    "masked_batch": (lambda s, t: _frame(s, t).filter(col("n") % lit(3) == lit(1)),
                     col("seg") == lit("AUTOMOBILE")),
    # upper() maps the vocab and leaves repeated entries (dict_unique=False)
    "repeated_vocab_entries": (_frame, F.upper(col("seg")) == lit("BUILDING")),
    "cached_frame": (lambda s, t: _frame(s, t).cache(),
                     col("seg") == lit("FURNITURE")),
    "parquet_host_fallback": (_parquet_frame, col("seg") == lit("BUILDING")),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_dict_column_against_literal_matches_cpu(case, tmp_path):
    make, pred = _CASES[case]
    session = TpuSession()
    CC.clear()
    before = CC.stats()
    for shape in _SHAPES:
        out = assert_tpu_and_cpu_are_equal_collect(
            lambda s: shape(make(s, tmp_path), pred), session, ignore_order=True)
    assert out.num_rows > 0 or case == "literal_absent_from_vocab"
    after = CC.stats()
    assert after["vocab_predicates_traced"] > before["vocab_predicates_traced"]
    assert after["dict_flattens_traced"] == before["dict_flattens_traced"]


def _walk(jaxpr, in_loop=False):
    """Every equation of a jaxpr and of the jaxprs nested in it, with
    whether it sits inside a while or scan body."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        loop = in_loop or eqn.primitive.name in ("while", "scan")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, loop)


def test_filter_over_dict_column_and_literal_holds_no_flatten():
    """The traced predicate holds no array beyond a small multiple of the
    batch's capacity (the flatten's byte plane is capacity x 45 here) and
    no loop over batch-sized arrays (the flatten's searchsorted)."""
    cap = 4096
    vocab = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
    pred = EqualTo(BoundRef(0, T.STRING), Literal("BUILDING", T.STRING))

    def stage(data, n):
        ctx = EvalCtx([ColumnVector(T.STRING, data, None)], n, cap)
        out = pred.eval_tpu(ctx)
        return out.data & out.validity

    values = np.array(vocab, object)[np.arange(cap) % len(vocab)]
    data = column_from_arrow(pa.array(values, pa.string()), T.STRING, cap).data
    assert "codes" in data
    jaxpr = jax.make_jaxpr(stage)(data, jnp.int32(cap - 7))
    sizes, in_loops = [], []
    for eqn, in_loop in _walk(jaxpr.jaxpr):
        assert eqn.params.get("name") != "searchsorted"
        for v in list(eqn.invars) + list(eqn.outvars):
            size = int(np.prod(getattr(v.aval, "shape", ())))
            sizes.append(size)
            if in_loop:
                in_loops.append(size)
    assert max(sizes) <= 2 * cap, max(sizes)
    assert in_loops and max(in_loops) < cap, in_loops
    got = np.asarray(jax.jit(stage)(data, jnp.int32(cap - 7)))
    want = (values == "BUILDING") & (np.arange(cap) < cap - 7)
    assert (got == want).all()


def _q3_shaped(s, n, seg_pred):
    rng = np.random.default_rng(5)
    segs = np.array(_SEGMENTS[:5], object)
    customer = s.create_dataframe(pa.table({
        "c_custkey": pa.array(np.arange(n, dtype=np.int64)),
        "c_mktsegment": pa.array(segs[rng.integers(0, 5, n)], pa.string()),
        "c_nation": pa.array(segs[rng.integers(0, 5, n)], pa.string())}))
    orders = s.create_dataframe(pa.table({
        "o_orderkey": pa.array(np.arange(2 * n, dtype=np.int64)),
        "o_custkey": pa.array(rng.integers(0, n, 2 * n).astype(np.int64)),
        "o_total": pa.array(rng.uniform(1, 100, 2 * n))}))
    return (customer.filter(seg_pred)
            .join(orders, [(col("c_custkey"), col("o_custkey"))])
            .group_by("o_custkey").agg(F.sum(col("o_total")).alias("revenue")))


def test_counters_count_traces_of_each_path():
    session = TpuSession()
    CC.clear()
    CC.reset_stats_for_tests()
    # fresh shapes (row counts no other test of this file uses), so the
    # programs trace here
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _q3_shaped(s, 3100, col("c_mktsegment") == lit("BUILDING")),
        session, ignore_order=True, approx_float=1e-9)
    st = CC.stats()
    assert st["vocab_predicates_traced"] >= 1
    assert st["dict_flattens_traced"] == 0
    traced = st["vocab_predicates_traced"]
    # warm: the same query again dispatches and traces nothing
    _q3_shaped(session, 3100, col("c_mktsegment") == lit("BUILDING")).collect()
    assert CC.stats()["vocab_predicates_traced"] == traced
    # a dict column against a computed string still flattens at the bound
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _q3_shaped(
            s, 3100, col("c_mktsegment") == F.upper(col("c_nation"))),
        session, ignore_order=True, approx_float=1e-9)
    st = CC.stats()
    assert st["dict_flattens_traced"] >= 1
    assert st["vocab_predicates_traced"] == traced
    doc = CC.doc()
    assert doc["vocab_predicates_traced"] == st["vocab_predicates_traced"]
    assert doc["dict_flattens_traced"] == st["dict_flattens_traced"]
