"""A tiny-bucket aggregate of float sums and counts in one pass
(ops/groupby.bucket_digit_agg): the exact integer digits of each sum's
values, contracted with the one-hot of the row's bucket on the MXU.

Held here, on the CPU backend (IEEE f64): the digit split recomposes to
round(x * scale) exactly; every sum lies within the digits' rounding of
the exact sum (`fractions`) and agrees with the masked reduction a bucket
that it replaces; NaN and the infinities, nulls, empty buckets, a bucket
deeper than one accumulation chunk and capacities off the chunk keep
Spark's answers; Q1 answers the benchmark's reference on one device and
per shard of a four-device mesh; and who stays on the old branch (65
buckets, a max state) does, with `bucket_digit_traced` unmoved.
"""
import importlib
import os
import sys
import zlib
from fractions import Fraction

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec.tpu_nodes import HashAggregateExec
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.ops import pallas_segsum as PS
from spark_rapids_tpu.runtime import compile_cache as CC
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession

from asserts import assert_tpu_and_cpu_are_equal_collect

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


# -- the digit split -----------------------------------------------------------

def test_exact_split_and_its_digits_recompose_to_the_rounded_value():
    rng = np.random.default_rng(39)
    scale = np.float64(2.0 ** 31)
    x = np.concatenate([
        rng.uniform(-2 ** 16, 2 ** 16, 4000),
        # ties and near-ties of the rounding, and both ends of the range
        (np.arange(-50, 50) + 0.5) / scale,
        (np.arange(-50, 50) + 0.5 + 2.0 ** -20) / scale,
        [0.0, -0.0, 2 ** 17 - 2 ** -30, -(2 ** 17 - 2 ** -30), 2.0 ** -40]])
    q, n = jax.jit(G.exact_split)(jnp.asarray(x), jnp.ones(x.size, bool),
                                  scale)
    q, n = np.asarray(q, np.int64), np.asarray(n, np.int64)
    want = np.round(x * scale).astype(np.int64)
    assert np.array_equal(q * (1 << 24) + n, want)
    assert np.abs(q).max() <= 1 << 24 and np.abs(n).max() <= 1 << 24
    digits = [np.asarray(d, np.int64) for half in (q, n)
              for d in PS.signed_bytes(jnp.asarray(half, jnp.int32))]
    assert np.array_equal(sum(d << s for d, s in zip(digits, PS.SHIFTS)),
                          want)
    # each half's top digit reaches 2^8, the others 2^7: bf16-exact
    bound = [256, 128, 128, 256, 128, 128]
    assert all(np.abs(d).max() <= b for d, b in zip(digits, bound))
    # non-finite values carry their flags above every finite q
    sq, sn = jax.jit(G.exact_split)(
        jnp.asarray([np.nan, np.inf, -np.inf, 1.0]),
        jnp.asarray([True, True, True, False]), scale)
    assert list(np.asarray(sq)) == [PS.SPECIAL + 3, PS.SPECIAL + 1,
                                    PS.SPECIAL + 2, 0]
    assert list(np.asarray(sn)) == [0, 0, 0, 0]


# -- bucket_digit_agg against exact sums and the masked branch -----------------

def _plane(rng, n, lo=-1e4, hi=1e4):
    return np.round(rng.uniform(lo, hi, n), 2)


def _case(name):
    """(B, bucket, live, [(op, values, valid)]) as numpy planes."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    n, B = 5000, 12
    values = _plane(rng, n)
    valid = np.ones(n, bool)
    bucket = rng.integers(0, B, n)
    if name == "one_bucket":
        B, bucket = 1, np.zeros(n, np.int64)
    elif name == "sixty_four_buckets":
        B = 64
        bucket = rng.integers(0, B, n)
    elif name == "mixed_magnitudes":
        values = rng.choice([-1e15, -3.25, 0.0, -0.0, 1e-15, 7e-300, 1e15,
                             123456.789, -2.0 ** 40], n) \
            * rng.uniform(0.5, 1.0, n)
    elif name == "specials":
        # bucket 0: a NaN; 1: +inf; 2: -inf; 3: both infinities; 4: all
        # of them; the rest finite
        for b, specials in enumerate([[np.nan], [np.inf], [-np.inf],
                                      [np.inf, -np.inf],
                                      [np.nan, np.inf, -np.inf]]):
            rows = np.flatnonzero(bucket == b)[:len(specials)]
            values[rows] = specials
    elif name == "nulls":
        valid = rng.random(n) > 0.3
        valid[bucket == 5] = False          # an all-null bucket
    elif name == "empty_buckets":
        bucket = rng.choice([0, 3, 11], n)  # nine buckets see no row
    elif name == "deep_bucket_odd_capacity":
        # one bucket of more rows than a grid step holds, at a capacity
        # that is no multiple of the step
        n, B = PS.DIGIT_TILE + 4465, 3
        values = _plane(rng, n, 0, 1e5)
        valid = np.ones(n, bool)
        bucket = np.where(rng.random(n) < 0.95, 1, rng.integers(0, B, n))
    live = rng.random(n) > 0.1
    other = _plane(rng, n, -1, 1)
    states = [("sum", values, valid & live), ("count", values, valid & live),
              ("sum", other, live), ("count_all", None, live)]
    return B, bucket, live, states


def _run(fn, B, bucket, live, states):
    """fn over the states under jit, each distinct numpy plane one
    argument, so planes shared on the host are shared in the trace."""
    planes = [live]
    for _op, v, valid in states:
        for p in (v, valid):
            if p is not None and not any(p is q for q in planes):
                planes.append(p)

    def at(p):
        return None if p is None else next(
            i for i, q in enumerate(planes) if p is q)
    where = [(op, at(v), at(valid)) for op, v, valid in states]

    def go(bucket, args):
        traced = [(op, None if i is None else args[i], args[j])
                  for op, i, j in where]
        return fn(traced, bucket, args[0], B)
    return jax.jit(go)(jnp.asarray(bucket, jnp.int32),
                       [jnp.asarray(p) for p in planes])


def _masked(states, bucket, live, B):
    outs = [G.bucket_agg(op, jnp.zeros(live.shape, jnp.float64)
                         if v is None else v, valid, bucket, B, True)
            for op, v, valid in states]
    return outs, jnp.stack([jnp.any(live & (bucket == b)) for b in range(B)])


def _spark_sum(values):
    """Spark's double sum of the valid values of one group, exactly."""
    nan = any(np.isnan(values))
    pinf, ninf = np.inf in values, -np.inf in values
    if nan or (pinf and ninf):
        return np.nan
    if pinf or ninf:
        return np.inf if pinf else -np.inf
    return sum((Fraction(float(x)) for x in values), Fraction(0))


CASES = ["twelve_buckets", "one_bucket", "sixty_four_buckets",
         "mixed_magnitudes", "specials", "nulls", "empty_buckets",
         "deep_bucket_odd_capacity"]


@pytest.mark.parametrize("case", CASES)
def test_digit_sums_match_exact_sums_and_the_masked_branch(case):
    B, bucket, live, states = _case(case)
    outs, occ = _run(G.bucket_digit_agg, B, bucket, live, states)
    old_outs, old_occ = _run(_masked, B, bucket, live, states)
    assert np.array_equal(np.asarray(occ), np.asarray(old_occ))
    assert np.array_equal(np.asarray(occ),
                          [live[bucket == b].any() for b in range(B)])
    for (op, v, valid), (got, gvalid), (old, ovalid) in zip(
            states, outs, old_outs):
        got, gvalid = np.asarray(got), np.asarray(gvalid)
        assert np.array_equal(gvalid, np.asarray(ovalid)), op
        if op != "sum":
            want = [int(valid[bucket == b].sum()) for b in range(B)]
            assert got.dtype == np.int64 and list(got) == want, op
            assert list(np.asarray(old)) == want
            continue
        finite = v[valid & np.isfinite(v)]
        top = np.abs(finite).max() if finite.size else 0.0
        for b in range(B):
            rows = v[valid & (bucket == b)]
            assert gvalid[b] == (rows.size > 0)
            if not rows.size:
                continue
            want = _spark_sum(rows)
            if not isinstance(want, Fraction):
                assert (np.isnan(got[b]) and np.isnan(want)) \
                    or got[b] == want, (case, b, got[b], want)
                assert (np.isnan(old[b]) and np.isnan(want)) \
                    or old[b] == want
                continue
            # each row rounds to 2^-48 of the plane's max; the
            # recomposition and the final divide to a few ulp of the sum
            tol = rows.size * top * 2.0 ** -47 + 4 * np.spacing(
                abs(float(want)))
            assert abs(Fraction(float(got[b])) - want) <= Fraction(tol), \
                (case, b, float(got[b]), float(want))
            assert abs(float(got[b]) - float(old[b])) <= 2 * tol


def test_grid_steps_and_blocks_of_the_kernel_add_up(monkeypatch):
    """Several output blocks of several grid steps each, a padded last
    block: the same answer as one step over the whole plane."""
    B, bucket, live, states = _case("twelve_buckets")
    whole = _run(G.bucket_digit_agg, B, bucket, live, states)
    monkeypatch.setattr(PS, "DIGIT_TILE", 1024)
    monkeypatch.setattr(PS, "DIGIT_BLOCK_ROWS", 2048)
    stepped = _run(G.bucket_digit_agg, B, bucket, live, states)
    for (a, av), (b, bv) in zip(whole[0], stepped[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(av), np.asarray(bv))


# -- the engine: who takes the branch ------------------------------------------

@pytest.fixture(scope="module")
def tpch():
    import datagen
    import run as harness
    tables = datagen.generate(20_000 / 6_000_000, 39)
    return tables, harness.plain_strings(tables["lineitem"])


def _agrees(got, want, rel=1e-11):
    assert set(got) == set(want)
    for name, values in want.items():
        assert len(got[name]) == len(values), name
        for g, w in zip(got[name], values):
            if isinstance(w, float):
                assert abs(g - w) <= rel * max(abs(w), 1e-300), (name, g, w)
            else:
                assert g == w, (name, g, w)


def _traced(run):
    CC.clear()
    before = CC.stats()["bucket_digit_traced"]
    out = run()
    return out, CC.stats()["bucket_digit_traced"] - before


def _partial_aggs(node):
    found = [node] if isinstance(node, HashAggregateExec) \
        and node.mode == "partial" else []
    for c in node.children:
        found.extend(_partial_aggs(c))
    return found


@pytest.mark.parametrize("devices", [1, 4])
def test_q1_answers_the_reference_through_the_digit_branch(tpch, devices):
    """Q1 over datagen's lineitem at 20,000 rows: one device, and per
    shard under shard_map over a four-device mesh."""
    import run as harness
    tables, lineitem = tpch
    conf = {} if devices == 1 else {C.MULTICHIP_ENABLED.key: "true",
                                    C.MULTICHIP_DEVICES.key: devices}
    sess = TpuSession(conf)
    df = sess.create_dataframe(lineitem).cache()
    df.count()
    sess.create_or_replace_temp_view("lineitem", df)
    got, traced = _traced(
        lambda: sess.sql(harness.load_query("q1")).to_pydict())
    _agrees(got, importlib.import_module("reference.q1").answer(tables))
    assert traced >= 1
    if devices > 1:
        assert "[sharded n=4]" in sess._last_exec.tree_string()
        (agg,) = _partial_aggs(sess._last_exec)
        assert agg.shard_over == devices and agg._shard_out, \
            "the update ran unsharded"
        assert getattr(agg, "_shard_fallback_reason", None) is None


def _keys_frame(n_a, n_b, n=6000, seed=65):
    rng = np.random.default_rng(seed)
    return pa.table({
        "a": pa.array([f"a{i}" for i in rng.integers(0, n_a, n)]),
        "b": pa.array([f"b{i}" for i in rng.integers(0, n_b, n)]),
        "v": pa.array(np.round(rng.uniform(-100, 100, n), 2))})


@pytest.mark.parametrize("case", ["sixty_five_buckets", "max_state",
                                  "integer_sum"])
def test_other_aggregates_keep_the_masked_or_scatter_branch(case):
    """65 buckets (5 x 13 with the null slots), a max beside the sums, and
    an integer sum: the old branch, the same answers, no digit trace."""
    n_a, n_b = (4, 12) if case == "sixty_five_buckets" else (3, 2)
    table = _keys_frame(n_a, n_b)
    if case == "integer_sum":
        table = table.append_column(
            "i", pa.array(np.arange(table.num_rows) % 97))
    aggs = {"sixty_five_buckets": [F.sum(col("v")).alias("s"),
                                   F.count(col("v")).alias("c")],
            "max_state": [F.sum(col("v")).alias("s"),
                          F.max(col("v")).alias("m")],
            "integer_sum": [F.sum(col("i")).alias("s"),
                            F.avg(col("v")).alias("m")]}[case]
    sess = TpuSession()
    _, traced = _traced(lambda: assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(table).cache().group_by("a", "b")
        .agg(*aggs), sess, ignore_order=True, approx_float=1e-9))
    assert traced == 0
