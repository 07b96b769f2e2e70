"""TPC-DS query 67 over the generated store-sales star, against the
benchmark's plain reference (benchmark/reference/q67.py), and the
mechanisms it runs through: filters pushed below joins, join inputs cut to
the columns read, a selective mask-through join compacted, GROUP BY ROLLUP
as one sort (exec/rollup.py), the ranked window over a float order key, the
wide final ORDER BY. Small sizes on the CPU; counts, never times."""
import importlib
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import tpcds_datagen  # noqa: E402

from spark_rapids_tpu.columnar.batch import from_arrow  # noqa: E402
from spark_rapids_tpu.exec import fuse  # noqa: E402
from spark_rapids_tpu.exec import rollup as RU  # noqa: E402
from spark_rapids_tpu.exec import tpu_nodes as N  # noqa: E402
from spark_rapids_tpu.ops import kernels as K  # noqa: E402
from spark_rapids_tpu.ops import radix as R  # noqa: E402
from spark_rapids_tpu.plan import nodes as P  # noqa: E402
from spark_rapids_tpu.runtime import obs  # noqa: E402
from spark_rapids_tpu.sql.session import TpuSession  # noqa: E402

reference = importlib.import_module("reference.q67")
SF = 0.001          # 2,880 store_sales rows; 360 items, 6 stores
KEYS = reference.KEYS


def _query(name: str) -> str:
    with open(os.path.join(BENCH, "queries", f"{name}.sql")) as f:
        lines = [ln for ln in f if not ln.lstrip().startswith("--")]
    return " ".join("".join(lines).split())


def _plain(table: pa.Table) -> pa.Table:
    return table.cast(pa.schema([
        pa.field(f.name, pa.string() if pa.types.is_dictionary(f.type)
                 else f.type) for f in table.schema]))


def _star(seed: int):
    tables = tpcds_datagen.generate(SF, seed)
    sess = TpuSession()
    for name, table in tables.items():
        sess.create_or_replace_temp_view(
            name, sess.create_dataframe(_plain(table)).cache())
    return sess, tables


@pytest.fixture(scope="module")
def star():
    return _star(2**31 + 11)


STAR = ("from store_sales join date_dim on ss_sold_date_sk = d_date_sk "
        "join store on ss_store_sk = s_store_sk "
        "join item on ss_item_sk = i_item_sk "
        "where d_month_seq between 1200 and 1200 + 11")
SUM = "sum(coalesce(ss_sales_price * ss_quantity, 0))"


@pytest.mark.parametrize("seed", [7, 2**31 + 11, 3100000123])
def test_q67_text_equals_the_reference(seed):
    sess, tables = _star(seed)
    got = sess.sql(_query("q67")).to_pydict()
    want = reference.answer(tables)
    assert list(got) == KEYS + ["sumsales", "rk"]
    assert len(want["rk"]) == 100
    for name in KEYS + ["rk"]:
        assert got[name] == want[name], name
    for g, w in zip(got["sumsales"], want["sumsales"]):
        assert abs(g - w) <= 1e-11 * abs(w)
    tree = sess._last_exec.tree_string()
    assert "RollupAggregateExec" in tree and "ExpandExec" not in tree


@pytest.fixture(scope="module")
def rolled(star):
    sess, _ = star
    out = sess.sql(f"select {', '.join(KEYS)}, {SUM} sumsales, count(*) n "
                   f"{STAR} group by rollup({', '.join(KEYS)})").to_pydict()
    return list(zip(*[out[k] for k in KEYS + ["sumsales", "n"]]))


@pytest.mark.parametrize("level", range(9))
def test_each_rollup_level_is_the_plain_group_by(star, rolled, level):
    sess, _ = star
    keys = KEYS[:level]
    text = f"select {', '.join(keys + [SUM + ' sumsales', 'count(*) n'])} " \
           f"{STAR}" + (f" group by {', '.join(keys)}" if keys else "")
    out = sess.sql(text).to_pydict()
    plain = list(zip(*[out[k] for k in keys + ["sumsales", "n"]]))
    assert plain
    have = {}
    for row in rolled:
        have.setdefault(row[:8], []).append(row[8:])
    for row in plain:
        padded = row[:level] + (None,) * (8 - level)
        assert any(n == row[-1] and abs(s - row[-2]) <= 1e-12 * abs(row[-2])
                   for s, n in have.get(padded, [])), padded
    if level == 8:   # and nothing besides the nine levels
        total = 1   # the grand total
        for k in range(1, 9):
            total += sess.sql(
                f"select count(*) n from (select 1 x {STAR} group by "
                f"{', '.join(KEYS[:k])}) g").to_pydict()["n"][0]
        assert total == len(rolled)


def test_a_group_of_the_same_rows_has_the_same_sum_at_every_level(rolled):
    """query 67's month filter keeps one year: the level with d_year and
    the level without it hold the same rows, and rank() ties on them only
    if their sums are the same double."""
    by_product = {r[:4]: r[8] for r in rolled if r[4] is None
                  and r[3] is not None}
    with_year = [r for r in rolled if r[4] is not None and r[5] is None
                 and r[3] is not None]
    assert with_year
    for r in with_year:
        assert r[8] == by_product[r[:4]]


def _rank(rows, desc, nulls_first):
    """SQL rank() of (partition, value) rows."""
    def key(v):
        if v is None:
            return (0 if nulls_first else 2, 0.0)
        if math.isnan(v):
            return (1, math.inf if not desc else -math.inf)
        return (1, -v if desc else v)
    out = []
    for p, v in rows:
        peers = [key(w) for q, w in rows if q == p]
        out.append(1 + sum(1 for k in peers if k < key(v)))
    return out


@pytest.mark.parametrize("desc,nulls", [(True, "last"), (True, "first"),
                                        (False, "last"), (False, "first")])
def test_rank_over_a_double_with_ties_and_a_null_partition(desc, nulls):
    rng = np.random.default_rng(3)
    n = 300
    part = [None if i % 7 == 0 else "p%d" % (i % 4) for i in range(n)]
    vals = [None if i % 11 == 0 else float(rng.integers(0, 12)) / 4 - 1
            for i in range(n)]
    vals[5], vals[9], vals[13] = float("nan"), -0.0, 0.0
    sess = TpuSession()
    sess.create_or_replace_temp_view("t", sess.create_dataframe(pa.table({
        "p": pa.array(part), "v": pa.array(vals, pa.float64()),
        "i": pa.array(list(range(n)), pa.int32())})).cache())
    order = f"v {'desc' if desc else 'asc'} nulls {nulls}"
    got = sess.sql(f"select i, rank() over (partition by p order by {order}"
                   ") rk from t").to_pydict()
    assert "windowSortTime" in next(
        v for k, v in sess.last_metrics().items() if k.startswith("Window"))
    want = _rank(list(zip(part, vals)), desc, nulls == "first")
    rk = dict(zip(got["i"], got["rk"]))
    assert [rk[i] for i in range(n)] == want


def test_rank_over_a_double_without_a_partition():
    vals = [3.5, 1.25, 3.5, None, -2.0, 1.25, 9.0]
    sess = TpuSession()
    sess.create_or_replace_temp_view("t", sess.create_dataframe(pa.table({
        "v": pa.array(vals, pa.float64()),
        "i": pa.array(list(range(7)), pa.int32())})).cache())
    got = sess.sql("select i, rank() over (order by v desc) rk "
                   "from t").to_pydict()
    rk = dict(zip(got["i"], got["rk"]))
    assert [rk[i] for i in range(7)] == _rank(
        [(0, v) for v in vals], True, False)


def test_null_foreign_keys_join_nothing(star):
    sess, tables = star
    ss = tables["store_sales"]
    assert ss["ss_sold_date_sk"].null_count and ss["ss_store_sk"].null_count
    joined = sess.sql(f"select count(*) n {STAR}").to_pydict()["n"][0]
    assert joined == reference.rollup(tables)[3]
    # every row but those with a null key joins when nothing filters
    all_rows = sess.sql(
        "select count(*) n from store_sales "
        "join date_dim on ss_sold_date_sk = d_date_sk "
        "join store on ss_store_sk = s_store_sk").to_pydict()["n"][0]
    date_ok = np.asarray(ss["ss_sold_date_sk"].is_valid())
    store_ok = np.asarray(ss["ss_store_sk"].is_valid())
    assert all_rows == int((date_ok & store_ok).sum())


@pytest.mark.parametrize("one_sort", [True, False])
def test_counters_follow_the_reference(star, monkeypatch, one_sort):
    """expand_rows counts rows that were really written once a level: none
    where the rollup is one sort, the joined rows times nine where its
    keys do not pack and it falls back to the expansion."""
    sess, tables = star
    if not one_sort:
        monkeypatch.setattr(R, "plan_packing_planes", lambda *a, **k: None)
    sess.sql(_query("q67")).to_pydict()
    rec = obs.recent_queries(1)[0]
    _, sums, _, rows = reference.rollup(tables)
    if one_sort:
        assert rec["counters"]["expand_rows"] == 0
        assert rec["counters"]["agg_groups"] == len(sums)
    # the joined rows' count stays on the device (a masked batch), so the
    # expansion's counter holds it deferred: read where a sync is allowed
    expanded = sum(m.get("expandRows", 0)
                   for m in sess.last_metrics().values())
    assert expanded == (0 if one_sort else 9 * rows)
    timers = rec["timers_ns"]
    assert timers["aggTime"] > 0 and timers["windowSortTime"] > 0
    assert timers["joinTime"] > 0
    # the device's time for the same steps: there, and no negative
    assert timers["joinDeviceTime"] >= 0
    assert timers["windowSortDeviceTime"] >= 0
    if one_sort:
        assert timers["aggDeviceTime"] >= 0


def test_a_device_mark_is_read_at_the_next_wait_that_exists(monkeypatch):
    """The time to a marked output goes to its timer when the thread next
    waits for the device, from the later of the step's first enqueue and
    the mark read before it; a mark the device has passed gives nothing;
    a thread keeps a bounded number unread."""
    from spark_rapids_tpu.runtime.metrics import GpuMetric
    from spark_rapids_tpu.runtime.obs import phases as PH

    class Clock:
        now = 0

        @classmethod
        def perf_counter_ns(cls):
            return cls.now

    class Out:   # an output the device reaches `after` ns into the wait
        def __init__(self, after):
            self.after = after

        def is_ready(self):
            return self.after == 0

        def block_until_ready(self):
            Clock.now += self.after
            self.after = 0

    monkeypatch.setattr(PH, "time", Clock)
    PH._marks.pending.clear()
    PH._marks.reached_ns = 0
    a, b = GpuMetric("aDeviceTime"), GpuMetric("bDeviceTime")
    Clock.now = 100
    PH._marks.pending += [(a, Out(50), 10),    # enqueued at 10, reached at 150
                          (b, Out(30), 20),    # behind a: 150 to 180
                          (a, Out(0), 30)]     # passed: its moment is gone
    with PH.device_wait():
        Clock.now += 5
    assert (a.peek(), b.peek()) == (140, 30)
    assert PH._marks.pending == [] and PH._marks.reached_ns == 180
    Clock.now = 300
    PH._marks.pending.append((b, Out(7), 290))  # enqueued after the last
    with PH.device_wait():
        pass
    assert b.peek() == 30 + 17
    x = jnp.arange(4) + 1
    for _ in range(3 * PH._MARKS_KEPT):
        PH.device_mark(a, x, 0)
    assert len(PH._marks.pending) == PH._MARKS_KEPT
    with PH.device_wait():
        pass
    assert PH._marks.pending == []


def test_q67_is_keyed_programs(star):
    """The programs a warm q67 starts through the engine's choke points,
    against every jitted call of the pass (the profiler's PjitFunction
    events on the host: eager jnp calls are among them)."""
    import jax
    import tempfile
    from spark_rapids_tpu.runtime.obs import phases
    sess, _ = star
    text = _query("q67")
    sess.sql(text).to_pydict()
    keys = []
    fuse.set_dispatch_hook(keys.append)
    try:
        sess.sql(text).to_pydict()
    finally:
        fuse.set_dispatch_hook(None)
    classes = [k[0] for k in keys]
    for want in ("dense_probe_masked", "rollup_pack", "argsort",
                 "rollup_scan", "rollup_emit", "window_keys",
                 "window_apply", "sort"):
        assert want in classes, (want, classes)
    assert obs.recent_queries(1)[0]["counters"]["keyed_dispatches"] \
        == len(keys) <= 24
    trace_dir = tempfile.mkdtemp(prefix="q67-dispatch-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    n0 = phases.keyed_dispatches
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        sess.sql(text).to_pydict()
    finally:
        jax.profiler.stop_trace()
    keyed = phases.keyed_dispatches - n0
    import glob
    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    calls = sum(1 for plane in data.planes for line in plane.lines
                for ev in line.events if ev.name.startswith("PjitFunction("))
    assert keyed == len(keys)
    # eager tails are what PR 31 found behind a 4-row sort (some 470
    # programs): a pass of q67 stays within a few times its keyed programs
    assert keyed <= calls <= 6 * keyed, (keyed, calls)


def test_a_rollup_too_wide_for_one_plane_sorts_by_two(monkeypatch):
    rng = np.random.default_rng(5)
    n = 500
    t = pa.table({
        "a": pa.array(rng.integers(0, 4, n) * (1 << 40), pa.int64()),
        "b": pa.array(rng.integers(0, 5, n) * (1 << 39) - (1 << 41),
                      pa.int64()),
        "c": pa.array([None if i % 9 == 0 else int(v) for i, v in
                       enumerate(rng.integers(0, 3, n))], pa.int32()),
        "v": pa.array(rng.integers(1, 1000, n) / 8.0),
        "w": pa.array([None if i % 5 == 0 else int(i) for i in range(n)],
                      pa.int64())})
    planes = []
    plan = R.plan_packing_planes
    monkeypatch.setattr(R, "plan_packing_planes", lambda *a, **k: (
        planes.append(plan(*a, **k)) or planes[-1]))
    sess = TpuSession()
    sess.create_or_replace_temp_view("t", sess.create_dataframe(t).cache())
    text = "select a, b, c, sum(v) s, count(w) n, count(*) m, avg(v) g, " \
           "sum(w) sw from t group by rollup(a, b, c)"

    def rows():
        out = sess.sql(text).to_pydict()
        return sorted(zip(*out.values()), key=lambda r: tuple(
            (x is None, x) for x in r))
    got = rows()
    assert "RollupAggregateExec" in sess._last_exec.tree_string()
    assert len(planes[-1]) == 2
    monkeypatch.setattr(RU, "rollup_shape", lambda *a, **k: None)
    want = rows()
    assert "ExpandExec" in sess._last_exec.tree_string()
    assert got == want


@pytest.mark.parametrize("special", [None, float("nan"), float("inf"),
                                     float("-inf")])
def test_a_rollups_float_sum_keeps_nan_and_infinity(monkeypatch, special):
    """A sum's planes that count NaN and the infinities are read only
    when the batch has any (the scan's read-back says): both ways agree
    with the general operators."""
    vals = [1.5, 2.25, -4.0, 8.0, 0.5, 16.0, None, 3.0]
    if special is not None:
        vals[2] = special
        vals[5] = float("inf") if special != special else special
    sess = TpuSession()
    sess.create_or_replace_temp_view("t", sess.create_dataframe(pa.table({
        "a": ["x", "x", "y", "y", "y", "z", "z", "x"],
        "b": pa.array([1, 2, 1, 1, 2, 3, 3, 1], pa.int32()),
        "v": pa.array(vals, pa.float64())})).cache())
    text = "select a, b, sum(v) s, avg(v) g from t group by rollup(a, b)"

    def rows():
        out = sess.sql(text).to_pydict()
        return sorted((repr(r) for r in zip(*out.values())))
    got = rows()
    assert "RollupAggregateExec" in sess._last_exec.tree_string()
    monkeypatch.setattr(RU, "rollup_shape", lambda *a, **k: None)
    assert got == rows()


def test_a_cube_keeps_the_general_operators():
    sess = TpuSession()
    sess.create_or_replace_temp_view("t", sess.create_dataframe(pa.table({
        "a": [1, 1, 2], "b": [1, 2, 2], "v": [1.0, 2.0, 3.0]})).cache())
    out = sess.sql("select a, b, sum(v) s from t group by cube(a, b)"
                   ).to_pydict()
    assert "ExpandExec" in sess._last_exec.tree_string()
    assert len(out["s"]) == 8
    # three rows, written once a grouping set: sizes the host has
    assert obs.recent_queries(1)[0]["counters"]["expand_rows"] == 4 * 3


def test_canonical_codes_are_equal_where_the_strings_are():
    words = ["w%03d" % (i % 150) for i in range(200)]   # flat at upload
    col = from_arrow(pa.table({"s": pa.array(words)})).columns[0]
    assert not col.is_dict
    idx = jnp.arange(256, dtype=jnp.int32)
    got = K.gather_column(col, jnp.where(idx < 200, idx, -1), 200)
    assert got.is_dict and not got.dict_unique
    codes = np.asarray(K.canonical_dict_codes(got).data["codes"])[:200]
    assert len(set(codes)) == 150
    for i in range(200):
        assert codes[i] == i % 150
    unique = from_arrow(pa.table({"s": pa.array(["a", "b"] * 100)})
                        ).columns[0]
    assert K.canonical_dict_codes(unique) is unique


def test_lexsort_by_many_planes_is_the_lexicographic_order():
    rng = np.random.default_rng(9)
    cap, rows, nkeys = 256, 200, 6
    keys, cols = [], []
    for j in range(nkeys):
        v = rng.integers(0, 3, cap).astype(np.uint64)
        nulls = rng.integers(0, 5, cap) == 0
        asc, nulls_first = bool(j % 2), bool(j % 3)
        keys.append((jnp.asarray(np.where(nulls, 0, v).astype(np.uint64)),
                     jnp.asarray(nulls), asc, nulls_first))
        rank = np.where(nulls, 0 if nulls_first else 2, 1)
        cols.append((rank, np.where(nulls, 0, v.astype(np.int64)
                                    * (1 if asc else -1))))
    assert 1 + 2 * nkeys > K._LEXSORT_LOOP_PLANES
    perm = np.asarray(K.lexsort_indices(keys, rows))
    by = [np.arange(cap)]
    for rank, v in reversed(cols):
        by += [v, rank]
    by.append(np.arange(cap) >= rows)
    assert list(perm) == list(np.lexsort(by))


@pytest.fixture
def dims():
    sess = TpuSession()
    sess.create_or_replace_temp_view("f", sess.create_dataframe(pa.table({
        "fk": pa.array([1, 2, 3, 4, None, 2, 9], pa.int64()),
        "x": pa.array([10, 20, 30, 40, 50, 60, 70], pa.int32()),
        "unused": pa.array(list("abcdefg"))})).cache())
    sess.create_or_replace_temp_view("d", sess.create_dataframe(pa.table({
        "k": pa.array([1, 2, 3, 4, 5], pa.int64()),
        "y": pa.array([1, 2, 3, 4, 5], pa.int32()),
        "z": pa.array(list("vwxyz"))})).cache())
    return sess


def _nodes(plan, kind):
    out = [plan] if isinstance(plan, kind) else []
    for c in plan.children:
        out += _nodes(c, kind)
    return out


def test_a_filter_on_one_side_moves_below_the_join(dims):
    from spark_rapids_tpu.plan.prune import prune_plan
    df = dims.sql("select x, y from f join d on fk = k "
                  "where y >= 2 and x < 60 and x + y > 0")
    plan = prune_plan(df.plan)
    [join] = _nodes(plan, P.Join)
    left, right = (_nodes(c, P.Filter) for c in join.children)
    assert len(left) == 1 and len(right) == 1     # x < 60; y >= 2
    above = [f for f in _nodes(plan, P.Filter)
             if f not in left and f not in right]
    assert len(above) == 1                        # x + y > 0 reads both
    # the join's inputs carry what is read and no more
    assert [f.name for f in join.children[0].schema.fields] == ["fk", "x"]
    assert [f.name for f in join.children[1].schema.fields] == ["k", "y"]
    assert sorted(df.to_pydict()["x"]) == [20, 30, 40]


def test_what_the_pushdown_costs_the_dispatch_budget(dims):
    """The golden budgets' drift, in one plan: a Filter that moves below a
    join is a narrow dispatch of its own on that input (it no longer rides
    in the stage above the join), and the column-subset Projects that
    narrow a join's inputs are selections, which dispatch nothing."""
    from spark_rapids_tpu.analysis.plan_verify import dispatch_budget
    from spark_rapids_tpu.exec.stage_fusion import _dispatching
    from spark_rapids_tpu.runtime.metrics import walk_exec_tree

    def budget(text):
        root, _ = dims.prepare_execution(dims.sql(text).plan)
        nodes = [n for _k, n, _d, role, _s in walk_exec_tree(root)
                 if role is None]
        return dispatch_budget(root), nodes

    plain, nodes = budget("select x, y from f join d on fk = k")
    subsets = [n for n in nodes if isinstance(n, N.ProjectExec)]
    assert len(subsets) >= 2                      # [fk, x] and [k, y]
    assert not any(_dispatching(n) for n in subsets)
    assert plain["narrow_dispatches_per_batch"] == 0
    pushed, nodes = budget("select x, y from f join d on fk = k "
                           "where y >= 2 and x < 60")
    filters = [n for n in nodes if isinstance(n, N.FilterExec)]
    assert len(filters) == 2                      # one an input
    assert pushed["narrow_dispatches_per_batch"] == 2


def test_a_left_joins_right_side_filter_stays_above(dims):
    from spark_rapids_tpu.plan.prune import prune_plan
    df = dims.sql("select x, y from f left join d on fk = k "
                  "where y is null and x > 10")
    [join] = _nodes(prune_plan(df.plan), P.Join)
    assert len(_nodes(join.children[0], P.Filter)) == 1
    assert not _nodes(join.children[1], P.Filter)
    assert sorted(df.to_pydict()["x"]) == [50, 70]


def test_pushing_a_filter_leaves_a_shared_join_as_it_was(dims):
    from spark_rapids_tpu.expr.core import col, lit
    joined = dims.table("f").join(dims.table("d"),
                                  on=col("fk") == col("k"))
    assert joined.filter(col("y") > lit(3)).count() == 1
    assert joined.count() == 5
    assert joined.filter(col("y") < lit(2)).count() == 1


def test_a_selective_star_join_is_compacted(dims, monkeypatch):
    """The rule reads what the host has: the build's distinct keys
    against the span of the probe key's column stats."""
    monkeypatch.setattr(N, "_JOIN_COMPACT_ABOVE", 4)
    n = 4096
    dims.create_or_replace_temp_view(
        "big", dims.create_dataframe(pa.table({
            "fk": pa.array(np.arange(n) % 1000, pa.int64()),
            "x": pa.array(np.arange(n), pa.int32())})).cache())
    keys = []
    fuse.set_dispatch_hook(keys.append)
    try:
        few = dims.sql("select x, y from big join d on fk = k").to_pydict()
        classes = [k[0] for k in keys]
        del keys[:]
        every = dims.sql("select f.x, y from f join d on fk = k"
                         ).to_pydict()
        classes_all = [k[0] for k in keys]
    finally:
        fuse.set_dispatch_hook(None)
    # a span of 5 of a span of 1000 is cut and compacted (K.compact_batch
    # follows the cut); a span of 5 of a span of 9 passes through masked
    assert "join_key_range" in classes
    assert "join_key_range" not in classes_all
    assert len(few["x"]) == 5 * 5 and len(every["x"]) == 5
    assert sorted(set(few["y"])) == [1, 2, 3, 4, 5]
