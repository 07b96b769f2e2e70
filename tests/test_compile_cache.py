"""Compile-latency subsystem (ISSUE 10): shape-bucket policy, the
warm-trace compile cache, AOT warmup, and post-shuffle tiny-partition
coalescing.

The determinism contract under test: the SAME plan run twice must build
ZERO new compiled entries the second time (asserted on the compile-cache
hit/miss counters AND the process-wide XLA backend-compile counter), and
fused results must match the unfused chain across masked, ANSI, empty,
and exact-bucket-boundary shapes — padding buckets must never change an
answer.
"""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar.batch import (
    ColumnVector, ColumnarBatch, column_to_numpy, from_pydict,
    round_capacity,
)
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.runtime import compile_cache as CC
from spark_rapids_tpu.runtime import shapes, warmup
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession


# ---------------------------------------------------------------------------
# shape policy (runtime/shapes.py)
# ---------------------------------------------------------------------------

def test_default_policy_is_next_power_of_two():
    # explicit floor: MIN_CAPACITY is session state (batchCapacityMinRows)
    for n in (1, 2, 7, 8, 9, 100, 1023, 1024, 1025, 1 << 20, (1 << 20) + 1):
        expect = 1 << (max(n, 8) - 1).bit_length() if n > 1 else 8
        assert round_capacity(n, minimum=8) == expect


@pytest.mark.parametrize("growth", [1.25, 1.5, 3.0])
@pytest.mark.parametrize("itemsize", [None, 1, 4])
def test_bucket_ladder_fixpoint_and_monotone(growth, itemsize):
    shapes.configure(growth, True)
    caps = sorted({shapes.bucket_rows(n, 8, itemsize)
                   for n in range(1, 200000, 37)})
    for c in caps:  # every ladder value maps to itself
        assert shapes.bucket_rows(c, 8, itemsize) == c
    for n in range(1, 60000, 499):
        assert shapes.bucket_rows(n, 8, itemsize) >= n
    # the ladder is bounded: growth g covers [1, 200k] in O(log) buckets
    assert len(caps) < 64


def test_dtype_alignment_rounds_to_whole_tiles():
    shapes.configure(1.5, True)
    # byte planes (itemsize 1): buckets past one 32x128 tile are
    # whole-tile multiples
    for n in (5000, 50000, 300000):
        cap = shapes.bucket_rows(n, 8, 1)
        assert cap % (32 * 128) == 0
    shapes.configure(1.5, False)
    assert any(shapes.bucket_rows(n, 8, 1) % (32 * 128)
               for n in (5000, 50000, 300000))


def test_growth_factor_clamped():
    shapes.configure(0.5, True)  # <=1 would bucket every row count
    assert shapes.GROWTH_FACTOR > 1.0
    shapes.configure(100.0, True)
    assert shapes.GROWTH_FACTOR <= 4.0


def test_conf_publishes_policy():
    from spark_rapids_tpu.config import set_session_conf
    sess = TpuSession({"spark.rapids.compile.shapes.growthFactor": "1.5"})
    set_session_conf(sess.conf)
    assert shapes.GROWTH_FACTOR == 1.5
    assert round_capacity(1100) != 2048  # tighter than pow2


def test_ensure_bucketed_pads_foreign_batch():
    # a hand-built batch at an off-ladder capacity pads up; values,
    # validity, and the live mask are preserved and the tail is dead
    data = jnp.arange(12, dtype=jnp.int64)
    valid = jnp.asarray([True] * 10 + [False] * 2)
    from spark_rapids_tpu import types as T
    b = ColumnarBatch([ColumnVector(T.Int64Type(), data, valid)], 10)
    out = shapes.ensure_bucketed(b)
    # canonicalization pads to ladder membership (minimum=1), not to the
    # session capacity floor
    assert out.capacity == 16 and out.num_rows == 10
    vals, v = column_to_numpy(out.columns[0], 10)
    assert list(vals) == list(range(10))
    assert bool(out.columns[0].validity[-1]) is False
    # already-bucketed batches pass through untouched (the fixpoint)
    b2 = from_pydict({"a": list(range(20))})
    assert shapes.ensure_bucketed(b2) is b2


# ---------------------------------------------------------------------------
# warm-trace cache determinism
# ---------------------------------------------------------------------------

def _probe_df(sess, rows=2000):
    rng = np.random.default_rng(7)
    t = pa.table({"k": rng.integers(0, 50, rows),
                  "v": rng.random(rows)})
    return (sess.create_dataframe(t)
            .filter(col("v") > lit(0.25))
            .select(col("k"), (col("v") * lit(2.0)).alias("w"))
            .group_by(col("k")).agg(F.sum(col("w")).alias("s")))


def test_same_plan_twice_zero_new_compiles():
    sess = TpuSession()
    df = _probe_df(sess)
    first = df.collect()
    warm = CC.stats()
    second = df.collect()
    after = CC.stats()
    assert after["misses"] == warm["misses"], "second run built new entries"
    assert after["xla_compiles"] == warm["xla_compiles"], \
        "second run triggered backend compiles"
    assert after["hits"] > warm["hits"]
    assert first.to_pydict() == second.to_pydict()


def test_clear_cache_forces_rebuild():
    from spark_rapids_tpu.exec import fuse
    sess = TpuSession()
    df = _probe_df(sess)
    df.collect()
    fuse.clear_cache()
    before = CC.stats()
    df.collect()
    after = CC.stats()
    assert after["misses"] > before["misses"]


def test_ansi_changes_conf_fingerprint():
    sess = TpuSession()
    t = pa.table({"a": [1, 2, 3], "b": [4, 5, 6]})
    df = sess.create_dataframe(t).select((col("a") + col("b")).alias("c"))
    df.collect()
    warm = CC.stats()
    df.collect()
    assert CC.stats()["misses"] == warm["misses"]
    sess2 = TpuSession({"spark.sql.ansi.enabled": "true"})
    df2 = sess2.create_dataframe(t).select((col("a") + col("b")).alias("c"))
    df2.collect()
    assert CC.stats()["misses"] > warm["misses"], \
        "ANSI flip must not share executables"


def test_compile_seconds_counted_and_attributed():
    from spark_rapids_tpu.exec import fuse
    sess = TpuSession()
    fuse.clear_cache()
    before = CC.stats()
    df = _probe_df(sess, rows=512)
    df.collect()
    after = CC.stats()
    assert after["misses"] > before["misses"]
    assert after["compile_ns"] > before["compile_ns"]
    attr = sess.last_attribution()
    assert attr is not None and attr["buckets"]["compile"] > 0


def test_healthz_compile_document():
    from spark_rapids_tpu.runtime import obs
    TpuSession()
    doc = obs.healthz()
    cd = doc.get("compile")
    assert cd is not None
    for k in ("warm_entries", "hits", "misses", "xla_compiles",
              "persistent_hits", "persistent_misses"):
        assert k in cd


# ---------------------------------------------------------------------------
# placing the persistent cache: ONE site, the environment wins
# ---------------------------------------------------------------------------

_CACHE_OPTS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_placement(monkeypatch):
    """An unplaced persistent cache on a pretend accelerator, every
    jax.config.update recorded, and jax's cache options put back as they
    were (no compile happens while a test holds a directory, so nothing
    is ever written)."""
    import jax
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTS}
    monkeypatch.setattr(CC, "_PLACED", None)
    monkeypatch.setattr(CC, "_IGNORED", set())
    monkeypatch.setattr(CC, "_on_cpu_simulator", lambda: False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = []
    real = jax.config.update

    def recording(name, value):
        calls.append((name, value))
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", recording)
    had_default = os.path.isdir(CC.DEFAULT_CACHE_DIR)
    yield calls
    for k, v in saved.items():
        real(k, v)
    if not had_default and os.path.isdir(CC.DEFAULT_CACHE_DIR):
        os.rmdir(CC.DEFAULT_CACHE_DIR)  # placed, never written to


def test_env_cache_dir_wins_and_no_directory_is_set_in_code(
        cache_placement, monkeypatch, tmp_path, caplog):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    with caplog.at_level("WARNING", logger="spark_rapids_tpu"):
        TpuSession({"spark.rapids.compile.cacheDir":
                    str(tmp_path / "from_conf")})
        TpuSession({"spark.rapids.compile.cacheDir":
                    str(tmp_path / "from_conf")})
        TpuSession()
    set_opts = dict(cache_placement)
    assert "jax_compilation_cache_dir" not in set_opts
    assert not (tmp_path / "from_conf").exists()
    # the entry thresholds are set in the same place in this case too
    assert set_opts["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert set_opts["jax_persistent_cache_min_entry_size_bytes"] == -1
    assert CC._PLACED == env_dir
    ignored = [r for r in caplog.records
               if "JAX_COMPILATION_CACHE_DIR" in r.getMessage()]
    assert len(ignored) == 1  # logged once, not per session


def test_default_cache_dir_is_fixed_inside_the_checkout(cache_placement):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CC.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    TpuSession()
    assert ("jax_compilation_cache_dir", CC.DEFAULT_CACHE_DIR) \
        in cache_placement
    assert CC.stats()["persistent_dir"] == CC.DEFAULT_CACHE_DIR
    assert CC.doc()["persistent_dir"] == CC.DEFAULT_CACHE_DIR
    set_opts = dict(cache_placement)
    assert set_opts["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert set_opts["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_conf_cache_dir_applies_when_env_is_unset(cache_placement,
                                                   tmp_path):
    d = str(tmp_path / "from_conf")
    TpuSession({"spark.rapids.compile.cacheDir": d})
    assert ("jax_compilation_cache_dir", d) in cache_placement
    assert CC.stats()["persistent_dir"] == d
    # first placement wins: a later default session changes nothing
    del cache_placement[:]
    TpuSession()
    assert cache_placement == []


def test_cpu_simulator_keeps_no_default_cache(cache_placement,
                                              monkeypatch):
    monkeypatch.setattr(CC, "_on_cpu_simulator", lambda: True)
    TpuSession()
    assert cache_placement == [] and CC._PLACED is None
    assert CC.stats()["persistent_dir"] is None


def test_nothing_else_sets_the_cache_directory():
    """The package's only jax_compilation_cache_dir write is in
    compile_cache.configure; __init__ places nothing."""
    pkg = os.path.dirname(os.path.abspath(CC.__file__))
    pkg = os.path.dirname(pkg)
    hits = []
    for root, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    if 'update("jax_compilation_cache_dir"' in fh.read():
                        hits.append(os.path.relpath(path, pkg))
    assert hits == [os.path.join("runtime", "compile_cache.py")]


# ---------------------------------------------------------------------------
# bucket-padding correctness: fused/unfused parity at boundary shapes
# ---------------------------------------------------------------------------

def _parity_table(rows):
    rng = np.random.default_rng(rows + 1)
    return pa.table({
        "k": rng.integers(0, 7, rows).astype(np.int64),
        "v": rng.integers(-1000, 1000, rows).astype(np.int64),
        "d": rng.random(rows),
    })


def _parity_query(df):
    return (df.filter(col("v") > lit(0))
            .select(col("k"), (col("v") * lit(3)).alias("w"),
                    col("d"))
            .group_by(col("k")).agg(F.sum(col("w")).alias("sw"),
                                    F.count(col("d")).alias("c")))


def _canon(table):
    rows = sorted(map(tuple, zip(*[table[c].to_pylist()
                                   for c in table.column_names])))
    return [tuple(round(v, 9) if isinstance(v, float) else v for v in r)
            for r in rows]


#: 8 = exactly one minimum bucket, 9 = one past the boundary, 64 = an
#: exact larger bucket, 0-survivor case exercised via the filter below
@pytest.mark.parametrize("rows", [8, 9, 64, 1000])
@pytest.mark.parametrize("ansi", [False, True])
def test_fused_unfused_parity_at_bucket_boundaries(rows, ansi):
    base = {"spark.rapids.tpu.batchCapacityMinRows": "8",
            "spark.sql.ansi.enabled": ansi}
    t = _parity_table(rows)
    fused = _parity_query(TpuSession(base).create_dataframe(t)).collect()
    unfused = _parity_query(TpuSession(
        dict(base, **{"spark.rapids.sql.stageFusion.enabled": "false"})
    ).create_dataframe(t)).collect()
    assert _canon(fused) == _canon(unfused)


def test_fused_unfused_parity_empty_result():
    base = {"spark.rapids.tpu.batchCapacityMinRows": "8"}
    t = _parity_table(64)

    def q(sess):
        return (sess.create_dataframe(t)
                .filter(col("v") > lit(10_000))  # nothing survives
                .select((col("v") + lit(1)).alias("w"))).collect()

    a = q(TpuSession(base))
    b = q(TpuSession(dict(base, **{
        "spark.rapids.sql.stageFusion.enabled": "false"})))
    assert a.num_rows == 0 and b.num_rows == 0
    assert a.schema == b.schema


# ---------------------------------------------------------------------------
# AOT warmup (runtime/warmup.py)
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_obs():
    """The obs singleton pins the FIRST session's historyDir for the
    process — these tests need their own tmp store, so tear the
    singleton down around them."""
    from spark_rapids_tpu.runtime import obs
    obs.shutdown_for_tests()
    yield
    obs.shutdown_for_tests()


def _seed_history(tmp_path, runs=2):
    hist = str(tmp_path / "hist")
    path = str(tmp_path / "t.parquet")
    import pyarrow.parquet as pq
    pq.write_table(pa.table({"a": list(range(200)),
                             "b": [float(i) for i in range(200)]}), path)
    s1 = TpuSession({"spark.rapids.obs.historyDir": hist})
    s1.create_or_replace_temp_view("t", s1.read_parquet(path))
    for _ in range(runs):
        s1.sql("SELECT a, SUM(b) AS sb FROM t WHERE a > 10 "
               "GROUP BY a").collect()
    return hist, path


def test_history_records_carry_sql(tmp_path, fresh_obs):
    hist, _ = _seed_history(tmp_path)
    recs = [json.loads(ln) for ln in
            open(os.path.join(hist, "query_history.jsonl"))]
    assert all(r.get("sql", "").startswith("SELECT") for r in recs)
    assert len({r["plan_digest"] for r in recs}) == 1


def test_warmup_replays_prime_the_cache(tmp_path, fresh_obs):
    hist, path = _seed_history(tmp_path)
    warmup.reset_for_tests()
    n_hist = len(open(os.path.join(hist, "query_history.jsonl"))
                 .readlines())
    s2 = TpuSession({"spark.rapids.obs.historyDir": hist,
                     "spark.rapids.compile.warmup.enabled": "true"})
    mgr = warmup.manager()
    assert mgr is not None and mgr.doc()["pending"] == 1
    s2.create_or_replace_temp_view("t", s2.read_parquet(path))
    assert mgr.wait(60), "warmup never drained"
    doc = mgr.doc()
    assert doc["replayed"] == 1 and doc["failed"] == 0
    # replays are cache-priming, not user queries: no history growth
    assert len(open(os.path.join(hist, "query_history.jsonl"))
               .readlines()) == n_hist
    # the user's first run of the warmed plan builds NOTHING new
    before = CC.stats()
    s2.sql("SELECT a, SUM(b) AS sb FROM t WHERE a > 10 "
           "GROUP BY a").collect()
    after = CC.stats()
    assert after["misses"] == before["misses"]
    assert after["xla_compiles"] == before["xla_compiles"]


def test_warmup_ranking_prefers_recurrence():
    recs = (
        [{"type": "query", "status": "ok", "plan_digest": "aa",
          "sql": "SELECT 1"}] * 3
        + [{"type": "query", "status": "ok", "plan_digest": "bb",
            "sql": "SELECT 2"}] * 5
        + [{"type": "query", "status": "failed", "plan_digest": "cc",
            "sql": "SELECT 3"}] * 9           # failed: never replayed
        + [{"type": "query", "status": "ok", "plan_digest": "dd",
            "sql": "SELECT 4"}]               # below minRuns
        + [{"type": "nds_scorecard", "plan_digest": "ee"}] * 9)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "query_history.jsonl"), "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        hot = warmup._hot_plans(d, min_runs=2, max_plans=8)
    assert [h["digest"] for h in hot] == ["bb", "aa"]


def test_warmup_replay_failure_never_raises(tmp_path, fresh_obs):
    hist, path = _seed_history(tmp_path)
    warmup.reset_for_tests()
    s2 = TpuSession({"spark.rapids.obs.historyDir": hist,
                     "spark.rapids.compile.warmup.enabled": "true"})
    mgr = warmup.manager()
    # the shadow session inherits s2's conf: an injected scan ioerror
    # makes the replay fail — it must be counted, never raised
    s2.conf.set("spark.rapids.debug.faults", "scan.decode:ioerror:1")
    s2.create_or_replace_temp_view("t", s2.read_parquet(path))
    assert mgr.wait(60)
    doc = mgr.doc()
    assert doc["failed"] == 1 and doc["replayed"] == 0
    # the session (fault disarmed after one shot) still answers
    s2.conf.set("spark.rapids.debug.faults", "")
    assert s2.sql("SELECT a FROM t").collect().num_rows == 200


def test_warmup_not_armed_without_history():
    warmup.reset_for_tests()
    TpuSession({"spark.rapids.compile.warmup.enabled": "true"})
    assert warmup.manager() is None


# ---------------------------------------------------------------------------
# post-shuffle tiny-partition coalescing
# ---------------------------------------------------------------------------

def _shuffle_df(sess, parts=8):
    rng = np.random.default_rng(0)
    t = pa.table({"k": rng.integers(0, 5000, 20000),
                  "v": rng.random(20000)})
    return (sess.create_dataframe(t, num_partitions=4)
            .repartition(parts, col("k"))
            .group_by(col("k")).agg(F.sum(col("v")).alias("s"))), t


def _coalesced(sess):
    return sum(v.get("shuffleCoalescedBatches", 0)
               for v in sess.last_metrics().values())


def test_coalesce_merges_tiny_sub_batches():
    sess = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "512"})
    df, t = _shuffle_df(sess)
    out = df.collect()
    assert _coalesced(sess) > 0, "coalescing never engaged"
    ref = t.group_by(["k"]).aggregate([("v", "sum")])
    got = sorted(zip(out["k"].to_pylist(),
                     (round(x, 9) for x in out["s"].to_pylist())))
    want = sorted(zip(ref["k"].to_pylist(),
                      (round(x, 9) for x in ref["v_sum"].to_pylist())))
    assert got == want


# Heaviest single test in the suite (~60-130s: the disabled path recompiles
# every tiny sub-batch shape); the coalesce-on representatives above keep the
# feature covered in tier-1, the off-switch runs under the full @slow/CI pass.
@pytest.mark.slow
def test_coalesce_disabled_by_conf():
    sess = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "512",
                       "spark.rapids.shuffle.coalesceTinyRows": "0"})
    df, _ = _shuffle_df(sess)
    df.collect()
    assert _coalesced(sess) == 0


def test_coalesce_respects_budget_and_order():
    from spark_rapids_tpu.exec import tpu_nodes as X

    class _Exch:
        def __init__(self, conf):
            self.conf = conf
            self.n_out = 4
            from spark_rapids_tpu.runtime.metrics import MetricsRegistry
            self.metrics = MetricsRegistry()
        _coalesce_tiny = X.ExchangeExec._coalesce_tiny
        _flush_coalesce_run = X.ExchangeExec._flush_coalesce_run

    conf = C.RapidsConf({"spark.rapids.shuffle.coalesceTinyRows": "100"})
    ex = _Exch(conf)
    mk = lambda lo, n: from_pydict(  # noqa: E731
        {"a": list(range(lo, lo + n))})
    batches = [mk(0, 60), mk(60, 60), mk(120, 60), mk(180, 60),
               mk(240, 60), mk(300, 5000), mk(5300, 30), mk(5330, 30)]
    out = list(ex._coalesce_tiny(iter(batches)))
    rows = [int(b.num_rows) for b in out]
    # budget 400: the five 60s merge as 300, the big batch passes
    # through, the two 30s merge — order preserved end to end
    assert rows == [300, 5000, 60]
    flat = []
    for b in out:
        vals, _ = column_to_numpy(b.columns[0], int(b.num_rows))
        flat.extend(int(v) for v in vals)
    assert flat == list(range(5360))
    assert ex.metrics.metric("shuffleCoalescedBatches").value == 7
