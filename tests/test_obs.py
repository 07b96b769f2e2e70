"""Live observability tests: registry (concurrency, histogram quantiles,
Prometheus rendering), /metrics + /healthz endpoint, query history store
(round-trip, digest stability, failure records), EXPLAIN ANALYZE, retry
re-execution accounting, and the history/trace cross-links.

Reference parity: the SQL-UI metric surface + driver-side liveness
registry (SURVEY.md §5.5 / :170) recast for a standalone engine: a
scrapeable process registry, a health signal, and a history store that
survives the process.
"""
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.expr.core import SparkException, col, lit
from spark_rapids_tpu.runtime import obs
from spark_rapids_tpu.runtime.obs.history import (QueryHistoryStore,
                                                  plan_digest)
from spark_rapids_tpu.runtime.obs.registry import (Counter, Histogram,
                                                   MetricsRegistry)
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import obs_smoke  # noqa: E402
import profiler_report as PR  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test gets its own obs singleton (ports, history dirs)."""
    obs.shutdown_for_tests()
    yield
    obs.shutdown_for_tests()


def _table(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(0, 40, n),
                     "v": rng.integers(1, 1000, n),
                     "d": rng.uniform(0, 1, n)})


def _query(s, t=None):
    return (s.create_dataframe(t if t is not None else _table(),
                               num_partitions=2)
            .filter(col("v") > lit(10))
            .select(col("k"), (col("v") * lit(2)).alias("v2"))
            .group_by("k").agg(F.sum(col("v2")).alias("sv")).collect())


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_counter_concurrent_publish_no_lost_updates():
    c = Counter("c")
    n_threads, per = 16, 5000

    def worker():
        for _ in range(per):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per


def test_registry_concurrent_publish_from_host_pool():
    # the deployment shape: host-pool worker threads all folding task
    # accumulators into the SAME registry instruments
    from spark_rapids_tpu.runtime.host_pool import (get_host_pool,
                                                    reset_host_pool)
    reg = MetricsRegistry()

    def publish(i):
        reg.counter("rapids_test_total").inc(2)
        reg.histogram("rapids_test_ms").observe(float(i % 50 + 1))
        return i

    reset_host_pool()
    try:
        pool = get_host_pool()
        out = list(pool.map_ordered(publish, range(400)))
        assert out == list(range(400))
        assert reg.counter("rapids_test_total").value == 800
        assert reg.histogram("rapids_test_ms").count == 400
    finally:
        reset_host_pool()


@pytest.mark.parametrize("dist,seed", [
    ("lognormal", 11), ("lognormal", 12), ("uniform", 13),
    ("exponential", 14), ("bimodal", 15)])
def test_histogram_quantiles_vs_numpy(dist, seed):
    rng = np.random.default_rng(seed)
    n = 5000
    xs = {
        "lognormal": rng.lognormal(3.0, 1.5, n),
        "uniform": rng.uniform(1.0, 1e6, n),
        "exponential": rng.exponential(1e4, n) + 1e-3,
        # 40/60 split keeps p50/p95/p99 INSIDE a mode (at a 50/50 split
        # the true median sits in the empty gap between modes, where
        # nearest-rank and linear interpolation legitimately disagree)
        "bimodal": np.concatenate([rng.normal(100, 5, 2 * n // 5),
                                   rng.normal(1e5, 1e3, 3 * n // 5)]),
    }[dist]
    xs = np.abs(xs) + 1e-9
    h = Histogram("h")
    for x in xs:
        h.observe(float(x))
    for q in (0.50, 0.95, 0.99):
        est = h.quantile(q)
        exact = float(np.percentile(xs, q * 100))
        assert abs(est - exact) / exact < 0.12, \
            (dist, q, est, exact)
    snap = h.snapshot()
    assert snap["count"] == len(xs)
    assert snap["min"] == pytest.approx(float(xs.min()))
    assert snap["max"] == pytest.approx(float(xs.max()))


def test_histogram_memory_is_bounded():
    h = Histogram("h")
    rng = np.random.default_rng(0)
    # 13 orders of magnitude of observations
    for x in 10.0 ** rng.uniform(-3, 10, 100_000):
        h.observe(float(x))
    # 13 decades * log2(10) octaves * 8 sub-buckets ~ 346 max
    assert h.bucket_count() < 400
    assert h.count == 100_000


def test_histogram_edge_cases():
    h = Histogram("h")
    assert h.quantile(0.5) == 0.0  # empty
    h.observe(0.0)
    h.observe(-5.0)
    h.observe(42.0)
    assert h.quantile(0.99) <= 42.0
    assert h.snapshot()["min"] == -5.0


def test_prometheus_render_parseable_and_typed():
    reg = MetricsRegistry()
    reg.counter("rapids_a_total", "a counter").inc(3)
    reg.gauge("rapids_g", "a gauge").set(1.5)
    reg.gauge_fn("rapids_live", lambda: 7, "live gauge",
                 labels={"tier": "t0"})
    h = reg.histogram("rapids_h_ms", "a histogram")
    for v in (1.0, 10.0, 100.0):
        h.observe(v)
    text = reg.render_prometheus()
    n = obs_smoke.check_prometheus(text)  # raises on malformed lines
    assert n >= 7  # 1 counter + 2 gauges + 3 quantiles + sum + count
    assert "# TYPE rapids_a_total counter" in text
    assert "# TYPE rapids_g gauge" in text
    assert "# TYPE rapids_h_ms summary" in text
    assert 'rapids_live{tier="t0"} 7.0' in text
    assert "rapids_h_ms_count 3" in text


def test_registry_type_conflict_fails_fast():
    reg = MetricsRegistry()
    reg.counter("rapids_x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("rapids_x")


# ---------------------------------------------------------------------------
# publish path: task + query folding
# ---------------------------------------------------------------------------

def test_task_and_query_publish(tmp_path):
    # historyDir makes the store a rollup consumer; without one (and
    # without a port) the per-exec publish is skipped (no device syncs
    # for series nothing reads — see test below)
    s = TpuSession({"spark.rapids.obs.historyDir": str(tmp_path)})
    _query(s)
    st = obs.state()
    assert st is not None
    snap = st.registry.snapshot()
    assert snap["rapids_tasks_completed_total"] >= 1
    assert snap['rapids_queries_total{status="ok"}'] == 1
    assert snap["rapids_query_wall_time_ms"]["count"] == 1
    # per-exec rollups landed with bounded exec-class labels
    assert any(k.startswith("rapids_exec_rows_total") for k in snap)


def test_exec_rollups_skipped_without_consumer():
    s = TpuSession()  # registry only: no endpoint, no history store
    _query(s)
    snap = obs.state().registry.snapshot()
    assert snap['rapids_queries_total{status="ok"}'] == 1
    assert not any(k.startswith("rapids_exec_") for k in snap)


def test_nested_query_joins_outer_and_unwinds():
    s = TpuSession()
    _query(s)  # installs obs
    before = obs.state().registry.snapshot()['rapids_queries_total'
                                             '{status="ok"}']
    tok = obs.on_query_start()
    assert isinstance(tok, int)
    nested = obs.on_query_start()  # re-entrant on this thread
    assert nested is obs.NESTED

    def end(t):
        obs.on_query_end(t, session=s, plan=None, status="ok",
                         error=None, duration_ns=1,
                         wall_start_unix=time.time(), trace_paths=None)

    end(nested)  # publishes nothing, unwinds depth
    snap = obs.state().registry.snapshot()
    assert snap['rapids_queries_total{status="ok"}'] == before
    end(tok)
    snap = obs.state().registry.snapshot()
    assert snap['rapids_queries_total{status="ok"}'] == before + 1
    # depth fully unwound: the next action is top-level again
    tok2 = obs.on_query_start()
    assert isinstance(tok2, int) and tok2 > tok
    end(tok2)


def test_concurrent_top_level_queries_all_count():
    # overlapping queries from different threads/sessions must each
    # publish (a serving process's /metrics cannot undercount load)
    sessions = [TpuSession() for _ in range(3)]
    errors = []

    def run(s):
        try:
            _query(s)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=run, args=(s,)) for s in sessions]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    snap = obs.state().registry.snapshot()
    assert snap['rapids_queries_total{status="ok"}'] == 3
    assert snap["rapids_query_wall_time_ms"]["count"] == 3


def test_obs_disabled_is_one_global_read():
    assert obs.state() is None
    s = TpuSession({"spark.rapids.obs.enabled": "false"})
    _query(s)
    assert obs.state() is None  # nothing installed, nothing published


# ---------------------------------------------------------------------------
# endpoint
# ---------------------------------------------------------------------------

def test_endpoint_scrape_and_healthz_flip(tmp_path):
    port = obs_smoke._free_port()
    s = TpuSession({"spark.rapids.obs.port": str(port),
                    "spark.rapids.obs.probeTimeoutMs": "400"})
    errors = []

    def driver():
        try:
            for _ in range(2):
                _query(s, _table(100_000))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    th = threading.Thread(target=driver)
    th.start()
    mid = 0
    while th.is_alive():
        code, body = obs_smoke._get(f"http://127.0.0.1:{port}/metrics")
        assert code == 200
        obs_smoke.check_prometheus(body)
        mid += 1
        time.sleep(0.02)
    th.join()
    assert not errors and mid >= 1
    code, body = obs_smoke._get(f"http://127.0.0.1:{port}/metrics")
    for name in obs_smoke.ROSTER:
        assert name in body, name
    code, hz = obs_smoke._get(f"http://127.0.0.1:{port}/healthz")
    doc = json.loads(hz)
    assert code == 200 and doc["status"] == "ok"
    assert doc["device"]["alive"] and doc["semaphore"]["permits"] >= 1
    assert doc["queries"]["completed_ok"] >= 2
    # blocked probe -> degraded + 503 (the liveness acceptance criterion)
    obs.set_device_probe(lambda: time.sleep(30) or True)
    code, hz = obs_smoke._get(f"http://127.0.0.1:{port}/healthz")
    doc = json.loads(hz)
    assert code == 503 and doc["status"] == "degraded"
    assert doc["device"]["blocked"]
    code, _ = obs_smoke._get(f"http://127.0.0.1:{port}/")
    assert code == 200


# ---------------------------------------------------------------------------
# history store
# ---------------------------------------------------------------------------

def test_history_round_trip_and_digest_stability(tmp_path):
    s = TpuSession({"spark.rapids.obs.historyDir": str(tmp_path)})
    _query(s)
    _query(s)
    # a DIFFERENT query must get a different digest
    s.create_dataframe(_table()).filter(col("v") > lit(999)).collect()
    recs = QueryHistoryStore(str(tmp_path)).read_all()
    assert len(recs) == 3
    assert {r["status"] for r in recs} == {"ok"}
    d1, d2, d3 = (r["plan_digest"] for r in recs)
    assert d1 == d2 and d1 != d3
    assert QueryHistoryStore(str(tmp_path)).by_digest(d1) == recs[:2]
    # rollups + plan + conf delta persisted
    r = recs[0]
    assert r["physical_plan"] and r["execs"]
    assert any(v["_rollup"]["rows"] > 0 for v in r["execs"].values())
    assert C.OBS_HISTORY_DIR.key in r["conf_delta"]
    assert r["duration_ns"] > 0 and r["query_id"] == 1


def test_plan_digest_is_process_independent():
    # same logical plan built twice (fresh objects) -> same digest
    s1, s2 = TpuSession(), TpuSession()
    t = _table()
    p1 = s1.create_dataframe(t).filter(col("v") > lit(5)).plan
    p2 = s2.create_dataframe(t).filter(col("v") > lit(5)).plan
    assert plan_digest(p1) == plan_digest(p2)
    p3 = s1.create_dataframe(t).filter(col("v") > lit(6)).plan
    assert plan_digest(p1) != plan_digest(p3)


def test_digest_stable_across_cache_state():
    s = TpuSession()
    df = s.create_dataframe(_table()).cache().filter(col("v") > lit(5))
    d_cold = plan_digest(df.plan)
    df.collect()  # materializes the cache (describe() would flip hot)
    assert plan_digest(df.plan) == d_cold


def test_failed_query_recorded_and_trace_finalized(tmp_path):
    # satellite: a query that raises mid-collect must still flush its
    # trace (with an error marker) and land in history as failed
    s = TpuSession({
        "spark.rapids.obs.historyDir": str(tmp_path / "hist"),
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.path": str(tmp_path / "tr"),
        "spark.sql.ansi.enabled": "true"})
    t = pa.table({"v": [1, 2, 3, 4], "z": [1, 1, 0, 1]})
    df = s.create_dataframe(t).select((col("v") / col("z")).alias("x"))
    with pytest.raises(SparkException):
        df.collect()
    # trace artifacts exist and validate despite the failure
    paths = s.last_trace_paths
    assert paths is not None and os.path.exists(paths["trace"])
    events = PR.validate_chrome_trace(paths["trace"])
    err = [e for e in events if e["ph"] == "i" and e["name"] == "queryError"]
    assert err and err[0]["args"]["error"] == "SparkException"
    with open(paths["events"]) as f:
        qrec = json.loads(f.readline())
    assert qrec["status"] == "failed"
    assert qrec["error_class"] == "SparkException"
    assert qrec["plan_digest"]
    # history: status=failed + exception class (the satellite contract)
    recs = QueryHistoryStore(str(tmp_path / "hist")).read_all()
    assert len(recs) == 1
    assert recs[0]["status"] == "failed"
    assert recs[0]["error_class"] == "SparkException"
    assert recs[0]["plan_digest"] == qrec["plan_digest"]
    # the engine is healthy for the next query
    _query(s)
    recs = QueryHistoryStore(str(tmp_path / "hist")).read_all()
    assert recs[-1]["status"] == "ok"


# ---------------------------------------------------------------------------
# retry re-execution accounting (satellite)
# ---------------------------------------------------------------------------

def _task_rollups(paths):
    out = []
    with open(paths["events"]) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "task":
                out.append(rec)
    return out


def test_retry_reexecution_tagged_and_split_out(tmp_path):
    s = TpuSession({"spark.rapids.sql.test.injectRetryOOM": "1",
                    "spark.rapids.sql.trace.enabled": "true",
                    "spark.rapids.sql.trace.path": str(tmp_path)})
    t = pa.table({"k": ["a", "b"] * 32, "v": list(range(64))})
    got = s.create_dataframe(t).group_by("k") \
        .agg(F.sum(col("v"))).collect().to_pylist()
    assert sorted(r["k"] for r in got) == ["a", "b"]
    # task rollups report attempt count AND the replayed-attempt time
    # separately from the exec timers (first-attempt = timer - wasted)
    recs = _task_rollups(s.last_trace_paths)
    assert any(r["metrics"].get("retryCount", 0) >= 1 for r in recs)
    assert any(r["metrics"].get("retryWastedTime", 0) > 0 for r in recs)
    events = PR.validate_chrome_trace(s.last_trace_paths["trace"])
    attempts = [e for e in events
                if e["ph"] == "X" and e["name"] == "retryAttempt"]
    assert attempts, "failed attempt must be a tagged span"
    assert attempts[0]["args"]["retried"] is True
    assert attempts[0]["args"]["attempt"] == 1
    succ = [e for e in events
            if e["ph"] == "i" and e["name"] == "retrySucceeded"]
    assert succ and succ[0]["args"]["attempts"] == 2
    # registry side: the wasted-time counter advanced
    snap = obs.state().registry.snapshot()
    assert snap["rapids_retries_total"] >= 1
    assert snap["rapids_retry_wasted_ns_total"] > 0


def test_split_retry_wasted_time_accounted(tmp_path):
    # the split flavor replays work too: its failed attempt must be a
    # tagged span and count into retryWastedTime like a plain retry
    s = TpuSession({"spark.rapids.sql.test.injectRetryOOM": "1,0,split",
                    "spark.rapids.sql.trace.enabled": "true",
                    "spark.rapids.sql.trace.path": str(tmp_path)})
    t = pa.table({"k": ["a", "b"] * 32, "v": list(range(64))})
    got = s.create_dataframe(t).group_by("k") \
        .agg(F.sum(col("v"))).collect().to_pylist()
    assert sorted(r["k"] for r in got) == ["a", "b"]
    recs = _task_rollups(s.last_trace_paths)
    assert any(r["metrics"].get("splitAndRetryCount", 0) >= 1
               for r in recs)
    assert any(r["metrics"].get("retryWastedTime", 0) > 0 for r in recs)
    events = PR.validate_chrome_trace(s.last_trace_paths["trace"])
    attempts = [e for e in events
                if e["ph"] == "X" and e["name"] == "retryAttempt"]
    assert attempts and attempts[0]["args"].get("split") is True


def test_semaphore_hold_time_accumulates(tmp_path):
    s = TpuSession({"spark.rapids.sql.trace.enabled": "true",
                    "spark.rapids.sql.trace.path": str(tmp_path)})
    _query(s)
    recs = _task_rollups(s.last_trace_paths)
    assert any(r["metrics"].get("semaphoreHoldTime", 0) > 0
               for r in recs), recs


def test_serialized_shuffle_bytes_metric(tmp_path):
    # historyDir makes obs a rollup consumer, so the registry counter
    # must mirror the exchange's GpuMetric
    s = TpuSession({"spark.rapids.shuffle.mode": "SERIALIZED",
                    "spark.rapids.obs.historyDir": str(tmp_path)})
    t = _table(3000)
    (s.create_dataframe(t, num_partitions=3)
     .group_by("k").agg(F.sum(col("v"))).collect())
    written = sum(snap.get("shuffleBytesWritten", 0)
                  for snap in s.last_metrics().values())
    assert written > 0
    snap = obs.state().registry.snapshot()
    assert snap["rapids_shuffle_bytes_written_total"] == written


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE
# ---------------------------------------------------------------------------

def test_explain_analyze_matches_last_metrics(capsys):
    from spark_rapids_tpu.runtime.metrics import exec_rollup
    s = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "1024"})
    df = (s.create_dataframe(_table(8000), num_partitions=1)
          .filter(col("v") > lit(5))
          .select(col("k"), (col("v") + lit(1)).alias("v1"), col("d"))
          .filter(col("d") < lit(0.95))
          .select(col("k"), (col("v1") * lit(3)).alias("v3"))
          .group_by("k").agg(F.sum(col("v3")).alias("s3")))
    text = df.explain(mode="analyze")
    capsys.readouterr()
    snaps = s.last_metrics()
    assert snaps, "analyze must execute the query"
    # every annotated line's numbers must match last_metrics exactly
    lines = text.splitlines()
    assert len(lines) >= len(snaps)
    i = 0
    for key, snap in snaps.items():
        r = exec_rollup(snap)
        cls = key.split("#", 1)[0]
        line = lines[i]
        assert cls in line, (key, line)
        assert f"rows={r['rows']}" in line, (key, line)
        assert f"batches={r['batches']}" in line, (key, line)
        if r["dispatches"]:
            assert f"dispatches={r['dispatches']}" in line, (key, line)
        assert f"time={r['time_ns'] / 1e6:.3f}ms" in line, (key, line)
        i += 1
    # the fused scan->filter->project chain shows real numbers
    assert "*(" in text  # fusion-group marker
    scan = [ln for ln in lines if "InMemoryScanExec" in ln]
    assert scan and "rows=8000" in scan[0]


def test_explain_analyze_without_action():
    s = TpuSession()
    assert "no executed plan" in s.explain_analyze()


def test_fusion_groups_export():
    from spark_rapids_tpu.exec.stage_fusion import fusion_groups
    s = TpuSession()
    (s.create_dataframe(_table(), num_partitions=1)
     .filter(col("v") > lit(5))
     .select(col("k"), (col("v") + lit(1)).alias("v1"))
     .filter(col("v1") < lit(1900))
     .select((col("v1") * lit(2)).alias("v2"))
     .collect())
    groups = fusion_groups(s._last_exec)
    assert groups, "expected at least one fused stage"
    g = groups[0]
    assert g["kind"] in ("fused", "absorbed")
    assert len(g["members"]) >= 2 and g["stage_id"] is not None


# ---------------------------------------------------------------------------
# history server + profiler report cross-link
# ---------------------------------------------------------------------------

def test_history_server_renders_diffable_pair(tmp_path):
    import history_server as HS
    hist = tmp_path / "hist"
    s = TpuSession({"spark.rapids.obs.historyDir": str(hist)})
    _query(s)
    _query(s)  # same digest: a diffable pair
    s.create_dataframe(_table()).filter(col("v") > lit(0)).collect()
    out = tmp_path / "html"
    written = HS.render_site(str(hist), str(out))
    assert "index.html" in written
    diffs = [n for n in written if n.startswith("diff_")]
    assert len(diffs) == 1, "two runs of one digest -> one diff page"
    idx = open(written["index.html"]).read()
    assert idx.count("query_") >= 3
    qpages = [n for n in written if n.startswith("query_")]
    assert len(qpages) == 3
    body = open(written[qpages[0]]).read()
    for frag in ("Annotated plan", "rows=", "time="):
        assert frag in body, frag
    diff_body = open(written[diffs[0]]).read()
    assert "→" in diff_body and "Δ time" in diff_body


def test_history_server_marks_failures_and_fallbacks(tmp_path):
    hist = tmp_path / "hist"
    s = TpuSession({"spark.rapids.obs.historyDir": str(hist),
                    "spark.sql.ansi.enabled": "true"})
    t = pa.table({"v": [1, 2], "z": [1, 0]})
    with pytest.raises(SparkException):
        s.create_dataframe(t).select((col("v") / col("z")).alias("x")) \
            .collect()
    import history_server as HS
    written = HS.render_site(str(hist), str(tmp_path / "html"))
    idx = open(written["index.html"]).read()
    assert "failed" in idx
    qpage = [p for n, p in written.items() if n.startswith("query_")][0]
    assert "SparkException" in open(qpage).read()


def test_profiler_report_history_cross_link(tmp_path):
    s = TpuSession({
        "spark.rapids.obs.historyDir": str(tmp_path / "hist"),
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.path": str(tmp_path / "tr")})
    _query(s)
    art = PR.load_artifacts(s.last_trace_paths["trace"])
    rec = PR.cross_link_history(art, str(tmp_path / "hist"))
    assert rec is not None
    # the trace and the history record resolve to the SAME query: shared
    # digest AND the record points back at this very trace file
    assert rec["plan_digest"] == art["query"]["plan_digest"]
    assert os.path.abspath(rec["trace_paths"]["trace"]) == \
        os.path.abspath(s.last_trace_paths["trace"])
    report = PR.generate_report(art, history_rec=rec)
    assert "History cross-link" in report


def test_nds_scorecard_history_round_trip(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "nds_probe", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "nds_probe.py"))
    nds = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(nds)
    s = TpuSession()
    plan = s.create_dataframe(_table()).filter(col("v") > lit(1)).plan
    nds.append_scorecard(str(tmp_path), 5,
                         {"status": "ok", "device": "clean",
                          "rows": 10, "seconds": 0.5}, plan, time.time(),
                         sf=0.01)
    nds.append_scorecard(str(tmp_path), 5,
                         {"status": "ok", "device": "clean",
                          "rows": 10, "seconds": 0.4}, plan, time.time(),
                         sf=0.01)
    # a failure record at the same sf, later: latest run wins means the
    # regression shows; a different sf must NOT leak into the summary
    nds.append_scorecard(str(tmp_path), 7, {"status": "error",
                                            "error": "boom"},
                         None, time.time(), sf=0.01)
    nds.append_scorecard(str(tmp_path), 9, {"status": "ok", "rows": 1,
                                            "seconds": 9.9},
                         None, time.time(), sf=1.0)
    summary = nds.scorecard_from_history(str(tmp_path), sf=0.01)
    assert summary["translated"] == 2 and summary["ok"] == 1
    assert summary["queries"]["q5"]["seconds"] == 0.4  # latest run wins
    assert summary["queries"]["q7"]["status"] == "error"
    assert summary["queries"]["q9"] == {"status": "not_translated"}
    assert summary["queries"]["q1"] == {"status": "not_translated"}


def test_healthz_endpoint_free_port_scrape_via_urllib():
    # regression: the endpoint must bind 127.0.0.1 only and answer 404
    # for unknown paths
    port = obs_smoke._free_port()
    TpuSession({"spark.rapids.obs.port": str(port)})
    code, _ = obs_smoke._get(f"http://127.0.0.1:{port}/nope")
    assert code == 404
    req = urllib.request.Request(f"http://127.0.0.1:{port}/metrics")
    with urllib.request.urlopen(req, timeout=5) as r:
        assert "text/plain" in r.headers["Content-Type"]


# ---------------------------------------------------------------------------
# the per-query phase account (runtime/obs/phases.py) and its ring
# ---------------------------------------------------------------------------

_WALL_PHASES = ("admit", "plan", "execute", "epilogue", "unspanned")


def test_recent_queries_one_record_per_top_level_action():
    from spark_rapids_tpu.sql.session import nested_action_scope
    s = TpuSession()
    s.create_or_replace_temp_view("t", s.create_dataframe(_table()))
    assert obs.recent_queries() == []
    s.sql("select k, sum(v) as sv from t where v > 10 group by k").collect()
    _query(s)
    with nested_action_scope():  # as a broadcast materialization runs
        _query(s)
    recs = obs.recent_queries()
    assert [r["seq"] for r in recs] == [1, 2]
    assert all(r["status"] == "ok" for r in recs)
    assert obs.recent_queries(1) == recs[1:]
    assert obs.state().last_query is recs[-1]
    assert obs.healthz()["queries"]["last_completed"]["seq"] == 2
    for r in recs:
        p = r["phases_ns"]
        assert set(p) == {"parse", "fetch", *_WALL_PHASES}
        # the phases of the action and the glue between them ARE its wall
        assert sum(p[k] for k in _WALL_PHASES) == r["wall_ns"]
        assert all(p[k] > 0 for k in ("admit", "plan", "execute", "fetch",
                                      "epilogue"))
        assert p["unspanned"] >= 0
        assert r["wall_ns"] >= r["wall_ms"] * 1e6 * 0.999  # + the epilogue
        assert r["timers_ns"]["copyToDeviceTime"] > 0
        assert set(r["counters"]) == {"keyed_dispatches", "upload_bytes",
                                      "shard_waves", "mesh_put_bytes",
                                      "exchange_bypassed",
                                      "scan_columns_read",
                                      "scan_columns_pruned",
                                      "expand_rows", "agg_groups",
                                      "string_match_bytes",
                                      "join_output_rows", "host_syncs",
                                      "pipeline_stalls",
                                      "distinct_input_rows",
                                      "distinct_keys"}
        assert r["counters"]["scan_columns_read"] == 0  # no Parquet scan
        assert "mesh" not in r  # no sharded stage ran
        assert r["counters"]["upload_bytes"] > 0  # the in-memory scan's
    # the parse rides on the plan: a SQL action has it, a DataFrame's not
    assert recs[0]["phases_ns"]["parse"] > 0
    assert recs[1]["phases_ns"]["parse"] == 0
    # query.fetch is inside query.execute (one partition: one thread)
    assert recs[0]["phases_ns"]["fetch"] <= recs[0]["phases_ns"]["execute"]


def test_device_wait_is_the_time_in_the_download_doors():
    """timers_ns.deviceWaitTime: a scalar a host decision needs (a forced
    LazyRowCount among them), the counts' bulk fetch, a batch's download
    (so the one inside every query.fetch), each door at its own site."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.batch import (
        ColumnarBatch, LazyRowCount, fetch_batch_host, materialize_counts)
    from spark_rapids_tpu.runtime.obs import phases
    s = TpuSession()
    s.create_or_replace_temp_view("t", s.create_dataframe(_table()))
    s.sql("select k, sum(v) as sv from t where v > 10 group by k").collect()
    rec = obs.recent_queries(1)[0]
    wait, p = rec["timers_ns"]["deviceWaitTime"], rec["phases_ns"]
    assert 0 < wait <= p["execute"]  # one partition: one thread

    def grows(door):
        """{site: (syncs, ns)} the door added"""
        before = phases.syncs_now()
        door()
        return {site: (n - before[site][0], ns - before[site][1])
                for site, (n, ns) in phases.syncs_now().items()
                if n != before[site][0]}

    def one_at(site, door):
        got = grows(door)
        return list(got) == [site] and got[site][0] == 1 \
            and got[site][1] > 0

    from spark_rapids_tpu.columnar.batch import host_int
    assert one_at("size", lambda: host_int(jnp.asarray(9), "size"))
    n = LazyRowCount(jnp.asarray(7))
    assert one_at("count", lambda: int(n)) and int(n) == 7
    assert grows(lambda: int(n)) == {}  # forced once
    lazy = ColumnarBatch([], LazyRowCount(jnp.asarray(5)))
    assert one_at("count", lambda: materialize_counts([lazy]))
    assert grows(lambda: materialize_counts([lazy])) == {}
    assert one_at("fetch", lambda: fetch_batch_host(
        ColumnarBatch([], LazyRowCount(jnp.asarray(3))), "fetch"))


def test_a_breaching_query_says_so_in_its_record():
    s = TpuSession({"spark.rapids.obs.slo.latencySeconds": "0.000001"})
    _query(s)
    assert obs.recent_queries(1)[0]["slo_breach"] is True
    assert obs.healthz()["queries"]["last_completed"]["slo_breach"] is True
    s2 = TpuSession({"spark.rapids.obs.slo.latencySeconds": "1000"})
    _query(s2)
    assert "slo_breach" not in obs.recent_queries(1)[0]


def test_ring_is_bounded_to_the_newest():
    from spark_rapids_tpu.runtime.obs import phases
    TpuSession()  # installs obs
    for i in range(phases.RING_SIZE + 5):
        obs.publish_query_record({"i": i, "query_id": i})
    recs = obs.recent_queries()
    assert len(recs) == phases.RING_SIZE
    assert recs[0]["i"] == 5 and recs[-1]["seq"] == phases.RING_SIZE + 5
    assert [r["i"] for r in obs.recent_queries(2)] == [
        phases.RING_SIZE + 3, phases.RING_SIZE + 4]


def test_keyed_dispatches_equals_the_dispatch_hook():
    from spark_rapids_tpu.exec import fuse
    s = TpuSession()
    t = _table()
    _query(s, t)  # compile
    hooked = []
    fuse.set_dispatch_hook(hooked.append)
    try:
        _query(s, t)
    finally:
        fuse.set_dispatch_hook(None)
    with_hook = obs.recent_queries(1)[0]["counters"]["keyed_dispatches"]
    assert with_hook == len(hooked) > 0
    # and the hook-less closure (the production path) counts the same
    _query(s, t)
    assert obs.recent_queries(1)[0]["counters"]["keyed_dispatches"] == \
        with_hook


def test_writing_the_record_resolves_no_lazy_count(monkeypatch):
    """The epilogue of a query nobody scrapes (no endpoint, no history,
    no tracer) stays sync-free: the record, and the attribution it now
    always feeds, read timers through peek()."""
    from spark_rapids_tpu.runtime.metrics import GpuMetric, walk_exec_tree
    resolved = []
    value = GpuMetric.value.fget

    def spying(self):
        if self._deferred:
            resolved.append(self.name)
        return value(self)

    monkeypatch.setattr(GpuMetric, "value", property(spying))
    s = TpuSession()
    _query(s)
    assert len(obs.recent_queries()) == 1
    assert s._last_attribution is not None
    assert resolved == []
    # not vacuous: the tree does hold counts nobody fetched
    assert any(m._deferred for _k, node, *_ in walk_exec_tree(s._last_exec)
               for m in node.metrics.metrics.values())


def test_attribution_for_every_query_with_other_split():
    s = TpuSession()
    s.create_or_replace_temp_view("t", s.create_dataframe(_table()))
    assert not obs.wants_rollups()  # no snapshot consumer
    s.sql("select k, sum(v) as sv from t where v > 10 group by k").collect()
    doc = s.last_attribution()
    assert doc is s._last_attribution
    assert abs(sum(doc["buckets"].values()) - doc["wall_seconds"]) < 1e-6
    # `other` split by the phases no exec timer covers, in clock order
    split, other = doc["other_phases"], doc["buckets"]["other"]
    assert set(split) == {"admit", "plan", "execute"}
    assert abs(sum(split.values()) - other) < 1e-6
    p = obs.recent_queries(1)[0]["phases_ns"]
    admit = min(p["admit"] / 1e9, other)
    assert split["admit"] == pytest.approx(admit, abs=1e-8)
    assert split["plan"] == pytest.approx(
        min(p["plan"] / 1e9, other - admit), abs=1e-8)
    # and it reaches /metrics without an endpoint or a history store
    snap = obs.state().registry.snapshot()
    assert snap['rapids_query_seconds_bucket{phase="device_compute"}'] > 0


def test_obs_off_keeps_no_ring():
    s = TpuSession({"spark.rapids.obs.enabled": "false"})
    _query(s)
    assert obs.state() is None and obs.recent_queries() == []
    assert s.last_attribution() is not None  # the account still feeds it
