"""Flight recorder, time attribution & SLO detection regression.

Covers the round-10 acceptance bars: ring-buffer bounds, a dump fired
by EACH trigger class (query failure, degradation, watchdog timeout,
breaker open, SLO breach) with clean runs silent, dumps that validate
as Chrome-trace JSON with tracing OFF, attribution bucket sums
reconciling with query wall time (<1%, the PR 3 reconciliation bar),
and the disabled/always-on fast paths staying cheap (the hard 2% gate
lives in tools/flight_smoke.py with the trace-overhead counting
methodology — wall-clock gates here would flake on shared CI)."""
import glob
import importlib.util
import json
import os
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.runtime import obs, trace, watchdog
from spark_rapids_tpu.runtime.metrics import GpuMetric
from spark_rapids_tpu.runtime.obs import attribution, flight
from spark_rapids_tpu.runtime.obs.slo import SloDetector
from spark_rapids_tpu.sql.session import TpuSession

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_spec = importlib.util.spec_from_file_location(
    "profiler_report", os.path.join(REPO, "tools", "profiler_report.py"))
PR = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(PR)

from spark_rapids_tpu.expr.core import col, lit  # noqa: E402
from spark_rapids_tpu.sql import functions as F  # noqa: E402


def _table(n=20_000):
    rng = np.random.default_rng(7)
    return pa.table({"k": rng.integers(0, 20, n),
                     "v": rng.integers(0, 100, n)})


def _sess(tmp_path, **over):
    conf = {"spark.rapids.obs.flight.path": str(tmp_path / "flight"),
            "spark.rapids.obs.flight.minIntervalSeconds": "0",
            "spark.rapids.sql.reader.batchSizeRows": "4096"}
    conf.update(over)
    return TpuSession(conf)


def _query(sess, parts=2):
    return (sess.create_dataframe(_table(), num_partitions=parts)
            .filter(col("v") > lit(10))
            .group_by("k").agg(F.sum(col("v")).alias("sv")))


def _dumps(tmp_path):
    return sorted(glob.glob(str(tmp_path / "flight" / "flight_*.json")))


# ---------------------------------------------------------------------------
# ring buffer mechanics
# ---------------------------------------------------------------------------

def test_ring_is_bounded_and_keeps_newest(tmp_path):
    rec = flight.FlightRecorder(capacity=16, out_dir=str(tmp_path),
                                min_interval_s=0.0)
    for i in range(100):
        rec.record(f"e{i}", "t", i, 1)
    path = rec.dump("test")
    doc = json.load(open(path))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 16
    # the NEWEST 16 events survive, the oldest 84 were overwritten
    assert {e["name"] for e in spans} == {f"e{i}" for i in range(84, 100)}
    assert doc["otherData"]["dropped_events"] == 84


def test_flight_span_feeds_metric_and_ring(tmp_path):
    # the recorder has no span class of its own: trace.py's one span is
    # what feeds the paired metric and the ring
    from spark_rapids_tpu.runtime import trace
    rec = flight.install(capacity=64, out_dir=str(tmp_path))
    m = GpuMetric("opTime")
    with trace.metric_span("Exec.opTime", m, "exec"):
        time.sleep(0.002)
    assert m.value >= 2_000_000  # the paired GpuMetric still times
    events = PR.validate_chrome_trace(rec.dump("test"))
    spans = [e for e in events if e["name"] == "Exec.opTime"]
    assert len(spans) == 1 and spans[0]["dur"] >= 2000  # us


def test_instants_and_rate_limit(tmp_path):
    rec = flight.FlightRecorder(capacity=64, out_dir=str(tmp_path),
                                min_interval_s=60.0)
    rec.instant("somethingHappened", "t", {"x": 1})
    p1 = rec.dump("first")
    assert p1 is not None
    assert rec.dump("second") is None  # rate-limited
    events = PR.validate_chrome_trace(p1)
    inst = [e for e in events if e["name"] == "somethingHappened"]
    assert len(inst) == 1 and inst[0]["ph"] == "i" \
        and inst[0]["args"] == {"x": 1}


def test_dump_retention_bounded(tmp_path):
    rec = flight.FlightRecorder(capacity=16, out_dir=str(tmp_path),
                                min_interval_s=0.0, max_dumps=3)
    rec.record("e", "t", 0, 1)
    for _ in range(5):
        rec.dump("test")
    files = sorted(glob.glob(str(tmp_path / "flight_*.json")))
    assert len(files) == 3
    assert files[-1].endswith("flight_0005_test.json")


def test_dump_retention_survives_seq_past_9999(tmp_path):
    # lexicographic pruning would sort flight_10001 before flight_9999
    # and delete the NEWEST dumps; pruning must parse the seq
    rec = flight.FlightRecorder(capacity=16, out_dir=str(tmp_path),
                                min_interval_s=0.0, max_dumps=3)
    rec.record("e", "t", 0, 1)
    rec._seq = 9998
    for _ in range(4):
        rec.dump("test")
    kept = sorted(os.path.basename(p)
                  for p in glob.glob(str(tmp_path / "flight_*.json")))
    assert set(kept) == {"flight_10000_test.json",
                         "flight_10001_test.json",
                         "flight_10002_test.json"}, kept


def test_failed_write_does_not_eat_the_rate_interval(tmp_path):
    # out_dir collides with a regular FILE: makedirs raises, nothing is
    # written (chmod tricks don't work under root, a path collision does)
    blocked = tmp_path / "blocked"
    blocked.write_text("in the way")
    rec = flight.install(capacity=16, out_dir=str(blocked),
                         min_interval_s=3600.0)
    rec.record("e", "t", 0, 1)
    assert flight.dump("first") is None  # write failed, swallowed
    # the failed attempt must not have armed the rate limiter: the next
    # trigger (disk freed / path fixed) still dumps within the interval
    rec.out_dir = str(tmp_path / "ok")
    assert flight.dump("second") is not None


def test_trace_fastpaths_feed_flight_when_tracing_off(tmp_path):
    assert trace.active() is None
    rec = flight.install(capacity=64, out_dir=str(tmp_path))
    m = GpuMetric("opTime")

    class _Node:
        lore_id = None

        def name(self):
            return "FakeExec"

    with trace.exec_span(_Node(), m):
        pass
    with trace.metric_span("manual.span", m):
        pass
    with trace.span("plain.span"):
        pass
    trace.instant("anInstant")
    # DEBUG-level events must NOT reach the bounded ring
    with trace.span("debug.span", level=trace.DEBUG):
        pass
    trace.instant("debugInstant", level=trace.DEBUG)
    names = {e["name"]
             for e in PR.validate_chrome_trace(rec.dump("test"))}
    assert {"FakeExec.opTime", "manual.span", "plain.span",
            "anInstant"} <= names
    assert "debug.span" not in names and "debugInstant" not in names


def test_traced_debug_spans_filtered_from_ring(tmp_path):
    # with a DEBUG-level tracer active, _Span also feeds the ring — but
    # DEBUG spans must still be filtered or serde chatter flushes it
    from spark_rapids_tpu import config as C
    rec = flight.install(capacity=64, out_dir=str(tmp_path))
    qt = trace.start_query(C.RapidsConf({
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.path": str(tmp_path / "tr"),
        "spark.rapids.sql.trace.level": "DEBUG"}))
    try:
        with trace.span("moderate.span"):
            pass
        with trace.span("debug.span", level=trace.DEBUG):
            pass
    finally:
        trace.end_query(qt)
    names = {e["name"]
             for e in PR.validate_chrome_trace(rec.dump("test"))}
    assert "moderate.span" in names
    assert "debug.span" not in names


def test_disabled_path_returns_pretrace_objects():
    flight.uninstall_for_tests()
    m = GpuMetric("opTime")
    span = trace.metric_span("x", m)
    # recorder off + tracer off = the bare metric timer, exactly as
    # before the flight recorder existed
    assert type(span).__name__ == "_Timer"
    assert trace.span("x") is trace._NULL
    assert flight.dump("nothing") is None
    assert flight.doc() is None


# ---------------------------------------------------------------------------
# trigger classes (tracing OFF throughout)
# ---------------------------------------------------------------------------

def test_failed_query_dumps_readable_trace(tmp_path):
    sess = _sess(tmp_path,
                 **{"spark.rapids.debug.faults": "scan.decode:ioerror"})
    with pytest.raises(Exception):
        _query(sess).collect()
    dumps = _dumps(tmp_path)
    assert len(dumps) == 1 and "query_failed" in dumps[0]
    events = PR.validate_chrome_trace(dumps[0])
    names = {e["name"] for e in events}
    # the dump covers the failing query: exec spans + the fault + the
    # outcome marker + the trigger
    assert sum(1 for e in events if e["ph"] == "X") > 0
    assert "faultInjected" in names
    assert "queryError" in names
    assert "flightTrigger" in names
    other = json.load(open(dumps[0]))["otherData"]
    assert other["reason"] == "query_failed"
    assert other["error"] == "InjectedFaultError"


def test_clean_queries_stay_silent(tmp_path):
    sess = _sess(tmp_path)
    for _ in range(3):
        _query(sess).collect()
    assert _dumps(tmp_path) == []


def test_degraded_query_dumps(tmp_path):
    clean = _query(_sess(tmp_path)).collect()
    assert _dumps(tmp_path) == []
    sess = _sess(tmp_path, **{
        "spark.rapids.debug.faults": "scan.decode:ioerror",
        "spark.rapids.fallback.cpu.enabled": "true"})
    out = _query(sess).collect()
    assert sess.last_action_status[0] == "degraded"
    assert out.sort_by("k").equals(clean.sort_by("k"))
    dumps = _dumps(tmp_path)
    assert len(dumps) == 1 and "query_degraded" in dumps[0]
    other = json.load(open(dumps[0]))["otherData"]
    assert other["reason"] == "query_degraded"


def test_watchdog_timeout_dumps(tmp_path):
    flight.install(capacity=64, out_dir=str(tmp_path / "flight"))
    wd = watchdog.DispatchWatchdog(timeout_s=0.03)
    wd.start()
    try:
        with wd.guard("device.dispatch"):
            time.sleep(0.3)  # the "wedge": guard held past the deadline
        deadline = time.time() + 5
        while wd.timeouts_reported == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert wd.timeouts_reported >= 1
    finally:
        wd.stop()
        watchdog.uninstall_for_tests()
    dumps = _dumps(tmp_path)
    assert dumps and "watchdog_timeout" in dumps[0]
    events = PR.validate_chrome_trace(dumps[0])
    assert any(e["name"] == "watchdogDispatchTimeout" for e in events)


def test_breaker_open_dumps(tmp_path):
    flight.install(capacity=64, out_dir=str(tmp_path / "flight"))
    brk = watchdog.CircuitBreaker(failure_threshold=1)
    brk.record_failure("SomeDeviceError")
    assert brk.state == "open"
    dumps = _dumps(tmp_path)
    assert len(dumps) == 1 and "breaker_open" in dumps[0]
    other = json.load(open(dumps[0]))["otherData"]
    assert other["error"] == "SomeDeviceError"


# ---------------------------------------------------------------------------
# SLO detection
# ---------------------------------------------------------------------------

def test_slo_baseline_detector_unit():
    det = SloDetector(factor=2.0, min_runs=3, abs_seconds=0.0)
    assert det.record("d1", 1.0) is None
    assert det.record("d1", 1.1) is None
    # under min_runs: even a huge outlier folds silently
    assert det.record("d1", 0.9) is None
    assert det.record("d1", 1.9) is None  # under 2x baseline
    b = det.record("d1", 5.0)
    assert b is not None and b["kind"] == "baseline"
    assert 0.9 < b["baseline_seconds"] < 1.5 and b["runs"] >= 3
    # the breaching run did NOT fold in: a repeat still breaches
    b2 = det.record("d1", 5.0)
    assert b2 is not None and abs(
        b2["baseline_seconds"] - b["baseline_seconds"]) < 1e-9
    assert det.breaches == 2


def test_slo_absolute_bound_and_window():
    det = SloDetector(factor=100.0, min_runs=2, abs_seconds=0.5, window=4)
    assert det.record("d", 0.4) is None
    b = det.record("d", 0.6)
    assert b is not None and b["kind"] == "absolute" \
        and b["threshold_seconds"] == 0.5
    for i in range(10):
        det.observe("d", float(i))
    assert det.baseline("d")["runs"] == 4  # window bounds the history


def test_slo_disabled_never_breaches():
    det = SloDetector(enabled=False, abs_seconds=0.001)
    assert det.record("d", 10.0) is None
    assert det.breaches == 0


def test_slo_seed_skips_breaching_runs():
    # a breaching run is status=ok in history but carries slo_breach:
    # folding it at seed time would normalize the regression away
    # across restarts — the live-check invariant applies to seeding too
    class _Store:
        def read_all(self):
            return ([{"type": "query", "status": "ok", "plan_digest": "d",
                      "duration_ns": 1_000_000_000}] * 3
                    + [{"type": "query", "status": "ok",
                        "plan_digest": "d", "duration_ns": 60_000_000_000,
                        "slo_breach": {"kind": "baseline"}}])

    det = SloDetector(factor=3.0, min_runs=3)
    assert det.seed_from_history(_Store()) == 3
    base = det.baseline("d")
    assert base["runs"] == 3 and base["mean_seconds"] < 1.5
    assert det.record("d", 5.0) is not None  # still reads as a breach


def test_slo_breach_end_to_end(tmp_path):
    obs.shutdown_for_tests()
    try:
        hist = tmp_path / "hist"
        sess = _sess(tmp_path, **{
            "spark.rapids.obs.historyDir": str(hist),
            "spark.rapids.obs.slo.latencySeconds": "0.000001"})
        _query(sess).collect()
        st = obs.state()
        assert st.slo.breaches == 1
        # counter, healthz surface, flight dump, history record
        assert st.registry.counter("rapids_slo_breaches_total").value == 1
        hz = obs.healthz()
        last_slow = hz["slo"]["last_slow"]
        assert last_slow["plan_digest"]
        assert last_slow["breach"]["kind"] == "absolute"
        assert last_slow["attribution"]["top_buckets"]
        assert last_slow["flight_dump"] and os.path.exists(
            last_slow["flight_dump"])
        assert hz["flight"]["last_dump"]["reason"] == "slo_breach"
        events = PR.validate_chrome_trace(last_slow["flight_dump"])
        assert any(e["name"] == "slowQuery" for e in events)
        recs = [r for r in st.history.read_all()
                if r.get("type") == "query"]
        assert recs[-1]["slo_breach"]["kind"] == "absolute"
        assert recs[-1]["flight_dump"] == last_slow["flight_dump"]
        assert recs[-1]["attribution"]["buckets"]
        # /metrics exports the per-phase seconds counters
        rendered = st.registry.render_prometheus()
        assert 'rapids_query_seconds_bucket{phase="device_compute"}' \
            in rendered
    finally:
        obs.shutdown_for_tests()


def test_slo_baselines_seed_from_history(tmp_path):
    obs.shutdown_for_tests()
    try:
        hist = tmp_path / "hist"
        sess = _sess(tmp_path,
                     **{"spark.rapids.obs.historyDir": str(hist)})
        for _ in range(3):
            _query(sess).collect()
        obs.shutdown_for_tests()
        # a fresh "process": the detector seeds from the store
        sess2 = _sess(tmp_path, **{
            "spark.rapids.obs.historyDir": str(hist),
            "spark.rapids.obs.slo.minRuns": "3"})
        st = obs.state()
        digest = obs.plan_digest(_query(sess2).plan)
        base = st.slo.baseline(digest)
        assert base is not None and base["runs"] >= 3
    finally:
        obs.shutdown_for_tests()


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def test_attribution_reconciles_with_wall_time(tmp_path):
    sess = _sess(tmp_path)
    t0 = time.perf_counter()
    _query(sess).collect()
    wall_outer = time.perf_counter() - t0
    attr = sess.last_attribution()
    assert attr is not None
    assert set(attr["buckets"]) == set(attribution.BUCKETS)
    total = sum(attr["buckets"].values())
    # the acceptance bar: buckets sum to wall within 1%
    assert abs(total - attr["wall_seconds"]) <= 0.01 * attr["wall_seconds"]
    # the measured wall is the engine's own timing of the same action
    assert attr["wall_seconds"] <= wall_outer * 1.05
    assert all(v >= 0 for v in attr["buckets"].values())
    assert attr["buckets"]["device_compute"] + attr["buckets"]["compile"] > 0


def test_attribution_compile_bucket_on_fresh_cache(tmp_path):
    from spark_rapids_tpu.exec import fuse
    fuse.clear_cache()
    sess = _sess(tmp_path)
    _query(sess).collect()
    attr = sess.last_attribution()
    # a cold fuse cache means the first dispatches paid XLA compile
    assert attr["buckets"]["compile"] > 0


def test_attribution_in_explain_analyze(tmp_path, capsys):
    sess = _sess(tmp_path)
    df = _query(sess)
    text = df.explain(mode="analyze")
    capsys.readouterr()
    assert "-- time attribution (wall " in text
    # at least one named bucket line renders with seconds and percent
    assert any(b in text for b in ("device_compute", "compile"))
    assert "%" in text


def test_attribution_concurrency_scaling():
    # measured > wall: buckets scale to critical-path shares
    snaps = {"FakeExec#0": {"opTime": 4_000_000_000}}
    doc = attribution.attribute(snaps, 1_000_000_000)
    assert doc["concurrency_factor"] == pytest.approx(4.0)
    assert doc["buckets"]["device_compute"] == pytest.approx(1.0)
    assert sum(doc["buckets"].values()) == pytest.approx(
        doc["wall_seconds"])
    # measured < wall: the remainder is 'other'
    doc2 = attribution.attribute(snaps, 8_000_000_000)
    assert doc2["concurrency_factor"] == 1.0
    assert doc2["buckets"]["other"] == pytest.approx(4.0)


def test_attribution_classification_and_compile_correction():
    snaps = {
        "InMemoryScanExec#0": {"tpuDecodeTime": 10, "copyToDeviceTime": 10,
                               "numOutputRows": 99},
        "ShuffleExchangeExec#1": {"partitionTime": 30, "opTime": 10},
        "PipelineExec#2": {"pipelineStallTime": 25,
                           "pipelineProducerTime": 1000},  # excluded
        "FilterExec#3": {"filterTime": 40},
    }
    extra = {"compile": 15, "semaphore_wait": 5}
    doc = attribution.attribute(snaps, 1_000_000_000, extra=extra)
    ns = {b: round(s * 1e9) for b, s in doc["buckets"].items()}
    assert ns["host_decode"] == 20
    assert ns["shuffle"] == 40  # partitionTime + exchange opTime
    assert ns["pipeline_stall"] == 25
    assert ns["semaphore_wait"] == 5
    # compile correction: 15ns move OUT of device_compute (40 - 15)
    assert ns["compile"] == 15 and ns["device_compute"] == 25
    assert sum(ns.values()) == 1_000_000_000


def test_attribution_compile_correction_cascades_past_device():
    # a fresh EXCHANGE kernel's first call times into 'shuffle': the
    # compile subtraction must cascade there once device_compute is
    # exhausted, not leave the interval double-counted (which would
    # inflate measured_seconds and fake a concurrency factor)
    snaps = {"ShuffleExchangeExec#0": {"partitionTime": 100},
             "FilterExec#1": {"filterTime": 30}}
    doc = attribution.attribute(snaps, 1_000_000_000,
                                extra={"compile": 90})
    ns = {b: round(s * 1e9) for b, s in doc["buckets"].items()}
    assert ns["compile"] == 90
    assert ns["device_compute"] == 0   # 30 absorbed first
    assert ns["shuffle"] == 40         # then 60 of the 100
    assert doc["concurrency_factor"] == 1.0
    assert sum(ns.values()) == 1_000_000_000


def test_attribution_history_and_render(tmp_path):
    obs.shutdown_for_tests()
    try:
        hist = tmp_path / "hist"
        sess = _sess(tmp_path,
                     **{"spark.rapids.obs.historyDir": str(hist)})
        _query(sess).collect()
        st = obs.state()
        rec = [r for r in st.history.read_all()
               if r.get("type") == "query"][-1]
        attr = rec["attribution"]
        assert set(attr["buckets"]) == set(attribution.BUCKETS)
        # the text renderer emits one line per nonzero bucket
        lines = attribution.render_text(attr)
        assert lines and lines[0].startswith("-- time attribution")
        assert len(lines) - 1 == sum(
            1 for v in attr["buckets"].values() if v > 0)
    finally:
        obs.shutdown_for_tests()


def test_attribution_aggregate_cleared_between_queries(tmp_path):
    sess = _sess(tmp_path)
    _query(sess).collect()
    first = sess.last_attribution()
    # outside a query the aggregate must be closed (record is a no-op)
    attribution.record("compile", 10**12)
    _query(sess).collect()
    second = sess.last_attribution()
    assert second["buckets"]["compile"] <= first["buckets"]["compile"] + 1


# ---------------------------------------------------------------------------
# overhead guardrails (behavioral; the hard gate is flight_smoke.py)
# ---------------------------------------------------------------------------

def test_always_on_span_cost_is_bounded(tmp_path):
    rec = flight.install(capacity=2048, out_dir=str(tmp_path))
    m = GpuMetric("opTime")
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.metric_span("x", m):
            pass
    per_call_us = (time.perf_counter() - t0) / n * 1e6
    # generous CI-safe bound: the smoke gates the real 2% budget
    assert per_call_us < 50, f"flight span costs {per_call_us:.1f}us"
    assert rec.doc()["enabled"]


def test_dump_never_raises(tmp_path, monkeypatch):
    rec = flight.install(capacity=16, out_dir="/nonexistent\0bad")
    rec.record("e", "t", 0, 1)
    assert flight.dump("broken") is None  # swallowed + logged, not raised
