"""Structured tracing subsystem tests: span/metric single instrumentation
point, Chrome trace validity, task event log, offline profiler report,
semaphore direct-handoff (event-driven waits), LORE cross-link.

Reference parity: NvtxWithMetrics + ProfilerOnExecutor + GpuTaskMetrics
(SURVEY.md §5.1/§5.5) and the spark-rapids-tools profiling report those
artifacts feed.
"""
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.runtime import trace
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import profiler_report as PR  # noqa: E402


def _table(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(0, 40, n),
                     "v": rng.integers(0, 1000, n),
                     "d": rng.uniform(0, 1, n)})


def _traced_session(tmp_path, level="DEBUG", **extra):
    conf = {"spark.rapids.sql.trace.enabled": "true",
            "spark.rapids.sql.trace.path": str(tmp_path),
            "spark.rapids.sql.trace.level": level,
            "spark.rapids.sql.reader.batchSizeRows": "1024"}
    conf.update(extra)
    return TpuSession(conf)


def _load(s):
    return PR.load_artifacts(s.last_trace_paths["trace"])


# ---------------------------------------------------------------------------
# core artifacts
# ---------------------------------------------------------------------------

def test_trace_off_by_default_writes_nothing(tmp_path):
    s = TpuSession()
    s.create_dataframe(_table()).filter(col("v") > lit(1)).collect()
    assert s.last_trace_paths is None
    assert trace.active() is None


def test_trace_artifacts_chrome_valid(tmp_path):
    s = _traced_session(tmp_path)
    out = (s.create_dataframe(_table(), num_partitions=2)
           .filter(col("v") > lit(10))
           .select(col("k"), (col("v") * lit(2)).alias("v2"))
           .filter(col("v2") < lit(1900))
           .group_by("k").agg(F.sum(col("v2"))).collect())
    assert out.num_rows > 0
    p = s.last_trace_paths
    for k in ("trace", "events", "metrics"):
        assert os.path.exists(p[k]), k
    events = PR.validate_chrome_trace(p["trace"])  # raises on malformation
    phases = {e["ph"] for e in events}
    assert "X" in phases and "M" in phases
    # one named track per task thread
    names = [e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert any(n.startswith("task ") for n in names)
    # exec spans named ExecName.metricName
    spans = {e["name"] for e in events if e["ph"] == "X"}
    assert any(n.startswith("InMemoryScanExec.") for n in spans)
    # fused-stage dispatch instants (the chain fused into one stage here)
    instants = {e["name"] for e in events if e["ph"] == "i"}
    assert "semaphoreAcquire" in instants
    assert "stageDispatch" in instants


def test_tracer_uninstalled_after_collect(tmp_path):
    s = _traced_session(tmp_path)
    s.create_dataframe(_table()).filter(col("v") > lit(5)).collect()
    assert trace.active() is None
    # a second action gets its own query id
    s.create_dataframe(_table()).filter(col("v") > lit(7)).collect()
    q2 = s.last_trace_paths["trace"]
    art = PR.load_artifacts(q2)
    assert art["query"]["n_tasks"] >= 1


def test_trace_level_filters_events(tmp_path):
    ess = _traced_session(tmp_path / "e", level="ESSENTIAL")
    dbg = _traced_session(tmp_path / "d", level="DEBUG")
    q = (lambda s: s.create_dataframe(_table(), num_partitions=2)
         .filter(col("v") > lit(10)).group_by("k")
         .agg(F.sum(col("v"))).collect())
    q(ess)
    q(dbg)
    n_ess = len(PR.validate_chrome_trace(ess.last_trace_paths["trace"]))
    n_dbg = len(PR.validate_chrome_trace(dbg.last_trace_paths["trace"]))
    assert n_ess < n_dbg
    # MODERATE instants (semaphore) are filtered at ESSENTIAL
    ev = PR.validate_chrome_trace(ess.last_trace_paths["trace"])
    assert not any(e["ph"] == "i" and e["name"] == "semaphoreAcquire"
                   for e in ev)


def test_metric_span_is_single_instrumentation_point(tmp_path):
    # tracing OFF: metric still ticks through the same call site
    from spark_rapids_tpu.runtime.metrics import GpuMetric
    m = GpuMetric("opTime")
    with trace.metric_span("x.opTime", m):
        time.sleep(0.001)
    off_val = m.value
    assert off_val > 0
    # tracing ON: one timed block feeds BOTH metric and event
    conf = C.RapidsConf({"spark.rapids.sql.trace.enabled": "true",
                         "spark.rapids.sql.trace.path": str(tmp_path)})
    tr = trace.start_query(conf)
    try:
        m2 = GpuMetric("opTime")
        with trace.metric_span("x.opTime", m2):
            time.sleep(0.001)
    finally:
        paths = trace.end_query(tr)
    ev = [e for e in PR.validate_chrome_trace(paths["trace"])
          if e["ph"] == "X" and e["name"] == "x.opTime"]
    assert len(ev) == 1
    # the event duration IS the metric value (same measured interval)
    assert abs(ev[0]["dur"] - m2.value / 1000.0) < 1e-6


# ---------------------------------------------------------------------------
# report + reconciliation (acceptance criterion)
# ---------------------------------------------------------------------------

def _nds():
    spec = importlib.util.spec_from_file_location(
        "nds_probe", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "nds_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_profiler_report_reconciles_nds_probe_query(tmp_path):
    nds = _nds()
    s = _traced_session(tmp_path)
    tables = nds.gen_tables(0.002, seed=7)
    dfs = {name: s.create_dataframe(t) for name, t in tables.items()}
    qn = sorted(nds.QUERIES)[0]
    out = nds.QUERIES[qn](s, dfs).collect()
    assert out is not None
    art = _load(s)
    analysis = PR.analyze(art)
    # per-operator span totals reconcile with last_metrics time metrics
    rows = analysis["reconciliation"]
    assert rows, "no reconcilable operator timers found"
    for r in rows:
        assert r["delta_pct"] < 1.0, r
    # stageDispatches in the metrics snapshot match traced dispatch spans
    for d in analysis["dispatch_vs_batches"]:
        if d["exec"].startswith("FusedStageExec") and d["batches"]:
            assert d["dispatches"] == d["batches"], d
    report = PR.generate_report(art)
    for section in ("Top operators by exclusive time",
                    "Spill / retry hot spots", "Semaphore contention",
                    "reconciliation"):
        assert section in report, section


def test_report_fusion_wins_and_dispatch_contract(tmp_path):
    s = _traced_session(tmp_path)
    out = (s.create_dataframe(_table(8000), num_partitions=1)
           .filter(col("v") > lit(5))
           .select(col("k"), (col("v") + lit(1)).alias("v1"), col("d"))
           .filter(col("d") < lit(0.95))
           .select(col("k"), (col("v1") * lit(3)).alias("v3"))
           .collect())
    assert out.num_rows > 0
    analysis = PR.analyze(_load(s))
    disp = [d for d in analysis["dispatch_vs_batches"]
            if d["exec"].startswith("FusedStageExec")]
    assert disp, "expected a fused stage"
    for d in disp:
        assert d["batches"] > 0
        assert d["dispatches"] == d["batches"], d
    wins = analysis["fusion_wins"]
    assert wins and all(w["saved_dispatches"] > 0 for w in wins)


def test_report_fusion_wins_absorbed_agg_stage(tmp_path):
    # A Filter→Project chain absorbed into a partial aggregate's update
    # kernel dispatches via the agg (no FusedStageExec span); the report
    # must still show the stage from its absorbed stageDispatch instants.
    # Driven through stage_harness's partial-aggregate stage — the
    # simple SQL-level shape folds entirely at plan time (CollapseProject
    # + pre_filter) and never forms a pre_chain.
    import stage_harness as SH
    drive = SH.make_partial_agg_stage(40_000, 2048, fused=True)
    tr = trace.start_query(C.RapidsConf({
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.path": str(tmp_path)}))
    try:
        drive()
    finally:
        paths = trace.end_query(tr)
    art = PR.load_artifacts(paths["trace"])
    absorbed = [w for w in PR.analyze(art)["fusion_wins"]
                if w["exec"].startswith("absorbed agg chain")]
    assert absorbed, PR.analyze(art)["fusion_wins"]
    for w in absorbed:
        assert w["members"] >= 2 and w["dispatches"] > 0
        assert w["saved_dispatches"] == \
            (w["members"] - 1) * w["dispatches"]


# ---------------------------------------------------------------------------
# semaphore: direct handoff, event-driven waits (satellite regression)
# ---------------------------------------------------------------------------

class _RecordingEvent(threading.Event):
    calls = []

    def wait(self, timeout=None):
        _RecordingEvent.calls.append(timeout)
        return super().wait(timeout)


class _ThreadingShim:
    """threading proxy whose Event records wait() timeouts."""

    def __init__(self):
        self.Event = _RecordingEvent

    def __getattr__(self, name):
        return getattr(threading, name)


def test_semaphore_waits_are_event_driven(monkeypatch):
    from spark_rapids_tpu.runtime import semaphore as sem_mod
    _RecordingEvent.calls = []
    monkeypatch.setattr(sem_mod, "threading", _ThreadingShim())
    sem = sem_mod.PrioritySemaphore(1)
    sem.acquire(1)
    got = []

    def waiter():
        sem.acquire(1)
        got.append(time.perf_counter_ns())
        sem.release(1)

    t = threading.Thread(target=waiter)
    t.start()
    while not _RecordingEvent.calls:  # waiter parked
        time.sleep(0.001)
    t0 = time.perf_counter_ns()
    sem.release(1)
    t.join(timeout=5)
    assert not t.is_alive()
    assert got and (got[0] - t0) < 45_000_000, \
        "wakeup took a poll quantum — release must signal the waiter"
    # the regression: waits must carry NO timeout (no polling loop)
    assert _RecordingEvent.calls and all(
        c is None for c in _RecordingEvent.calls), _RecordingEvent.calls


def test_semaphore_priority_handoff_order():
    from spark_rapids_tpu.runtime.semaphore import PrioritySemaphore
    sem = PrioritySemaphore(1)
    sem.acquire(1)
    order = []
    started = []

    def waiter(tag, prio):
        started.append(tag)
        sem.acquire(1, priority=prio)
        order.append(tag)

    t_low = threading.Thread(target=waiter, args=("low", 0))
    t_low.start()
    while len(started) < 1 or sem._waiters == []:
        time.sleep(0.001)
    t_high = threading.Thread(target=waiter, args=("high", 1))
    t_high.start()
    while len(sem._waiters) < 2:
        time.sleep(0.001)
    sem.release(1)  # must go to the high-priority waiter
    for _ in range(5000):
        if order:
            break
        time.sleep(0.001)
    assert order[0] == "high"
    sem.release(1)
    t_low.join(timeout=5)
    t_high.join(timeout=5)
    assert order == ["high", "low"]


def test_semaphore_wait_time_measures_real_contention():
    from spark_rapids_tpu.runtime.metrics import GpuMetric
    from spark_rapids_tpu.runtime.semaphore import PrioritySemaphore
    sem = PrioritySemaphore(1)
    sem.acquire(1)
    m = GpuMetric("semaphoreWaitTime")
    done = []

    def waiter():
        sem.acquire(1, wait_metric=m)
        done.append(1)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.02)  # hold ~20ms of real contention
    sem.release(1)
    t.join(timeout=5)
    assert done
    # measured wait tracks the actual hold, not a 50ms poll quantum
    assert 10_000_000 < m.value < 500_000_000, m.value


# ---------------------------------------------------------------------------
# LORE cross-link (satellite)
# ---------------------------------------------------------------------------

def test_lore_trace_cross_link(tmp_path):
    lore_dir = str(tmp_path / "lore")
    s = _traced_session(tmp_path / "tr", **{
        "spark.rapids.sql.lore.dumpPath": lore_dir})
    s.create_dataframe(_table(500)).filter(col("v") > lit(3)) \
        .group_by("k").agg(F.sum(col("v"))).collect()
    # plan.txt names its lore id so a hot span maps to lore.replay
    with open(os.path.join(lore_dir, "loreId=0", "plan.txt")) as f:
        head = f.readline()
    assert "loreId=0" in head
    # exec spans carry the lore_id arg
    events = PR.validate_chrome_trace(s.last_trace_paths["trace"])
    tagged = [e for e in events if e["ph"] == "X"
              and (e.get("args") or {}).get("lore_id") is not None]
    assert tagged, "no exec span carried a lore_id"


# ---------------------------------------------------------------------------
# overhead guard (structural; the timing smoke lives in tools/ci_check.sh)
# ---------------------------------------------------------------------------

def test_invalid_trace_level_fails_fast(tmp_path):
    with pytest.raises(ValueError, match="trace.level"):
        trace.start_query(C.RapidsConf({
            "spark.rapids.sql.trace.enabled": "true",
            "spark.rapids.sql.trace.path": str(tmp_path),
            "spark.rapids.sql.trace.level": "VERBOSE"}))
    assert trace.active() is None  # nothing half-installed


def test_disabled_path_returns_plain_metric_timer():
    from spark_rapids_tpu.runtime.metrics import GpuMetric, _Timer
    assert trace.active() is None
    m = GpuMetric("opTime")
    cm = trace.metric_span("x", m)
    assert isinstance(cm, _Timer), "disabled path must be the raw timer"
    assert isinstance(trace.span("y"), trace._NullSpan)
    trace.instant("z")  # must be a no-op, not an error


# ---------------------------------------------------------------------------
# the profiler sink: spans on the device trace's clock
# ---------------------------------------------------------------------------

def _sql_session(**conf):
    s = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "1024", **conf})
    s.create_or_replace_temp_view("t", s.create_dataframe(_table()))
    return s


_SQL = "select k, sum(v) as sv from t where v > 10 group by k"


def test_profiler_capture_holds_engine_spans_with_tracing_off(tmp_path):
    """spark.rapids.sql.trace.enabled stays off: a running jax.profiler
    capture alone puts the phase spans and the exec spans, `rapids.`-
    prefixed, in the host plane of the profiler's own trace."""
    import glob

    import jax
    from jax.profiler import ProfileData
    s = _sql_session()
    s.sql(_SQL).collect()  # compile outside the capture
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        s.sql(_SQL).collect()
    finally:
        jax.profiler.stop_trace()
    assert s.last_trace_paths is None and trace.active() is None
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    names = {e.name for plane in data.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events
             if e.name.startswith(trace.PROFILER_PREFIX)}
    for phase in ("sql.parse", "query.admit", "query.plan", "query.execute",
                  "query.fetch", "query.epilogue", "queryStart"):
        assert "rapids." + phase in names, (phase, sorted(names))
    execs = {n for n in names if n.endswith("Time")}
    assert execs, sorted(names)  # at least one rapids.<Exec>.<metric>


@pytest.mark.parametrize("traced", [False, True])
def test_no_capture_constructs_no_annotation(tmp_path, monkeypatch, traced):
    """With no capture running a span costs one is_enabled() check: no
    TraceAnnotation is constructed, tracer installed or not."""
    built = []

    class _Probe:
        def __init__(self, name, **kw):
            built.append(name)

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(trace, "_ANNOTATION", _Probe)
    conf = {"spark.rapids.sql.trace.enabled": "true",
            "spark.rapids.sql.trace.path": str(tmp_path)} if traced else {}
    s = _sql_session(**conf)
    s.sql(_SQL).collect()
    assert (s.last_trace_paths is not None) == traced
    assert built == []


def test_capture_annotates_every_entry_point(monkeypatch):
    """One resolver, so one place opens the annotation: span, metric_span,
    exec_span and instant all do, by the `rapids.` name, with no tracer
    and no ring installed; a DEBUG event does not, nor does emit_span (an
    interval measured earlier cannot be backdated on the profiler)."""
    from spark_rapids_tpu.runtime.metrics import GpuMetric
    opened = []

    class _Probe:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            opened.append((self.name, self.kw))
            return self

        def __exit__(self, *exc):
            return False

        @staticmethod
        def is_enabled():
            return True

    class _Node:
        lore_id = None

        def name(self):
            return "XExec"

    monkeypatch.setattr(trace, "_ANNOTATION", _Probe)
    assert trace.active() is None
    m = GpuMetric("opTime")
    with trace.span("a"):
        pass
    with trace.metric_span("b", m):
        pass
    with trace.exec_span(_Node(), m):
        pass
    trace.instant("c")
    trace.emit_span("d", 0, 7)
    with trace.span("quiet", level=trace.DEBUG):
        pass
    assert opened == [("rapids.a", {}), ("rapids.b", {}),
                      ("rapids.XExec.opTime", {}), ("rapids.c", {})]
    assert m.value > 0  # the paired metric still times
