"""Structured tracing: spans, instant events, per-task event log.

Reference parity: NvtxWithMetrics.scala (NVTX ranges tied to GpuMetrics —
entering a range optionally starts the paired metric timer, so the trace
and the SQL-UI metrics are ONE instrumentation point), profiler.scala /
Plugin.scala:442 (ProfilerOnExecutor: a built-in executor profiler writing
per-query artifacts under a configured directory), and GpuTaskMetrics
(per-task accumulators — retry/spill/semaphore times — consumed by the
offline spark-rapids-tools profiling report; tools/profiler_report.py is
that report's analog here).

One instrumentation point, three sinks (`_sinks` resolves them once per
event; every entry point below goes through it):

- the per-query Tracer (spark.rapids.sql.trace.*: enabled, path, level,
  taskMetrics — see config.py): Chrome trace-event JSON (Perfetto /
  chrome://tracing loadable). One track per task thread (tid = task id
  while a TaskContext is bound, thread ident otherwise, named by a
  thread_name metadata event), complete events ("ph":"X") for spans,
  instant events ("ph":"i") for semaphore acquire/release, spill
  (device→host→disk, bytes), retry and split-retry, host-pool queueing,
  and fused-stage dispatches;
- the always-on bounded rings (runtime/obs/flight.py, reqtrace.py);
- the profiler: while a jax.profiler capture is running (the
  benchmark's --trace 1, spark.rapids.profile.dir, or anyone's
  start_trace) every span and instant that passes its level filter
  opens a jax.profiler.TraceAnnotation named `rapids.<span name>`,
  whatever spark.rapids.sql.trace.enabled says, so the engine's spans
  sit on the device trace's clock (an interval handed over after the
  fact, emit_span, cannot be backdated there and stays off this sink).

Overhead discipline: with no tracer, no ring and no capture a span costs
its level check, three module-global reads and one
TraceAnnotation.is_enabled() (about 20 ns) — `metric_span` then returns
the GpuMetric's own timer (exactly the pre-trace hot path) and `instant`
returns immediately. Levels reuse the metric levels (ESSENTIAL <
MODERATE < DEBUG): a DEBUG event with no DEBUG tracer installed costs
two reads and no is_enabled().
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional

from spark_rapids_tpu.runtime.metrics import DEBUG, ESSENTIAL, MODERATE

#: Names of the per-task accumulators rolled up into the event log
#: (the GpuTaskMetrics analog). semaphoreWaitTime is fed by the
#: semaphore itself; the rest by runtime/retry.py and runtime/memory.py.
TASK_METRIC_NAMES = (
    "semaphoreWaitTime", "semaphoreHoldTime",
    "retryCount", "splitAndRetryCount", "retryBlockTime",
    "retryWastedTime",
    "spillToHostBytes", "spillToDiskBytes",
    "spillToHostTime", "spillToDiskTime",
    "maxDeviceBytesHeld",
    "shuffleCorruptionRetries",
)

from spark_rapids_tpu.analysis import sanitizer as _san  # noqa: E402
# the always-on flight recorder shares these instrumentation points: a
# span/instant that the tracer is not consuming (tracing off, or above
# the configured level) still lands in the bounded per-thread ring so a
# failure can dump a retroactive timeline. _flight._REC is None when the
# recorder is off — one module-global read past the tracer check.
from spark_rapids_tpu.runtime.obs import flight as _flight  # noqa: E402
# per-request tail sampling (runtime/obs/reqtrace.py): when the flight
# recorder is ON its record() feeds the bound request's ring, so the
# branches below only cover the flight-OFF + reqtrace-ON combination —
# the disabled path stays one module-global read per hook.
from spark_rapids_tpu.runtime.obs import reqtrace as _reqtrace  # noqa: E402
# cross-thread query correlation (runtime/obs/live.py): traced events
# carry the emitting thread's bound query id so two queries' events in
# one trace (nested collects, pool threads) stay attributable
from spark_rapids_tpu.runtime.obs import live as _live  # noqa: E402

#: the profiler sink: while a jax.profiler capture is running, every
#: span/instant that passes its level filter opens one of these, named
#: PROFILER_PREFIX + the span's name, so the engine's events sit on the
#: device trace's clock and a trace reducer picks them with one test
from jax.profiler import TraceAnnotation as _ANNOTATION  # noqa: E402

PROFILER_PREFIX = "rapids."

_TRACER: "Optional[Tracer]" = None
_STATE_LOCK = _san.lock("trace.state")
_QUERY_SEQ = 0


class _NullSpan:
    """Context manager for the disabled path when no metric is paired."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class Tracer:
    """One query's trace: an in-memory event buffer (tasks append under a
    lock; writing files mid-query would serialize the hot path) finalized
    to <dir>/query_<id>_{trace.json,events.jsonl,metrics.json}."""

    def __init__(self, out_dir: str, level: int = MODERATE,
                 task_metrics: bool = True, query_id: int = 0):
        self.out_dir = out_dir
        self.level = level
        self.task_metrics = task_metrics
        self.query_id = query_id
        self.pid = os.getpid()
        self._t0 = time.perf_counter_ns()
        self._wall0 = time.time()
        self._lock = _san.lock("trace.buffer")
        self._events: List[dict] = []
        self._task_records: List[dict] = []
        self._named_tids: set = set()

    # -- clocks ------------------------------------------------------------

    def _ts_us(self, t_ns: int) -> float:
        return (t_ns - self._t0) / 1000.0

    # -- track identity ----------------------------------------------------

    def _track(self) -> int:
        """One track per task thread: the bound task's id when a
        TaskContext is live on this thread, the raw thread ident
        otherwise (host-pool workers, the driver)."""
        from spark_rapids_tpu.runtime.task import TaskContext
        ctx = TaskContext.peek()
        if ctx is not None:
            tid = ctx.task_id
            name = f"task {ctx.task_id} (partition {ctx.partition_id})"
        else:
            tid = threading.get_ident() & 0x7FFFFFFF
            name = threading.current_thread().name
        if tid not in self._named_tids:
            self._named_tids.add(tid)
            with self._lock:
                self._events.append({
                    "ph": "M", "name": "thread_name", "pid": self.pid,
                    "tid": tid, "args": {"name": name}})
        return tid

    # -- event emission ----------------------------------------------------

    @staticmethod
    def _with_qid(args: Optional[dict]) -> Optional[dict]:
        """args + the emitting thread's bound query id (one thread-local
        read; None binding leaves args untouched)."""
        qid = _live.current_query_id()
        if qid is None:
            return args
        out = dict(args) if args else {}
        out.setdefault("query_id", qid)
        return out

    def complete(self, name: str, t0_ns: int, dur_ns: int, cat: str,
                 args: Optional[dict] = None) -> None:
        args = self._with_qid(args)
        ev = {"ph": "X", "name": name, "cat": cat, "pid": self.pid,
              "tid": self._track(), "ts": self._ts_us(t0_ns),
              "dur": dur_ns / 1000.0}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, cat: str,
                args: Optional[dict] = None) -> None:
        args = self._with_qid(args)
        ev = {"ph": "i", "name": name, "cat": cat, "pid": self.pid,
              "tid": self._track(), "ts": self._ts_us(time.perf_counter_ns()),
              "s": "t"}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def task_rollup(self, record: dict) -> None:
        with self._lock:
            self._task_records.append(record)

    # -- lifecycle ---------------------------------------------------------

    def paths(self) -> Dict[str, str]:
        base = os.path.join(self.out_dir, f"query_{self.query_id}")
        return {"trace": base + "_trace.json",
                "events": base + "_events.jsonl",
                "metrics": base + "_metrics.json"}

    def finalize(self, last_metrics: Optional[dict] = None,
                 status: str = "ok",
                 error: Optional[BaseException] = None,
                 plan_digest: Optional[str] = None) -> Dict[str, str]:
        """Write the three artifacts; returns their paths. A failed query
        finalizes with status="failed" + the exception class so the
        buffered events flush instead of dying with the query (and the
        offline report can say WHY the trace ends early); plan_digest
        cross-links these artifacts to the query-history record that
        shares it."""
        os.makedirs(self.out_dir, exist_ok=True)
        p = self.paths()
        with self._lock:
            events = list(self._events)
            tasks = list(self._task_records)
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "query_id": self.query_id,
                "trace_level": self.level,
                "wall_start_unix": self._wall0,
                "status": status,
                "plan_digest": plan_digest,
                "producer": "spark_rapids_tpu.runtime.trace",
            },
        }
        with open(p["trace"], "w") as f:
            json.dump(doc, f)
        with open(p["events"], "w") as f:
            qrec = {
                "type": "query", "query_id": self.query_id,
                "wall_start_unix": self._wall0,
                "duration_ns": time.perf_counter_ns() - self._t0,
                "n_tasks": len(tasks),
                "status": status,
                "plan_digest": plan_digest}
            if error is not None:
                qrec["error_class"] = type(error).__name__
            f.write(json.dumps(qrec) + "\n")
            for rec in tasks:
                f.write(json.dumps(rec) + "\n")
        if last_metrics is not None:
            with open(p["metrics"], "w") as f:
                json.dump(last_metrics, f, indent=1)
        return p


class _Span:
    """A live span: times the block ONCE, feeds the paired GpuMetric (the
    NvtxWithMetrics contract) and hands the interval to every sink that
    `_sinks` resolved for it: the tracer's buffer, the bounded ring, and
    a `rapids.`-prefixed TraceAnnotation on the profiler's clock."""

    __slots__ = ("sinks", "name", "metric", "cat", "args", "t0", "_ann")

    def __init__(self, sinks: tuple, name: str, metric, cat: str,
                 args: Optional[dict]):
        self.sinks = sinks
        self.name = name
        self.metric = metric
        self.cat = cat
        self.args = args

    def __enter__(self):
        ann = self.sinks[2]
        if ann is not None:
            self._ann = ann(PROFILER_PREFIX + self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        tr, ring, ann = self.sinks
        if ann is not None:
            self._ann.__exit__(*exc)
        if self.metric is not None:
            self.metric.add(dur)
        if tr is not None:
            tr.complete(self.name, self.t0, dur, self.cat, self.args)
        if ring is not None:
            ring.record(self.name, self.cat, self.t0, dur, self.args)
        return False


# ---------------------------------------------------------------------------
# Module-level fast-path API (what the instrumentation points call)
# ---------------------------------------------------------------------------

def active() -> Optional[Tracer]:
    return _TRACER


def _sinks(level: int) -> Optional[tuple]:
    """THE sink cascade, resolved once per event: (tracer, ring,
    annotation class) with None for each sink that does not want an
    event of `level`, or None when nobody does (the caller then takes
    its pre-trace path).

    - tracer: installed (spark.rapids.sql.trace.enabled) and `level`
      within its configured level;
    - ring: the flight recorder, else the per-request recorder while a
      request is bound (with the flight recorder on, its record() feeds
      the request ring itself). DEBUG events never enter a bounded ring:
      serde chatter would flush the interesting events;
    - annotation: a jax.profiler capture is running. The filter is the
      tracer's when one is installed, the ring's (below DEBUG) otherwise,
      whatever spark.rapids.sql.trace.enabled says."""
    tr = _TRACER
    if tr is not None and level > tr.level:
        tr = None
    ring = None
    if level < DEBUG:
        ring = _flight._REC
        if ring is None:
            rr = _reqtrace._REC
            if rr is not None and _live.current_request() is not None:
                ring = rr
    elif tr is None:
        return None
    ann = _ANNOTATION if _ANNOTATION.is_enabled() else None
    if tr is None and ring is None and ann is None:
        return None
    return tr, ring, ann


def metric_span(name: str, metric, cat: str = "exec",
                args: Optional[dict] = None, level: Optional[int] = None):
    """THE instrumentation point: one timed block feeding both the
    GpuMetric and the trace. With no sink for its level it returns the
    metric's own nanosecond timer, the exact pre-trace hot path."""
    sinks = _sinks(level if level is not None
                   else getattr(metric, "level", MODERATE))
    if sinks is None:
        return metric.ns() if metric is not None else _NULL
    return _Span(sinks, name, metric, cat, args)


def exec_span(node, metric, name: Optional[str] = None):
    """Span for one exec's per-batch device work, named
    `ExecName.metricName`. Carries the node's lore id when LORE dumping
    is active so a hot span can be replayed with lore.replay (the
    LORE↔trace cross-link)."""
    sinks = _sinks(metric.level)
    if sinks is None:
        return metric.ns()
    args = None
    if sinks[0] is not None:
        lid = getattr(node, "lore_id", None)
        if lid is not None:
            args = {"lore_id": lid}
    return _Span(sinks, name or f"{node.name()}.{metric.name}", metric,
                 "exec", args)


def span(name: str, cat: str = "runtime", args: Optional[dict] = None,
         level: int = MODERATE):
    """Metric-less span (planner passes, report-only ranges)."""
    sinks = _sinks(level)
    return _NULL if sinks is None else _Span(sinks, name, None, cat, args)


def instant(name: str, cat: str = "runtime", args: Optional[dict] = None,
            level: int = MODERATE) -> None:
    sinks = _sinks(level)
    if sinks is None:
        return
    tr, ring, ann = sinks
    if tr is not None:
        tr.instant(name, cat, args)
    if ring is not None:
        ring.record(name, cat, time.perf_counter_ns(), -1, args)
    if ann is not None:
        with ann(PROFILER_PREFIX + name):
            pass  # a marker on the profiler's timeline


def emit_span(name: str, t0_ns: int, dur_ns: int, cat: str = "exec",
              args: Optional[dict] = None, level: int = MODERATE) -> None:
    """Record an already-measured interval as a complete event (for call
    sites that must own the timing, e.g. the fused-stage dispatch whose
    duration also splits across member metrics). Tracer and ring only:
    the profiler cannot backdate an interval, so it gets none."""
    sinks = _sinks(level)
    if sinks is None:
        return
    tr, ring, _ann = sinks
    if tr is not None:
        tr.complete(name, t0_ns, dur_ns, cat, args)
    if ring is not None:
        ring.record(name, cat, t0_ns, dur_ns, args)


def on_task_complete(ctx) -> None:
    """TaskContext completion hook: roll the task's accumulators into the
    per-query event log (the GpuTaskMetrics → profiling-tool handoff)."""
    tr = _TRACER
    if tr is None or not tr.task_metrics:
        return
    metrics = {}
    # roster keys first (stable event-log schema order), ad-hoc
    # accumulators after
    ordered = list(TASK_METRIC_NAMES) + [
        k for k in ctx._metrics if k not in TASK_METRIC_NAMES]
    for name in ordered:
        m = ctx._metrics.get(name)
        if m is None:
            continue
        try:
            v = int(m.value)
        except Exception:  # noqa: BLE001 - a lazy count that cannot resolve
            continue
        if v:
            metrics[name] = v
    tr.task_rollup({
        "type": "task",
        "query_id": tr.query_id,
        # the LIVE registry's id (runtime/obs/live.py; the tracer's own
        # query_id is its per-tracer sequence) — lets the event log of a
        # trace shared by nested/concurrent work split per real query
        "live_query_id": ctx.query_id,
        "task_id": ctx.task_id,
        "partition_id": ctx.partition_id,
        "stage_id": ctx.stage_id,
        "failed": ctx._failed,
        "duration_ns": time.perf_counter_ns() - ctx.start_ns,
        "metrics": metrics,
    })


# ---------------------------------------------------------------------------
# Query lifecycle (driven by TpuSession.collect)
# ---------------------------------------------------------------------------

def start_query(conf) -> Optional[Tracer]:
    """Install a process-wide tracer for one query when
    spark.rapids.sql.trace.enabled is set. Returns None when tracing is
    off OR a query trace is already active (a nested collect — broadcast
    materialization, subqueries — joins the enclosing query's trace).

    The tracer is a process-wide singleton (the reference runs ONE
    ProfilerOnExecutor per executor for the same reason: instrumentation
    points are global). Known limit: two top-level queries collected
    CONCURRENTLY from different sessions share the first query's trace —
    the second query's events land in (and end with) the first's
    artifacts, and its session's last_trace_paths stays None."""
    global _TRACER, _QUERY_SEQ
    from spark_rapids_tpu import config as Cf
    if not conf.get(Cf.TRACE_ENABLED):
        return None
    with _STATE_LOCK:
        if _TRACER is not None:
            return None
        out_dir = conf.get(Cf.TRACE_PATH) or "/tmp/rapids_tpu_trace"
        level_s = str(conf.get(Cf.TRACE_LEVEL)).strip().upper()
        levels = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE,
                  "DEBUG": DEBUG}
        if level_s not in levels:
            # fail fast: a silent MODERATE fallback would make the user
            # debug missing DEBUG events instead of a typo
            raise ValueError(
                f"invalid {Cf.TRACE_LEVEL.key} {level_s!r}: expected "
                f"ESSENTIAL, MODERATE, or DEBUG")
        lvl = levels[level_s]
        _QUERY_SEQ += 1
        tr = Tracer(out_dir, level=lvl,
                    task_metrics=conf.get(Cf.TRACE_TASK_METRICS),
                    query_id=_QUERY_SEQ)
        _TRACER = tr
        return tr


def end_query(tracer: Tracer,
              last_metrics: Optional[dict] = None,
              status: str = "ok",
              error: Optional[BaseException] = None,
              plan_digest: Optional[str] = None) -> Dict[str, str]:
    """Uninstall + finalize; returns the artifact paths. The tracer is
    uninstalled FIRST so a finalize failure can never leave a dead
    tracer swallowing the next query's events."""
    global _TRACER
    with _STATE_LOCK:
        if _TRACER is tracer:
            _TRACER = None
    return tracer.finalize(last_metrics=last_metrics, status=status,
                           error=error, plan_digest=plan_digest)
