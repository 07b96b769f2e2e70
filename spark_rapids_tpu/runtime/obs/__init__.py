"""Live observability: process-wide registry, HTTP endpoint, query history.

This package is the LIVE half of the observability story — the offline
half (structured traces + event logs + profiler report) is
runtime/trace.py. Data flow:

    GpuMetric / TaskContext accumulators   (per batch, unchanged hot path)
        -> on_task_complete(ctx)           (ONE registry fold per task)
    last_metrics() exec rollups, history   (once per query, at the end)
        -> on_query_end(...)
    registry  ->  /metrics (Prometheus text), tools/history_server.py
    healthz() ->  /healthz (device probe, semaphore, spill, last query)

Overhead discipline (same budget as trace.py): with
`spark.rapids.obs.enabled=false` every hook is one module-global read +
branch; enabled, the hooks run per task/query completion, never per
batch. The HTTP endpoint starts only when `spark.rapids.obs.port` is
set; the history store only when `spark.rapids.obs.historyDir` is set.

Process-wide singleton (like the tracer and the semaphore): the first
session that installs wins the endpoint port and history dir; later
sessions publish into the same registry. Nested collects (broadcast
materialization, subqueries) join the enclosing query — only top-level
actions produce history records.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, List, Optional

from spark_rapids_tpu.runtime.obs import (attribution, flight, live, phases,
                                          reqtrace, sampler)
from spark_rapids_tpu.runtime.obs.history import (  # noqa: F401 (re-export)
    QueryHistoryStore, build_query_record, conf_delta, plan_digest,
)
from spark_rapids_tpu.runtime.obs.registry import MetricsRegistry
from spark_rapids_tpu.runtime.obs.slo import SloDetector

from spark_rapids_tpu.analysis import sanitizer as _san  # noqa: E402

_STATE: "Optional[ObsState]" = None
_STATE_LOCK = _san.lock("obs.state")

#: TaskContext accumulator -> process counter (folded once per task)
_TASK_COUNTERS = {
    "semaphoreWaitTime": ("rapids_semaphore_wait_ns_total",
                          "Total ns tasks waited on the device semaphore"),
    "semaphoreHoldTime": ("rapids_semaphore_hold_ns_total",
                          "Total ns tasks held a device semaphore permit"),
    "retryCount": ("rapids_retries_total",
                   "Retry-OOM attempts replayed"),
    "splitAndRetryCount": ("rapids_split_retries_total",
                           "Split-and-retry OOM splits"),
    "retryBlockTime": ("rapids_retry_block_ns_total",
                       "Total ns spent draining spill stores before "
                       "re-attempts"),
    "retryWastedTime": ("rapids_retry_wasted_ns_total",
                        "Total ns spent in attempts that later OOMed and "
                        "were replayed"),
    "spillToHostBytes": ("rapids_spill_to_host_bytes_total",
                         "Bytes spilled device->host"),
    "spillToDiskBytes": ("rapids_spill_to_disk_bytes_total",
                         "Bytes spilled host->disk"),
    "spillToHostTime": ("rapids_spill_to_host_ns_total",
                        "Total ns spent spilling device->host"),
    "spillToDiskTime": ("rapids_spill_to_disk_ns_total",
                        "Total ns spent spilling host->disk"),
    "shuffleCorruptionRetries": (
        "rapids_shuffle_corruption_retries_total",
        "Shuffle blobs that failed integrity verification and were "
        "transparently re-fetched from the store"),
}


class ObsState:
    """Everything the live layer owns. One per process."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.history: Optional[QueryHistoryStore] = None
        self.server = None  # ObsHttpServer
        self.probe = None   # DeviceProbe
        self.slo: Optional[SloDetector] = None
        #: live query registry gate (spark.rapids.obs.progress.enabled)
        self.progress_enabled = True
        self._lock = threading.Lock()
        self._query_seq = 0
        self._active = 0  # top-level queries currently running
        #: the phase-account records of the newest top-level actions
        #: (runtime/obs/phases.py says what one holds), oldest first;
        #: appended and copied under _lock
        self.recent: collections.deque = collections.deque(
            maxlen=phases.RING_SIZE)
        self._record_seq = 0
        #: the most recent SLO breach: digest, breach doc, attribution
        #: summary, flight-dump path (the /healthz slow-query surface)
        self.last_slow: Optional[dict] = None
        #: the most recent audited query's roofline doc (analysis/
        #: kernel_audit.py) — the /console roofline table reads this
        self.last_roofline: Optional[dict] = None
        #: this process's fleet identity (spark.rapids.obs.replicaId, or
        #: pid-derived) — stamped on every history record so a shared
        #: historyDir splits per replica (tools/fleet_report.py)
        self.replica_id: str = ""

    @property
    def last_query(self) -> Optional[dict]:
        """The newest record of `recent` (the /healthz last_completed)."""
        with self._lock:
            return self.recent[-1] if self.recent else None


#: per-thread collect depth: a re-entrant collect on the SAME thread is
#: a nested action (broadcast materialization, subqueries) and joins the
#: enclosing query; a collect on ANOTHER thread is a concurrent
#: top-level query and gets its own token — queries that merely overlap
#: must not vanish from the counters/history of a serving process
_TLS = threading.local()

#: sentinel token for a nested collect (must still flow to on_query_end
#: so the thread's depth unwinds; publishes nothing)
NESTED = "nested"


def _count(name: str, help_: str, labels: Dict[str, str]) -> None:
    st = _STATE
    if st is not None:
        st.registry.counter(name, help_, labels=labels).inc()


def _counts(name: str, label: str, roster) -> Dict[str, int]:
    """Per-label values of a rostered counter (zeros with obs off)."""
    st = _STATE
    return {v: 0 if st is None else st.registry.counter(
        name, labels={label: v}).value for v in roster}


#: the per-stage catch-and-replay sites: a stage whose trace, compile or
#: setup failed keeps the query correct on a slower path (unfused chain,
#: single-device stage, synchronous pipeline) — counted here so a run on
#: real hardware can FAIL on a dead fast path instead of reading logs
EXEC_FALLBACK_SITES = ("fused_stage", "absorbed_chain", "sharded_stage",
                       "pipeline")
_EXEC_FALLBACK_HELP = ("Stages that fell back to their slower replay path "
                       "after a trace/compile/setup failure")


def note_exec_fallback(site: str) -> None:
    """Count one catch-and-replay fallback at `site`."""
    _count("rapids_stage_fallbacks_total", _EXEC_FALLBACK_HELP,
           {"site": site})


def exec_fallbacks() -> int:
    """Fallbacks counted so far across every site (0 with obs off)."""
    return sum(_counts("rapids_stage_fallbacks_total", "site",
                       EXEC_FALLBACK_SITES).values())


#: which Pallas sorted-window aggregation path a traced group-by took
#: (exec/tpu_nodes._AggKernels._bucket_scatter_agg): the eligibility
#: gates are data-dependent, so only a run says whether a query's shapes
#: ever reach the kernel. Counted at TRACE time — zero per-dispatch cost
PALLAS_SEGSUM_PATHS = ("whole", "chunked")
_PALLAS_SEGSUM_HELP = ("Group-by traces routed into the Pallas sorted-"
                       "window segmented sum, by path")


def note_pallas_segsum(path: str) -> None:
    """Count one group-by trace that took the Pallas segsum `path`."""
    _count("rapids_pallas_segsum_traces_total", _PALLAS_SEGSUM_HELP,
           {"path": path})


def pallas_segsum_traces() -> Dict[str, int]:
    """Per-path trace counts so far (zeros with obs off)."""
    return _counts("rapids_pallas_segsum_traces_total", "path",
                   PALLAS_SEGSUM_PATHS)


def _preregister(reg: MetricsRegistry) -> None:
    """Create the roster instruments up front so a scrape before the
    first task/query still renders them (at zero) — an empty /metrics
    reads as a broken exporter, not an idle engine."""
    for _, (name, help_) in _TASK_COUNTERS.items():
        reg.counter(name, help_)
    reg.counter("rapids_tasks_completed_total", "Tasks completed")
    reg.counter("rapids_tasks_failed_total", "Tasks failed")
    reg.counter("rapids_tasks_cancelled_total",
                "Tasks unwound by a query cancel token or an early "
                "sibling close (neither completed nor failed)")
    for status in ("ok", "failed", "degraded", "cancelled"):
        reg.counter("rapids_queries_total", "Queries completed",
                    labels={"status": status})
    reg.counter("rapids_queries_rejected_total",
                "Queries refused by admission control "
                "(spark.rapids.query.maxConcurrent)")
    reg.counter("rapids_faults_injected_total",
                "Injected faults fired (spark.rapids.debug.faults)")
    for site in EXEC_FALLBACK_SITES:
        reg.counter("rapids_stage_fallbacks_total", _EXEC_FALLBACK_HELP,
                    labels={"site": site})
    for path in PALLAS_SEGSUM_PATHS:
        reg.counter("rapids_pallas_segsum_traces_total",
                    _PALLAS_SEGSUM_HELP, labels={"path": path})
    reg.counter("rapids_watchdog_dispatch_timeouts_total",
                "Device dispatches that exceeded the watchdog deadline")
    reg.counter("rapids_breaker_transitions_total",
                "Circuit-breaker state transitions",
                labels={"to": "open"})

    def _breaker_open():
        from spark_rapids_tpu.runtime import watchdog as WD
        brk = WD.peek_breaker()
        return 0 if brk is None or brk.state == "closed" else (
            2 if brk.state == "open" else 1)

    reg.gauge_fn("rapids_breaker_state", _breaker_open,
                 "Device circuit-breaker state "
                 "(0 closed, 1 half-open, 2 open)")
    reg.counter("rapids_shuffle_bytes_written_total",
                "Serialized shuffle bytes written to the host store")
    reg.counter("rapids_shuffle_bytes_spilled_total",
                "Serialized shuffle bytes spilled to disk")
    reg.counter("rapids_slo_breaches_total",
                "Queries that exceeded their latency SLO "
                "(spark.rapids.obs.slo.*)")
    # compile accounting (runtime/compile_cache.py): backend compiles
    # and persistent-cache traffic count via the jax.monitoring
    # listener; warm-trace hit/miss read live from the cache stats
    reg.counter("rapids_xla_compiles_total",
                "XLA backend compiles observed process-wide (including "
                "jit signature-cache re-traces)")
    reg.float_counter("rapids_xla_compile_seconds_total",
                      "Seconds spent in XLA backend compiles")
    reg.counter("rapids_persistent_cache_hits_total",
                "Compile requests served from the persistent "
                "compilation cache (spark.rapids.compile.cacheDir)")
    reg.counter("rapids_persistent_cache_misses_total",
                "Compile requests the persistent compilation cache "
                "missed")

    def _cc_stat(name):
        def read():
            from spark_rapids_tpu.runtime import compile_cache as CC
            return CC.stats()[name]
        return read

    reg.gauge_fn("rapids_compile_cache_hits", _cc_stat("hits"),
                 "Warm-trace compile-cache hits (keyed entries resolved "
                 "without building)")
    reg.gauge_fn("rapids_compile_cache_misses", _cc_stat("misses"),
                 "Warm-trace compile-cache misses (fresh entries built "
                 "and first-call compile paid)")
    reg.gauge_fn("rapids_compile_cache_entries", _cc_stat("entries"),
                 "Live warm-trace compile-cache entries")
    reg.counter("rapids_flight_dumps_total",
                "Flight-recorder dumps written, by trigger",
                labels={"reason": "query_failed"})
    # serving layer (runtime/serving/): request intake and the
    # plan-digest-keyed result cache
    reg.counter("rapids_serving_requests_total",
                "POST /sql requests accepted into the serving "
                "layer (past the maxInflight bound).")
    reg.counter("rapids_serving_rejected_total",
                "POST /sql requests refused with HTTP 429 "
                "(maxInflight, maxSessions, or admission-gate "
                "rejection).")
    reg.counter("rapids_result_cache_hits_total",
                "Serving result-cache hits (byte-identical replay of "
                "a prior execution with the same plan digest, table "
                "epoch, and compile fingerprint).")
    reg.counter("rapids_result_cache_misses_total",
                "Serving result-cache misses (the request executed and "
                "its serialized result was inserted).")
    reg.counter("rapids_result_cache_evictions_total",
                "Serving result-cache LRU evictions (byte or entry "
                "bound exceeded).")
    reg.counter("rapids_result_cache_bypasses_total",
                "Serving requests that bypassed the result cache "
                "(non-deterministic plan or cache=false).")
    for phase in attribution.BUCKETS:
        reg.float_counter(
            "rapids_query_seconds_bucket",
            "Per-query wall time attributed to each phase bucket "
            "(seconds; runtime/obs/attribution.py)",
            labels={"phase": phase})
    # roofline attribution of the most recent AUDITED query (analysis/
    # kernel_audit.py; spark.rapids.obs.audit.enabled): set once per
    # query end, zero when no audited query has completed yet
    for group in ("device_compute", "shuffle", "total"):
        reg.gauge("rapids_roofline_achieved_gbps",
                  "Achieved device bandwidth of the most recent "
                  "audited query (audited bytes / measured device "
                  "seconds)", labels={"group": group})
        reg.gauge("rapids_roofline_pct",
                  "Share of the configured bandwidth roofline "
                  "(spark.rapids.obs.audit.peakGbps) the most recent "
                  "audited query achieved", labels={"group": group})
    for group in ("device_compute", "shuffle"):
        reg.gauge("rapids_roofline_achieved_gflops",
                  "Achieved device FLOP rate of the most recent "
                  "audited query", labels={"group": group})
        reg.gauge("rapids_roofline_padding_waste_ratio",
                  "Worst-case shape-bucket padding share of the most "
                  "recent audited query's input plane bytes "
                  "(runtime/shapes.py ladder exposure)",
                  labels={"group": group})
    reg.histogram("rapids_query_wall_time_ms",
                  "Per-query wall time (ms)")
    reg.histogram("rapids_serving_request_ms",
                  "Per-request serving wall time (ms), intake to "
                  "response doc; buckets carry reqtrace exemplars")
    reg.histogram("rapids_task_duration_ms", "Per-task duration (ms)")
    reg.gauge("rapids_max_device_bytes_held",
              "High-water mark of registered device bytes (any task)")
    # live gauges (evaluated at scrape time)
    from spark_rapids_tpu.runtime import host_pool as HP
    from spark_rapids_tpu.runtime import memory as MEM
    from spark_rapids_tpu.runtime import semaphore as SEM

    def _sem(attr):
        def read():
            sem = SEM.peek_semaphore()
            return getattr(sem, attr) if sem is not None else 0
        return read

    reg.gauge_fn("rapids_semaphore_available", _sem("available"),
                 "Device semaphore permits currently free")
    reg.gauge_fn("rapids_semaphore_waiting", _sem("waiting"),
                 "Tasks parked on the device semaphore")

    def _pool_depth(tier):
        def read():
            pool = HP.current_pool()
            return pool.queue_depths().get(tier, 0) if pool else 0
        return read

    for tier in ("tier0", "tier1"):
        reg.gauge_fn("rapids_host_pool_queue_depth", _pool_depth(tier),
                     "Host task-pool queued (not yet running) tasks",
                     labels={"tier": tier})

    def _spill(attr):
        def read():
            fw = MEM.peek_spill_framework()
            return getattr(fw, attr)() if fw is not None else 0
        return read

    reg.gauge_fn("rapids_device_bytes_held", _spill("device_bytes_held"),
                 "Registered (spillable) device bytes currently held")
    reg.gauge_fn("rapids_host_spill_bytes_held", _spill("host_bytes_held"),
                 "Spilled bytes currently resident in the host store")
    # the live query registry + resource sampler (runtime/obs/live.py,
    # runtime/obs/sampler.py): one gauge per rostered series reading
    # the ring's newest sample, so Prometheus and the console agree on
    # "current"; running-query count reads the registry live
    reg.gauge_fn("rapids_queries_running", live.running_count,
                 "Top-level queries currently in flight (live registry)")

    def _smp(series):
        def read():
            s = sampler.sampler()
            if s is None:
                return 0.0
            smp = s.rings[series].latest()
            return smp[1] if smp is not None else 0.0
        return read

    for series, shelp in sampler.SERIES.items():
        reg.gauge_fn(f"rapids_sampler_{series}", _smp(series),
                     f"Sampled {shelp} (newest ring sample; "
                     f"spark.rapids.obs.sampler.*)")


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def install(conf) -> "Optional[ObsState]":
    """Install (or extend) the process-wide observability state from a
    session's conf. Idempotent; called from TpuSession.__init__."""
    global _STATE
    from spark_rapids_tpu import config as Cf
    # the flight recorder is its own conf's concern: always-on unless
    # spark.rapids.obs.flight.enabled=false, even with the live layer off
    flight.maybe_install(conf)
    # the resource sampler is likewise its own conf's concern: always-on
    # (like the flight recorder) even with the live layer off, so every
    # flight dump carries its promised counter tracks
    sampler.maybe_install(conf)
    # per-request tail-sampled tracing (opt-in:
    # spark.rapids.obs.reqtrace.enabled) — its own conf's concern too
    reqtrace.maybe_install(conf)
    if not conf.get(Cf.OBS_ENABLED):
        return _STATE
    with _STATE_LOCK:
        st = _STATE
        if st is None:
            st = ObsState(MetricsRegistry())
            _preregister(st.registry)
            # log lines from any thread attribute to the bound query:
            # %(query_id)s becomes available to every formatter on the
            # engine logger (idempotent: one filter instance per type)
            import logging
            lg = logging.getLogger("spark_rapids_tpu")
            if not any(isinstance(f, live.QueryLogFilter)
                       for f in lg.filters):
                lg.addFilter(live.QueryLogFilter())
            _STATE = st
        st.progress_enabled = bool(conf.get(Cf.OBS_PROGRESS_ENABLED))
        if not st.replica_id:
            import os as _os
            st.replica_id = (conf.get(Cf.OBS_REPLICA_ID)
                             or f"pid-{_os.getpid()}")
        hist_dir = conf.get(Cf.OBS_HISTORY_DIR)
        if hist_dir and st.history is None:
            st.history = QueryHistoryStore(hist_dir)
        if st.slo is None:
            st.slo = SloDetector()
        st.slo.configure(conf.get(Cf.OBS_SLO_ENABLED),
                         conf.get(Cf.OBS_SLO_FACTOR),
                         conf.get(Cf.OBS_SLO_MIN_RUNS),
                         conf.get(Cf.OBS_SLO_ABS_SECONDS),
                         conf.get(Cf.OBS_SLO_WINDOW))
        port = int(conf.get(Cf.OBS_PORT))
        if port > 0 and st.server is None:
            from spark_rapids_tpu.runtime.obs.endpoint import (
                DeviceProbe, ObsHttpServer,
            )
            if st.probe is None:
                st.probe = DeviceProbe(
                    timeout_s=conf.get(Cf.OBS_PROBE_TIMEOUT_MS) / 1000.0)
            try:
                from spark_rapids_tpu.runtime.obs.console import \
                    render_live
                server = ObsHttpServer(port, st.registry.render_prometheus,
                                       healthz,
                                       queries=live.queries_doc,
                                       console=render_live,
                                       cors_origin=conf.get(
                                           Cf.OBS_CORS_ORIGIN),
                                       cancel=_cancel_query,
                                       sql=_serving_sql,
                                       serving=_serving_doc)
                server.start()
                st.server = server
            except Exception:  # noqa: BLE001 - a bind failure (port in
                # use by another engine process) must not kill session
                # construction for an observability feature; queries run,
                # the endpoint just isn't served from this process
                import logging
                logging.getLogger("spark_rapids_tpu").warning(
                    "failed to start obs endpoint on port %d", port,
                    exc_info=True)
    if st.history is not None:
        # baselines survive restarts: seed once from the store (outside
        # the state lock — seeding reads the history file)
        st.slo.seed_from_history(st.history)
    return st


def state() -> "Optional[ObsState]":
    return _STATE


def enabled() -> bool:
    return _STATE is not None


def shutdown_for_tests() -> None:
    """Tear the singleton down (tests only: frees the port, drops the
    registry so the next install starts clean). Also stops the resource
    sampler's service thread and clears the live query registry."""
    global _STATE
    with _STATE_LOCK:
        st, _STATE = _STATE, None
    if st is not None and st.server is not None:
        try:
            st.server.stop()
        except Exception:  # noqa: BLE001
            pass
    sampler.uninstall_for_tests()
    live.reset_for_tests()


def set_device_probe(fn: Callable[[], bool]) -> None:
    """Swap the /healthz device probe (tests: a blocking fn proves the
    degraded flip without wedging a real device)."""
    st = _STATE
    if st is not None:
        from spark_rapids_tpu.runtime.obs.endpoint import DeviceProbe
        timeout = st.probe.timeout_s if st.probe is not None else 2.0
        st.probe = DeviceProbe(fn, timeout_s=timeout)


# ---------------------------------------------------------------------------
# publish hooks (the only calls on engine paths)
# ---------------------------------------------------------------------------

def on_task_complete(ctx) -> None:
    """Fold one finished task's accumulators into the process registry —
    ONE write batch per task, nothing per batch. Called by
    TaskContext.complete after the trace rollup."""
    st = _STATE
    if st is None:
        return
    reg = st.registry
    try:
        if getattr(ctx, "_cancelled", False):
            reg.counter("rapids_tasks_cancelled_total").inc()
        else:
            reg.counter("rapids_tasks_failed_total" if ctx._failed
                        else "rapids_tasks_completed_total").inc()
        dur_ns = time.perf_counter_ns() - ctx.start_ns
        reg.histogram("rapids_task_duration_ms").observe(dur_ns / 1e6)
        for acc_name, (cname, chelp) in _TASK_COUNTERS.items():
            m = ctx._metrics.get(acc_name)
            if m is None:
                continue
            try:
                v = int(m.value)
            except Exception:  # noqa: BLE001 - unresolvable lazy count
                continue
            if v:
                reg.counter(cname, chelp).inc(v)
        mdb = ctx._metrics.get("maxDeviceBytesHeld")
        if mdb is not None:
            reg.gauge("rapids_max_device_bytes_held").set_max(int(mdb.value))
    except Exception:  # noqa: BLE001 - observability never fails a task
        pass


def on_query_start(plan_digest: Optional[str] = None,
                   sql: Optional[str] = None):
    """Returns a query token: None when obs is off, the NESTED sentinel
    for a re-entrant collect on this thread (it joins the enclosing
    query but must still reach on_query_end to unwind the depth), or a
    fresh query id. Concurrent top-level queries from other threads/
    sessions each get their own token — they all count, and each gets
    its OWN live QueryContext (runtime/obs/live.py) carrying its own
    exec tree, so concurrent progress never interleaves the way the
    tracer-singleton per-exec rollups can. The token also binds to the
    calling thread as the correlation id (propagated by host_pool /
    pipeline / task to every thread working for this query)."""
    st = _STATE
    if st is None:
        return None
    depth = getattr(_TLS, "depth", 0)
    _TLS.depth = depth + 1
    if depth:
        return NESTED
    with st._lock:
        st._query_seq += 1
        st._active += 1
        token = st._query_seq
    live.bind(token)
    if st.progress_enabled:
        try:
            # registered in the `queued` state: the session transitions
            # it to `planning` once admission control
            # (spark.rapids.query.maxConcurrent — runtime/lifecycle.py)
            # grants the slot; ungated queries pass through immediately
            live.register(token, plan_digest=plan_digest, sql=sql)
        except Exception:  # noqa: BLE001 - the registry must never
            pass  # fail a query
    return token


def publish_query_record(rec: dict) -> None:
    """Append one finished top-level action's phase-account record
    (phases.QueryPhases.record) to the ring; obs off: nothing is kept."""
    st = _STATE
    if st is None:
        return
    slow = st.last_slow  # on_query_end ran before: the breach is there
    if slow is not None and slow["query_id"] == rec["query_id"]:
        rec["slo_breach"] = True
    with st._lock:
        st._record_seq += 1
        rec["seq"] = st._record_seq
        st.recent.append(rec)


def recent_queries(n: Optional[int] = None) -> List[dict]:
    """The records of the newest `n` top-level actions (all the ring
    holds, at most phases.RING_SIZE, when None), oldest first; empty
    when obs is off. runtime/obs/phases.py documents a record."""
    st = _STATE
    if st is None:
        return []
    with st._lock:
        recs = list(st.recent)
    return recs if n is None else recs[max(len(recs) - n, 0):]


def wants_rollups() -> bool:
    """Does a consumer (endpoint or history store) exist for per-exec
    rollups? The epilogue uses this to decide whether the metric
    snapshot — which resolves lazy device row counts, real syncs — is
    worth taking at all."""
    st = _STATE
    return st is not None and (st.server is not None
                               or st.history is not None)


def on_query_end(token, *, session, plan, status: str,
                 error: Optional[BaseException], duration_ns: int,
                 wall_start_unix: float,
                 trace_paths: Optional[dict],
                 last_metrics: Optional[Dict[str, dict]] = None,
                 degraded_reason: Optional[str] = None,
                 attribution_doc: Optional[dict] = None,
                 roofline_doc: Optional[dict] = None,
                 aqe_doc: Optional[dict] = None,
                 flight_dump: Optional[str] = None
                 ) -> Optional[dict]:
    """Publish one finished top-level action: registry rollups, the SLO
    check, the attribution export, and the history record. Returns the
    record (None when history is off). MUST be called for every
    non-None token (including NESTED) — it unwinds the thread's collect
    depth."""
    _TLS.depth = max(0, getattr(_TLS, "depth", 1) - 1)
    st = _STATE
    if st is None or token is NESTED:
        return None
    # land the terminal live-registry state and release this thread's
    # correlation binding (a NESTED return above keeps the outer
    # query's binding intact)
    try:
        live.finish(token, status, duration_ns=duration_ns)
    except Exception:  # noqa: BLE001 - the registry must never fail a
        pass  # query epilogue
    live.bind(None)
    # distributed tracing: the epilogue runs on the request's handler
    # thread, so the bound serving request (if any) learns its query's
    # live id here — the join key between its serving span tree and the
    # engine exec spans sharing its ring
    rctx = live.current_request()
    if rctx is not None and isinstance(token, int):
        rctx.query_id = token
    reg = st.registry
    try:
        reg.counter("rapids_queries_total",
                    labels={"status": status}).inc()
        reg.histogram("rapids_query_wall_time_ms").observe(
            duration_ns / 1e6,
            exemplar=({"trace_id": rctx.trace_id}
                      if rctx is not None else None))
        if attribution_doc:
            for phase, secs in attribution_doc.get("buckets", {}).items():
                if secs:
                    reg.float_counter("rapids_query_seconds_bucket",
                                      labels={"phase": phase}).inc(secs)
        if roofline_doc:
            st.last_roofline = roofline_doc
            # last-audited-query roofline gauges (the console and any
            # scraper read these; per-query history carries the full
            # doc). Zero the whole group roster FIRST: a query whose
            # doc omits a group (no exchange dispatched) must not leave
            # a PREVIOUS query's number labelled as this one's.
            for group in ("device_compute", "shuffle", "total"):
                lbl = {"group": group}
                reg.gauge("rapids_roofline_achieved_gbps",
                          labels=lbl).set(0.0)
                reg.gauge("rapids_roofline_pct", labels=lbl).set(0.0)
                if group != "total":
                    reg.gauge("rapids_roofline_achieved_gflops",
                              labels=lbl).set(0.0)
                    reg.gauge("rapids_roofline_padding_waste_ratio",
                              labels=lbl).set(0.0)
            for group, g in roofline_doc.get("groups", {}).items():
                lbl = {"group": group}
                reg.gauge("rapids_roofline_achieved_gbps", labels=lbl
                          ).set(g.get("achieved_gbps") or 0.0)
                reg.gauge("rapids_roofline_pct", labels=lbl
                          ).set(_share(g.get("roofline_pct_bw")))
                reg.gauge("rapids_roofline_achieved_gflops", labels=lbl
                          ).set(g.get("achieved_gflops") or 0.0)
                reg.gauge("rapids_roofline_padding_waste_ratio",
                          labels=lbl
                          ).set(g.get("padding_waste_ratio") or 0.0)
            tot = roofline_doc.get("total") or {}
            reg.gauge("rapids_roofline_achieved_gbps",
                      labels={"group": "total"}
                      ).set(tot.get("achieved_gbps") or 0.0)
            reg.gauge("rapids_roofline_pct", labels={"group": "total"}
                      ).set(_share(tot.get("roofline_pct_bw")))
        digest = None
        try:
            digest = plan_digest(plan)
        except Exception:  # noqa: BLE001 - an undigestable plan still
            pass  # publishes; it just cannot baseline or diff
        breach = None
        if st.slo is not None and status == "ok" and digest:
            breach = st.slo.record(digest, duration_ns / 1e9)
        if rctx is not None and breach is not None:
            # the request's tail-sampling verdict must see the breach
            rctx.slo_breach = True
        if breach is not None:
            if attribution_doc is None:
                # no rollup consumer took a snapshot for this query —
                # a breach is worth the lazy-count syncs of one now
                try:
                    attribution_doc = session.last_attribution()
                except Exception:  # noqa: BLE001 - advisory
                    pass
            reg.counter("rapids_slo_breaches_total").inc()
            try:
                from spark_rapids_tpu.runtime import trace as _tr
                _tr.instant("slowQuery", cat="query", args=dict(breach),
                            level=_tr.ESSENTIAL)
            except Exception:  # noqa: BLE001 - slo must not need a tracer
                pass
            if flight_dump is None:
                flight_dump = flight.dump(
                    "slo_breach",
                    query_id=token if isinstance(token, int) else None)
            st.last_slow = {
                "query_id": token,
                "plan_digest": digest,
                "wall_ms": round(duration_ns / 1e6, 3),
                "breach": breach,
                "attribution": attribution.summary(attribution_doc),
                "flight_dump": flight_dump,
                "finished_unix": time.time(),
            }
        # per-exec rollups resolve lazy device row counts (real syncs):
        # pay them only when something consumes the result — a scrape
        # endpoint or the history store. A bare registry (obs enabled,
        # nothing configured) keeps the query epilogue sync-free, and
        # the caller's snapshot (if it took one for the trace) is
        # reused so the epilogue snapshots the tree exactly ONCE.
        snaps = last_metrics
        if st.server is not None or st.history is not None:
            if snaps is None:
                snaps = {}
                try:
                    snaps = session.last_metrics()
                except Exception:  # noqa: BLE001 - a poisoned lazy count
                    pass  # must not drop the whole publish
            _publish_exec_rollups(reg, snaps)
        rec = None
        if st.history is not None:
            mesh_doc = None
            try:
                conf = getattr(session, "conf", None)
                from spark_rapids_tpu.parallel import mesh as _mesh
                if conf is not None and _mesh.multichip_on(conf):
                    mesh_doc = {
                        "n_devices": _mesh.multichip_devices(conf),
                        "axes": [_mesh.PART_AXIS],
                    }
            except Exception:  # noqa: BLE001 - history never fails a query
                mesh_doc = None
            rec = build_query_record(
                query_id=token, wall_start_unix=wall_start_unix,
                duration_ns=duration_ns, status=status, error=error,
                plan=plan, session=session, trace_paths=trace_paths,
                snaps=snaps, degraded_reason=degraded_reason,
                attribution=attribution_doc, roofline=roofline_doc,
                aqe=aqe_doc, slo_breach=breach,
                flight_dump=flight_dump, digest=digest,
                replica_id=st.replica_id or None,
                trace_id=rctx.trace_id if rctx is not None else None,
                mesh=mesh_doc)
            st.history.append(rec)
        return rec
    except Exception:  # noqa: BLE001 - observability never fails a query
        return None
    finally:
        with st._lock:
            st._active -= 1


def _share(pct) -> float:
    """A roofline share as a gauge value: NaN where the doc carries
    None (a device whose peaks are unknown), never a made-up 0."""
    return float("nan") if pct is None else pct


def _publish_exec_rollups(reg: MetricsRegistry, snaps: Dict[str, dict]
                          ) -> None:
    """Per-exec-CLASS rollups (bounded cardinality: one series per
    operator type, not per instance)."""
    from spark_rapids_tpu.runtime.metrics import exec_rollup
    per_cls: Dict[str, dict] = {}
    shuffle_written = shuffle_spilled = 0
    for exec_key, snap in snaps.items():
        cls = exec_key.split("#", 1)[0]
        r = exec_rollup(snap)
        dst = per_cls.setdefault(cls, {"rows": 0, "batches": 0,
                                       "dispatches": 0, "time_ns": 0})
        for k in dst:
            v = r.get(k)
            if v:
                dst[k] += int(v)
        shuffle_written += int(snap.get("shuffleBytesWritten", 0))
        shuffle_spilled += int(snap.get("shuffleBytesSpilled", 0))
    for cls, r in per_cls.items():
        lbl = {"exec": cls}
        if r["time_ns"]:
            reg.counter("rapids_exec_time_ns_total",
                        "Per-operator-class device/op time (ns)",
                        labels=lbl).inc(r["time_ns"])
        if r["rows"]:
            reg.counter("rapids_exec_rows_total",
                        "Per-operator-class output rows", labels=lbl
                        ).inc(r["rows"])
        if r["dispatches"]:
            reg.counter("rapids_exec_dispatches_total",
                        "Per-operator-class device dispatches", labels=lbl
                        ).inc(r["dispatches"])
    if shuffle_written:
        reg.counter("rapids_shuffle_bytes_written_total"
                    ).inc(shuffle_written)
    if shuffle_spilled:
        reg.counter("rapids_shuffle_bytes_spilled_total"
                    ).inc(shuffle_spilled)


# ---------------------------------------------------------------------------
# health
# ---------------------------------------------------------------------------

def _compile_doc():
    try:
        from spark_rapids_tpu.runtime import compile_cache as CC
        return CC.doc()
    except Exception:  # noqa: BLE001 - health must always render
        return None


def _warmup_doc():
    try:
        from spark_rapids_tpu.runtime import warmup as WU
        return WU.doc()
    except Exception:  # noqa: BLE001 - health must always render
        return None


def _lifecycle_doc():
    try:
        from spark_rapids_tpu.runtime import lifecycle as LC
        return LC.doc()
    except Exception:  # noqa: BLE001 - health must always render
        return None


def _cancel_query(query_id) -> bool:
    """The POST /queries/<id>/cancel handler target."""
    from spark_rapids_tpu.runtime import lifecycle as LC
    return LC.cancel(query_id, reason="http")


def _serving_sql(payload: dict):
    """The POST /sql handler target (lazy: the serving layer may install
    after the endpoint starts, or never)."""
    from spark_rapids_tpu.runtime import serving as SRV
    return SRV.handle_sql(payload)


def _serving_doc():
    """The GET /serving + healthz['serving'] document (None when the
    serving layer is not installed)."""
    try:
        from spark_rapids_tpu.runtime import serving as SRV
        return SRV.server_doc()
    except Exception:  # noqa: BLE001 - health must always render
        return None


def suppressed_actions():
    """Context manager making every collect on the CURRENT thread look
    nested to the live layer (on_query_start returns NESTED: no history
    record, no SLO fold, no query counters). The AOT warmup replays run
    under this — they are cache-priming work, not user queries."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        _TLS.depth = getattr(_TLS, "depth", 0) + 1
        try:
            yield
        finally:
            _TLS.depth = max(0, getattr(_TLS, "depth", 1) - 1)

    return _cm()


def healthz() -> dict:
    """The /healthz document. Degraded when the device probe is blocked
    or failing OR the device circuit breaker is open (the engine is
    serving, but on the CPU fallback path); breaker state and per-site
    injected-fault counts ride along so a prober can tell a degraded
    serving process from a healthy one without parsing logs."""
    st = _STATE
    if st is None:
        return {"status": "degraded", "reason": "obs not installed"}
    from spark_rapids_tpu.runtime import faults as FLT
    from spark_rapids_tpu.runtime import memory as MEM
    from spark_rapids_tpu.runtime import semaphore as SEM
    from spark_rapids_tpu.runtime import watchdog as WD
    if st.probe is None:
        from spark_rapids_tpu.runtime.obs.endpoint import DeviceProbe
        st.probe = DeviceProbe()
    sem = SEM.peek_semaphore()
    sem_doc = {"permits": sem.permits, "available": sem.available,
               "waiting": sem.waiting,
               "saturated": sem.available == 0} if sem is not None else None
    # a busy device is not a degraded device: while a running query
    # holds EVERY semaphore permit, the liveness probe's trivial
    # dispatch would queue behind real work (or time out and flip the
    # status) — defer it and report the reason instead. `_active` (not
    # the live registry, which progress.enabled=false leaves empty)
    # counts in-flight top-level queries unconditionally.
    with st._lock:
        active = st._active
    if sem is not None and sem.available == 0 and active > 0:
        device = {"alive": None, "deferred": True,
                  "reason": "all semaphore permits held by a running "
                            "query; probe skipped"}
        device_ok = True
    else:
        device = st.probe.check()
        device_ok = bool(device.get("alive"))
    fw = MEM.peek_spill_framework()
    if fw is not None:
        host_held = fw.host_bytes_held()
        spill_doc = {
            "device_bytes_held": fw.device_bytes_held(),
            "device_budget": fw.device_budget,
            "host_bytes_held": host_held,
            "host_budget": fw.host_budget,
            "disk_spill_bytes": fw.metrics.get("spill_to_disk_bytes", 0),
            "pressure": round(host_held / fw.host_budget, 4)
            if fw.host_budget else 0.0,
        }
    else:
        spill_doc = None
    # direct counter reads: a full registry snapshot would walk every
    # histogram's quantiles per poll, and load balancers poll often
    reg = st.registry
    brk = WD.peek_breaker()
    breaker_doc = brk.state_doc() if brk is not None else {
        "backend": "device", "state": "closed"}
    return {
        "status": "ok" if (device_ok
                           and breaker_doc["state"] != "open")
        else "degraded",
        "device": device,
        "breaker": breaker_doc,
        "faults": FLT.fault_counts(),
        "semaphore": sem_doc,
        "spill": spill_doc,
        # the retroactive surfaces: most recent flight dump + the last
        # slow query (digest, breach, attribution summary, dump path)
        "flight": flight.doc(),
        # compile tax: warm-trace hit/miss, backend compile totals, the
        # persistent layer's cross-process traffic, and AOT warmup
        # progress (runtime/compile_cache.py + runtime/warmup.py)
        "compile": _compile_doc(),
        "warmup": _warmup_doc(),
        "slo": dict(st.slo.doc(), last_slow=st.last_slow)
        if st.slo is not None else None,
        # the resource time-series sampler's state + newest samples
        "sampler": sampler.doc(),
        # the prospective surface: every in-flight query's live state/
        # progress (compact — /queries carries the per-exec detail) +
        # the last completed record and the lifetime counters
        "queries": {
            "active": active,
            "running": live.running_docs(with_execs=False),
            "completed_ok": reg.counter(
                "rapids_queries_total", labels={"status": "ok"}).value,
            "failed": reg.counter(
                "rapids_queries_total",
                labels={"status": "failed"}).value,
            "degraded": reg.counter(
                "rapids_queries_total",
                labels={"status": "degraded"}).value,
            "cancelled": reg.counter(
                "rapids_queries_total",
                labels={"status": "cancelled"}).value,
            "rejected": reg.counter(
                "rapids_queries_rejected_total").value,
            "last_completed": st.last_query,
        },
        # query lifecycle control (runtime/lifecycle.py): live cancel
        # tokens, admission-gate occupancy, reject/cancel totals
        "lifecycle": _lifecycle_doc(),
        # the serving layer (runtime/serving/): intake bounds, overlay
        # sessions, result-cache traffic (None when serving is off)
        "serving": _serving_doc(),
    }
