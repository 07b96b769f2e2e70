"""Typed config registry with documentation generation.

Reference parity: com/nvidia/spark/rapids/RapidsConf.scala (251 typed
`spark.rapids.*` entries built by a ConfBuilder DSL with doc strings and a
`help` main that emits docs/configs.md). Same design here: every knob is
declared once with type/default/doc, values can be overridden per-session,
and `generate_docs()` renders the registry to markdown.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Callable, Dict, Optional

_REGISTRY: "Dict[str, ConfEntry]" = {}


@dataclasses.dataclass(frozen=True)
class ConfEntry:
    key: str
    default: Any
    doc: str
    conv: Callable[[str], Any]
    internal: bool = False
    startup_only: bool = False
    commonly_used: bool = False

    def render_default(self) -> str:
        return "None" if self.default is None else str(self.default)


def _bool_conv(s: str) -> bool:
    return str(s).strip().lower() in ("1", "true", "yes", "on")


def _register(key, default, doc, conv, **kw) -> ConfEntry:
    e = ConfEntry(key, default, doc, conv, **kw)
    if key in _REGISTRY:
        raise ValueError(f"duplicate conf key {key}")
    _REGISTRY[key] = e
    return e


def conf_bool(key, default, doc, **kw):
    return _register(key, default, doc, _bool_conv, **kw)


def conf_int(key, default, doc, **kw):
    return _register(key, default, doc, int, **kw)


def conf_float(key, default, doc, **kw):
    return _register(key, default, doc, float, **kw)


def conf_str(key, default, doc, **kw):
    return _register(key, default, doc, str, **kw)


# ---------------------------------------------------------------------------
# The registry. Key namespace mirrors the reference's spark.rapids.* layout
# so users migrating from the reference find the same knobs.
# ---------------------------------------------------------------------------

SQL_ENABLED = conf_bool(
    "spark.rapids.sql.enabled", True,
    "Enable TPU acceleration of SQL plans (reference RapidsConf.scala:801).",
    commonly_used=True)

SQL_MODE = conf_str(
    "spark.rapids.sql.mode", "executeOnTPU",
    "executeOnTPU runs supported operators on TPU; explainOnly plans and "
    "reports what would run on TPU without requiring a device "
    "(reference RapidsConf.scala:807).",
    commonly_used=True)

SQL_EXPLAIN = conf_str(
    "spark.rapids.sql.explain", "NOT_ON_TPU",
    "What to log about plan placement: NONE, NOT_ON_TPU (every fallback with "
    "its reason), ALL (reference RapidsConf.scala:2107).",
    commonly_used=True)

CONCURRENT_TPU_TASKS = conf_int(
    "spark.rapids.sql.concurrentTpuTasks", 2,
    "Number of tasks admitted to the device concurrently by the semaphore "
    "(reference GpuSemaphore / RapidsConf.scala:545).",
    commonly_used=True)

TARGET_BATCH_SIZE = conf_int(
    "spark.rapids.sql.batchSizeBytes", 1 << 30,
    "Target columnar batch size in bytes; coalesce goals aim for this "
    "(reference gpuTargetBatchSizeBytes).",
    commonly_used=True)

MAX_READER_BATCH_SIZE_ROWS = conf_int(
    "spark.rapids.sql.reader.batchSizeRows", 1 << 20,
    "Soft cap on rows per batch produced by scans.")

BATCH_CAPACITY_MIN = conf_int(
    "spark.rapids.tpu.batchCapacityMinRows", 1024,
    "Minimum padded row capacity of a device batch; capacities are rounded "
    "to size buckets so XLA compiles each stage once per bucket.")

DEVICE_MEMORY_FRACTION = conf_float(
    "spark.rapids.memory.tpu.allocFraction", 0.85,
    "Fraction of per-chip HBM the arena budget may use "
    "(reference rmm.pool allocFraction).", startup_only=True)

WRITER_THREADS = conf_int(
    "spark.rapids.sql.asyncWrite.numThreads", 4,
    "Background threads encoding+writing output files (reference "
    "io/async ThrottlingExecutor).")

OPTIMIZER_ENABLED = conf_bool(
    "spark.rapids.sql.optimizer.enabled", False,
    "Cost-based reversion of TPU subtrees whose estimated device cost "
    "(incl. transfer + dispatch) exceeds the CPU cost "
    "(reference CostBasedOptimizer.scala, off by default).")

PROFILE_DIR = conf_str(
    "spark.rapids.profile.dir", "",
    "When set, each collect() runs under a jax.profiler trace written to "
    "this directory (XProf/TensorBoard-viewable; the reference's "
    "CUPTI-based Profiler + NVTX analog).")

TRACE_ENABLED = conf_bool(
    "spark.rapids.sql.trace.enabled", False,
    "Record a structured trace per query: spans for every exec's device "
    "work (tied to the same GpuMetric timers the SQL metrics use — one "
    "instrumentation point), instant events for semaphore/spill/retry/"
    "host-pool/fused-dispatch activity, and a per-task accumulator event "
    "log, written as Chrome-trace-event JSON plus JSONL under "
    "spark.rapids.sql.trace.path and aggregated offline by "
    "tools/profiler_report.py (reference NvtxWithMetrics + "
    "ProfilerOnExecutor). Off by default; the disabled path costs one "
    "branch per span.", commonly_used=True)

TRACE_PATH = conf_str(
    "spark.rapids.sql.trace.path", "/tmp/rapids_tpu_trace",
    "Directory receiving per-query trace artifacts "
    "(query_<n>_trace.json / _events.jsonl / _metrics.json) when "
    "spark.rapids.sql.trace.enabled is set (reference "
    "spark.rapids.profile pathPrefix).")

TRACE_LEVEL = conf_str(
    "spark.rapids.sql.trace.level", "MODERATE",
    "Trace verbosity, reusing the metric levels: ESSENTIAL (exec spans + "
    "task rollups), MODERATE (+ semaphore/spill/retry/dispatch instants), "
    "DEBUG (+ host-pool queueing, shuffle serde, per-stage internals).")

TRACE_TASK_METRICS = conf_bool(
    "spark.rapids.sql.trace.taskMetrics", True,
    "Roll per-task accumulators (retry count/time, spill bytes/time, "
    "semaphore wait, max device bytes held — the GpuTaskMetrics analog) "
    "into the per-query event log at task completion.")

SANITIZER_ENABLED = conf_bool(
    "spark.rapids.debug.sanitizer.enabled", False,
    "Enable the runtime concurrency sanitizer (analysis/sanitizer.py): "
    "the engine's named lock sites record a process-wide lock-"
    "acquisition-order graph, report cycles (potential ABBA deadlocks) "
    "the first time both orders are merely observed, flag locks held "
    "past the holdWarnMs threshold (blocking work inside a critical "
    "section — the runtime twin of tpulint TPU-L001), and flag "
    "Condition waits made while other locks are held. Findings rank in "
    "sanitizer.report() and emit sanitizerFinding trace instants via "
    "sanitizer.dump(). Debug-only: enabled runs capture a stack per "
    "acquire; disabled, every lock operation costs one global read "
    "(gated <2% by tools/sanitizer_smoke.py).")

SANITIZER_HOLD_WARN_MS = conf_float(
    "spark.rapids.debug.sanitizer.holdWarnMs", 50.0,
    "Hold-duration threshold (milliseconds) above which the sanitizer "
    "reports a held-lock-blocking finding with the acquire-site stack.")

SANITIZER_STACK_DEPTH = conf_int(
    "spark.rapids.debug.sanitizer.stackDepth", 8,
    "Innermost stack frames captured per lock acquisition while the "
    "sanitizer is enabled (deeper = better reports, slower acquires).")

PLAN_VERIFY_ENABLED = conf_bool(
    "spark.rapids.debug.planVerify.enabled", False,
    "Run the plan-invariant verifier (analysis/plan_verify.py) on every "
    "converted exec tree: schema consistency across exec boundaries, "
    "fusion-group legality, and pipeline-boundary sanity. Violations "
    "raise PlanVerifyError before execution starts. Always exercised in "
    "CI against the golden dispatch budgets regardless of this conf.")

OBS_ENABLED = conf_bool(
    "spark.rapids.obs.enabled", True,
    "Publish live metrics into the process-wide observability registry "
    "(runtime/obs): task accumulators fold in once per task completion, "
    "per-exec rollups once per query — never per batch. Disabled, every "
    "hook costs one global read (same budget as trace.py). The registry "
    "feeds the /metrics endpoint and the query history store.")

OBS_PORT = conf_int(
    "spark.rapids.obs.port", 0,
    "When > 0, serve a background HTTP endpoint on this port: /metrics "
    "(Prometheus text format from the live registry) and /healthz (JSON: "
    "device liveness via a trivial dispatch probe, semaphore saturation, "
    "spill pressure, last-query status; HTTP 200 ok / 503 degraded). "
    "0 disables the endpoint (the reference surfaces GpuMetrics through "
    "the Spark UI; a standalone engine scrapes).", commonly_used=True)

OBS_HISTORY_DIR = conf_str(
    "spark.rapids.obs.historyDir", "",
    "When set, append one JSON record per query to "
    "<dir>/query_history.jsonl: plan digest, per-exec metric rollups, "
    "fusion groups, fallback reasons, config delta, wall time, status "
    "(ok/failed + exception class), trace artifact paths. Rendered by "
    "tools/history_server.py (query list -> annotated plan -> "
    "run-over-run diff by plan digest); tools/nds_probe.py appends its "
    "scorecards here too.", commonly_used=True)

OBS_PROBE_TIMEOUT_MS = conf_int(
    "spark.rapids.obs.probeTimeoutMs", 2000,
    "Timeout for the /healthz device dispatch probe; a probe that "
    "exceeds it reports the device as blocked and flips the endpoint "
    "to degraded (503).")

OBS_FLIGHT_ENABLED = conf_bool(
    "spark.rapids.obs.flight.enabled", True,
    "Run the always-on flight recorder (runtime/obs/flight.py): a "
    "bounded per-thread ring of the most recent span/instant events, "
    "fed from the SAME instrumentation points structured tracing uses, "
    "auto-dumped as a Chrome-trace file when a query fails or degrades, "
    "the dispatch watchdog reports a wedge, the circuit breaker opens, "
    "or a query breaches its SLO — so failures get a timeline "
    "retroactively even with spark.rapids.sql.trace.enabled off. The "
    "hot path takes no locks (one tuple store per recorded event; "
    "DEBUG-level events are filtered); overhead is gated <2% by "
    "tools/flight_smoke.py.", commonly_used=True)

OBS_FLIGHT_PATH = conf_str(
    "spark.rapids.obs.flight.path", "/tmp/rapids_tpu_flight",
    "Directory receiving flight-recorder dumps "
    "(flight_<seq>_<reason>.json, Chrome-trace/Perfetto loadable).")

OBS_FLIGHT_EVENTS = conf_int(
    "spark.rapids.obs.flight.events", 2048,
    "Per-thread ring capacity of the flight recorder: how many recent "
    "span/instant events each thread retains for a retroactive dump. "
    "Older events are overwritten; the dump reports how many were "
    "dropped.")

OBS_FLIGHT_MIN_INTERVAL_S = conf_float(
    "spark.rapids.obs.flight.minIntervalSeconds", 5.0,
    "Rate limit between flight-recorder dumps: a failure storm dumps at "
    "most one timeline per interval instead of one per failing query. "
    "0 disables the limit (tests).")

OBS_FLIGHT_MAX_DUMPS = conf_int(
    "spark.rapids.obs.flight.maxDumps", 50,
    "Bounded retention: only the newest N flight dump files are kept in "
    "spark.rapids.obs.flight.path; older ones are pruned after each "
    "dump.")

OBS_REQTRACE_ENABLED = conf_bool(
    "spark.rapids.obs.reqtrace.enabled", False,
    "Run the per-request tail-sampled tracer "
    "(runtime/obs/reqtrace.py): every serving request buffers its span "
    "tree (serving spans + the engine exec spans of its query, joined "
    "by query id) in a bounded per-request ring fed from the SAME "
    "instrumentation points the flight recorder uses. At request end a "
    "sampling verdict either drops the buffer or exports a "
    "self-contained per-request timeline (Chrome-trace + an OTLP-JSON-"
    "shaped file) under reqtrace.path. Errors, cancellations, "
    "deadlines, SLO breaches and runs slower than the digest baseline "
    "are ALWAYS kept; ordinary requests and hot cache hits sample at "
    "reqtrace.sampleRatio. The disabled path is one module-global "
    "read; armed overhead is gated <2% by tools/reqtrace_smoke.py.",
    commonly_used=True)

OBS_REQTRACE_PATH = conf_str(
    "spark.rapids.obs.reqtrace.path", "/tmp/rapids_tpu_reqtrace",
    "Directory receiving per-request timeline exports "
    "(req_<seq>_<verdict>_<trace_id>.json Chrome-trace files plus the "
    "matching req_<seq>_<verdict>_<trace_id>.otlp.json OTLP-JSON-"
    "shaped file).")

OBS_REQTRACE_EVENTS = conf_int(
    "spark.rapids.obs.reqtrace.events", 4096,
    "Per-request ring capacity: how many span/instant events one "
    "request retains for its timeline. Older events are overwritten; "
    "the export reports how many were dropped.")

OBS_REQTRACE_SAMPLE_RATIO = conf_float(
    "spark.rapids.obs.reqtrace.sampleRatio", 0.01,
    "Probability that an ordinary successful request (including a hot "
    "result-cache hit) exports its timeline. Error/cancelled/deadline/"
    "SLO-breach/slower-than-baseline requests always export regardless "
    "of this ratio. 0 keeps only the always-keep classes.")

OBS_REQTRACE_MIN_INTERVAL_S = conf_float(
    "spark.rapids.obs.reqtrace.minIntervalSeconds", 1.0,
    "Rate limit between per-request timeline exports: a failure storm "
    "exports at most one timeline per interval (always-keep verdicts "
    "and sampled keeps alike). 0 disables the limit (tests).")

OBS_REQTRACE_MAX_DUMPS = conf_int(
    "spark.rapids.obs.reqtrace.maxDumps", 100,
    "Bounded retention: only the newest N per-request exports (Chrome "
    "+ OTLP pairs) are kept in spark.rapids.obs.reqtrace.path; older "
    "ones are pruned after each export.")

OBS_REPLICA_ID = conf_str(
    "spark.rapids.obs.replicaId", "",
    "Stable identity of THIS serving replica in a fleet sharing one "
    "spark.rapids.obs.historyDir. Stamped into every query history "
    "record, response doc and per-request timeline so "
    "tools/fleet_report.py can split a digest's latency/compile/cache "
    "profile per replica. Empty (the default) derives pid-<os pid>, "
    "which is unique per process but not stable across restarts.",
    commonly_used=True)

OBS_SLO_ENABLED = conf_bool(
    "spark.rapids.obs.slo.enabled", True,
    "Check every successful top-level query against its SLO "
    "(runtime/obs/slo.py): a per-plan-digest latency baseline built "
    "from the query history (mean of the last slo.baselineWindow ok "
    "runs, armed after slo.minRuns samples) times slo.baselineFactor, "
    "plus the absolute bound slo.latencySeconds. A breach emits a "
    "slowQuery instant, bumps rapids_slo_breaches_total, surfaces on "
    "/healthz with its attribution summary, and triggers a "
    "flight-recorder dump. Baselines seed from "
    "spark.rapids.obs.historyDir when set, so they survive restarts.")

OBS_SLO_FACTOR = conf_float(
    "spark.rapids.obs.slo.baselineFactor", 3.0,
    "A query breaches its SLO when its wall time exceeds the per-digest "
    "baseline mean times this factor.")

OBS_SLO_MIN_RUNS = conf_int(
    "spark.rapids.obs.slo.minRuns", 5,
    "Successful runs of a plan digest required before its baseline arms "
    "(fewer samples would flag ordinary warm-up variance).")

OBS_SLO_ABS_SECONDS = conf_float(
    "spark.rapids.obs.slo.latencySeconds", 0.0,
    "Absolute per-query latency SLO in seconds, checked regardless of "
    "baseline state. 0 disables the absolute bound (the baseline check "
    "still applies).")

OBS_SLO_WINDOW = conf_int(
    "spark.rapids.obs.slo.baselineWindow", 32,
    "Successful runs per plan digest retained for the baseline mean "
    "(a bounded sliding window, newest runs win).")

OBS_CORS_ORIGIN = conf_str(
    "spark.rapids.obs.corsOrigin", "",
    "Value for the Access-Control-Allow-Origin header on obs endpoint "
    "responses. Empty (the default) sends no CORS header, so browser "
    "pages from other origins cannot read /queries (which carries "
    "in-flight SQL text) or /healthz. Set it to the history server's "
    "origin (or '*' on a trusted host) to enable the "
    "tools/history_server.py --engine live-console page, which polls "
    "the endpoint cross-origin from the browser.")

OBS_PROGRESS_ENABLED = conf_bool(
    "spark.rapids.obs.progress.enabled", True,
    "Register every top-level action in the live query registry "
    "(runtime/obs/live.py): query id, plan digest, state machine "
    "(queued -> planning -> executing -> finishing -> ok/failed/"
    "degraded), and per-exec batches/rows progress with %-complete and "
    "ETA derived from the plan's scan-size estimates. Surfaced by "
    "session.running_queries(), the /queries JSON endpoint, and the "
    "/console live page. Progress reads are pull-based snapshots of "
    "the metrics the execs already keep (no per-batch publish) and "
    "never resolve lazy device counts, so a scrape adds no device "
    "syncs to a running query.")

OBS_SAMPLER_ENABLED = conf_bool(
    "spark.rapids.obs.sampler.enabled", True,
    "Run the always-on resource time-series sampler "
    "(runtime/obs/sampler.py): a service thread samples the SERIES "
    "roster (device/host bytes held, semaphore permits and waiters, "
    "host-pool queue depths, pipeline stall state, breaker state, "
    "process RSS, running queries) into bounded per-series rings "
    "every sampler.intervalMs. Exported as rapids_sampler_* gauges on "
    "/metrics, rendered as sparklines on /console, and embedded as "
    "Chrome counter tracks in every flight-recorder dump so a "
    "post-mortem carries the resource context leading up to the "
    "trigger.")

OBS_SAMPLER_INTERVAL_MS = conf_int(
    "spark.rapids.obs.sampler.intervalMs", 200,
    "Resource-sampler period in milliseconds. Each tick reads ~10 "
    "in-process gauges (no locks shared with query hot paths, no "
    "device syncs); the ring covers ringSize*intervalMs of history.")

OBS_SAMPLER_RING = conf_int(
    "spark.rapids.obs.sampler.ringSize", 512,
    "Samples retained per sampler series (a bounded ring, newest "
    "kept — the flight-recorder ring discipline). At the default "
    "200ms interval, 512 samples cover the last ~102 seconds.")

OBS_AUDIT_ENABLED = conf_bool(
    "spark.rapids.obs.audit.enabled", False,
    "Arm the kernel cost auditor (analysis/kernel_audit.py): every "
    "computation resolved through the compile-cache choke point is "
    "audited AT TRACE TIME for XLA flops, bytes accessed, input/output "
    "plane bytes and shape-bucket padding exposure, deduped per "
    "(entry, shape signature) so steady-state dispatches add zero "
    "work. Joined with dispatch tallies and attribution device "
    "seconds into per-query roofline attribution: achieved GB/s and "
    "FLOP/s, % of the configured rooflines, memory/compute/"
    "dispatch-overhead boundedness — surfaced in "
    "explain(mode='analyze'), history records, rapids_roofline_* "
    "gauges, /console, and tools/roofline_report.py. Off by default: "
    "audited runs pay one extra lower+compile per traced shape at "
    "resolution time (CI's audit_smoke and the golden cost-signature "
    "generator run with it on).")

OBS_AUDIT_PEAK_GBPS = conf_float(
    "spark.rapids.obs.audit.peakGbps", 819.0,
    "Memory-bandwidth roofline in GB/s for roofline attribution "
    "(819 = one v5e chip's HBM bandwidth). Achieved GB/s is audited "
    "bytes over measured device seconds; roofline_pct_bw is its share "
    "of this peak.")

OBS_AUDIT_PEAK_GFLOPS = conf_float(
    "spark.rapids.obs.audit.peakGflops", 197000.0,
    "Compute roofline in GFLOP/s for roofline attribution (197000 = "
    "one v5e chip's bf16 peak). Drives roofline_pct_flops and the "
    "memory-vs-compute boundedness verdict.")

OBS_AUDIT_OVERHEAD_FACTOR = conf_float(
    "spark.rapids.obs.audit.overheadBoundFactor", 10.0,
    "A kernel group whose measured device seconds exceed this multiple "
    "of its best-case roofline time (max of bytes/peakGbps and "
    "flops/peakGflops) classifies as dispatch_overhead-bound: the "
    "device is waiting on per-dispatch latency, not moving data or "
    "computing.")

LORE_DUMP_DIR = conf_str(
    "spark.rapids.sql.lore.dumpPath", "",
    "When set, every exec's input batches dump as parquet under "
    "<dir>/<loreId>/ for local operator replay "
    "(reference LORE, lore/GpuLore.scala).")

SORT_OOC_BYTES = conf_int(
    "spark.rapids.sql.sort.outOfCoreBytes", 2 << 30,
    "Sorts over inputs larger than this run out-of-core: the device "
    "computes only the key permutation while row data stages through host "
    "memory (reference GpuSortExec out-of-core merge path).")

JOIN_SUBPARTITION_ROWS = conf_int(
    "spark.rapids.sql.join.subPartitionRows", 8 << 20,
    "Build sides larger than this many rows hash-split into buckets joined "
    "pairwise (skew/no-fit handling; reference GpuSubPartitionHashJoin).")

BROADCAST_JOIN_ROW_THRESHOLD = conf_int(
    "spark.rapids.sql.join.broadcastRowThreshold", 1 << 22,
    "Estimated build-side row count below which joins broadcast instead of "
    "shuffling both sides (reference: Spark autoBroadcastJoinThreshold).")

DEVICE_MEMORY_BUDGET = conf_int(
    "spark.rapids.memory.tpu.budgetBytes", 12 << 30,
    "Cooperative HBM budget in bytes for registered (spillable) batches; "
    "reservations beyond it drain the spill stores "
    "(reference rmm pool size; XLA owns the physical allocator).")

HOST_SPILL_LIMIT = conf_int(
    "spark.rapids.memory.host.spillStorageSize", 4 << 30,
    "Bytes of host memory for spilled device data before overflowing to disk "
    "(reference SpillFramework host store limit).")

SPILL_DIR = conf_str(
    "spark.rapids.memory.spillDir", "/tmp/rapids_tpu_spill",
    "Directory for disk spill files (reference RapidsDiskBlockManager).")

RETRY_OOM_INJECT = conf_str(
    "spark.rapids.sql.test.injectRetryOOM", "",
    "Fault-injection grammar 'count[,skip]' forcing retry-OOMs for tests "
    "(reference RapidsConf.scala:1627,2753).", internal=True)

FAULTS_SPEC = conf_str(
    "spark.rapids.debug.faults", "",
    "General fault-injection schedule (runtime/faults.py): "
    "'site:kind[:count[,skip]]' entries joined by ';', where site is a "
    "registered fault site (scan.decode, shuffle.read, shuffle.write, "
    "spill.disk, device.dispatch, pipeline.producer, exchange.fetch, "
    "retry.oom, query.cancel, semaphore.wait — tpulint TPU-L008 keeps "
    "the roster honest) and kind is ioerror, corrupt (data sites only), "
    "delay, wedge, oom, or cancel (fire the current query's cancel "
    "token at the site — chaos storms use it to deliver cancels at "
    "named checkpoints). Every "
    "fired fault emits a faultInjected trace instant and counts into "
    "rapids_faults_injected_total and /healthz. Empty disables injection "
    "(one global read per site pass — gated <2% by tools/chaos_smoke.py). "
    "Generalizes injectRetryOOM, which remains the retry.oom facade.")

FAULTS_DELAY_MS = conf_float(
    "spark.rapids.debug.faults.delayMs", 50.0,
    "Sleep injected by a 'delay'-kind fault, in milliseconds.")

FAULTS_WEDGE_S = conf_float(
    "spark.rapids.debug.faults.wedgeSeconds", 0.25,
    "Sleep injected by a 'wedge'-kind fault, in seconds. To exercise "
    "the watchdog detection path end-to-end, set this ABOVE "
    "spark.rapids.watchdog.dispatchTimeoutSeconds (tools/chaos_smoke.py "
    "does) — a wedge shorter than the timeout completes unnoticed.")

WATCHDOG_ENABLED = conf_bool(
    "spark.rapids.watchdog.enabled", False,
    "Run the device dispatch watchdog (runtime/watchdog.py): a heartbeat "
    "service thread detects fused dispatches exceeding "
    "dispatchTimeoutSeconds, reports each wedge once (log + "
    "watchdogDispatchTimeout trace instant + obs counter) and records a "
    "circuit-breaker failure so later queries degrade to CPU instead of "
    "joining the wedge (a wedged libtpu holds the GIL — the call itself "
    "cannot be interrupted). Disabled, dispatches run unwrapped at zero "
    "added cost.")

WATCHDOG_DISPATCH_TIMEOUT_S = conf_float(
    "spark.rapids.watchdog.dispatchTimeoutSeconds", 60.0,
    "Deadline for one fused device dispatch before the watchdog reports "
    "it wedged and records a breaker failure.")

WATCHDOG_BREAKER_THRESHOLD = conf_int(
    "spark.rapids.watchdog.breakerFailureThreshold", 3,
    "Consecutive device failures (failed/degraded queries, dispatch "
    "timeouts) that open the device circuit breaker. While open — and "
    "CPU fallback is enabled — queries skip the device entirely and run "
    "degraded on the CPU backend.")

WATCHDOG_BREAKER_BACKOFF_S = conf_float(
    "spark.rapids.watchdog.breakerBaseBackoffSeconds", 1.0,
    "Initial open-state backoff before the breaker half-opens and lets "
    "one probe query try the device again; doubles on each failed probe "
    "up to breakerMaxBackoffSeconds, resets on success.")

WATCHDOG_BREAKER_MAX_BACKOFF_S = conf_float(
    "spark.rapids.watchdog.breakerMaxBackoffSeconds", 60.0,
    "Cap on the breaker's exponential open-state backoff.")

FALLBACK_CPU_ENABLED = conf_bool(
    "spark.rapids.fallback.cpu.enabled", False,
    "Graceful degradation: when a top-level query fails with an engine/"
    "device error (exhausted OOM retries, corrupted shuffle data, a "
    "device error, an injected fault — NOT user-semantic errors like "
    "ANSI overflow, which surface unchanged), re-execute it on the CPU "
    "backend and record it as status=degraded (with the triggering "
    "error class) in query history, /metrics and /healthz instead of "
    "failed. Also consults the device circuit breaker: while the "
    "breaker is open, queries skip the device entirely. Off by default: "
    "batch/test workloads want failures loud; serving deployments flip "
    "this on (the reference's per-operator CPU fallback generalized to "
    "the query failure domain).", commonly_used=True)

RETRY_BACKOFF_BASE_MS = conf_float(
    "spark.rapids.retry.backoffBaseMs", 10.0,
    "Base of the bounded exponential backoff between OOM retry attempts "
    "(after the spill-store drain): attempt n sleeps "
    "base*2^(n-1) ms, jittered to 50-100%, capped at backoffMaxMs — so "
    "concurrent tasks that OOMed together do not re-dispatch together "
    "(thundering herd). Folded into the retryBlockTime accumulator. "
    "0 disables the backoff (drain-then-immediate-retry).")

RETRY_BACKOFF_MAX_MS = conf_float(
    "spark.rapids.retry.backoffMaxMs", 500.0,
    "Cap on the per-attempt OOM retry backoff.")

SHUFFLE_VERIFY_CHECKSUMS = conf_bool(
    "spark.rapids.shuffle.verifyChecksums", True,
    "Verify the CRC32 wire checksum on every serialized shuffle blob at "
    "read time (the serde header carries it; the frame body also keeps "
    "its xxhash64). A corrupt blob triggers ONE transparent re-fetch "
    "from the shuffle store (counted in shuffleCorruptionRetries) "
    "before the error surfaces — a transient disk bit-flip recovers, a "
    "persistent corruption fails the query (and degrades to CPU when "
    "spark.rapids.fallback.cpu.enabled).")

SHUFFLE_MODE = conf_str(
    "spark.rapids.shuffle.mode", "MULTITHREADED",
    "MULTITHREADED: in-process exchange by zero-copy selection-mask "
    "slicing on device (no files or serialization involved); "
    "ICI: device-resident exchange via XLA all-to-all collectives over the "
    "mesh; SERIALIZED: partitions serialize through the kudo-analog wire "
    "format into a spillable host store (parallel writers, compression, "
    "disk overflow — the cross-host-capable path) "
    "(reference RapidsConf.scala:1767 UCX|CACHE_ONLY|MULTITHREADED).")

SHUFFLE_PARTITIONING = conf_str(
    "spark.rapids.shuffle.partitioning", "compact",
    "Device repartition strategy for hash/round-robin/range exchanges. "
    "'compact': ONE fused counting-sort kernel per input batch permutes "
    "rows so each target partition is contiguous, a single host fetch of "
    "the n_out+1 offsets vector sizes the outputs, and downstream "
    "operators see right-sized sub-batches (the analog of cudf's "
    "hash-partition kernel returning a table plus offsets). 'masked': "
    "legacy zero-copy selection-mask slicing emitting n_out full-capacity "
    "sub-batches per input batch (escape hatch; costs n_out deferred "
    "count syncs and n_out*capacity downstream work per batch).")

SHUFFLE_WRITER_THREADS = conf_int(
    "spark.rapids.shuffle.multiThreaded.writer.threads", 8,
    "Threads in the executor-wide shuffle writer pool "
    "(reference RapidsShuffleInternalManagerBase.scala:119-218).")

SHUFFLE_READER_THREADS = conf_int(
    "spark.rapids.shuffle.multiThreaded.reader.threads", 8,
    "Threads in the executor-wide shuffle reader pool.")

SHUFFLE_COMPRESSION = conf_str(
    "spark.rapids.shuffle.compression.codec", "auto",
    "Codec for serialized shuffle tables: auto, none, zstd, zlib "
    "(reference TableCompressionCodec; nvcomp lz4 has no TPU-side analog "
    "in this environment, zstd plays that role). 'auto' resolves to zstd "
    "when the zstandard package is importable and zlib (stdlib, always "
    "present) otherwise; naming zstd explicitly without the package "
    "fails fast.")

SHUFFLE_HOST_BUDGET = conf_int(
    "spark.rapids.shuffle.hostSpillBudget", 256 << 20,
    "Host bytes the SERIALIZED shuffle store may hold resident before "
    "partitions flush to disk spill files "
    "(reference ShuffleBufferCatalog spillable shuffle data).")

ADAPTIVE_ENABLED = conf_bool(
    "spark.rapids.sql.adaptive.enabled", True,
    "Adaptive query execution (the AQE role: reference "
    "GpuCustomShuffleReaderExec / per-stage re-planning): pick the join "
    "strategy at RUNTIME from the measured build side, convert a shuffled "
    "hash join to broadcast when the materialized build side lands under "
    "the byte threshold, split skewed post-shuffle partitions, reuse "
    "materialized broadcast builds across queries, and let the measured "
    "cost pass (plan/cost.py) replan from audited history. Master switch "
    "for every spark.rapids.sql.adaptive.* feature below.")

ADAPTIVE_BROADCAST_BYTES = conf_int(
    "spark.rapids.sql.adaptive.broadcastThresholdBytes", 64 << 20,
    "Runtime shuffle-hash -> broadcast conversion threshold: the build "
    "side of a shuffled hash join materializes its exchange FIRST, and "
    "when its MEASURED device bytes (actual row counts from the compact "
    "offsets fetch - no extra sync) land at or under this many bytes, the "
    "probe-side exchange is never dispatched - the join replans as a "
    "broadcast hash join over the raw probe partitions (reference "
    "spark.sql.adaptive.autoBroadcastJoinThreshold + "
    "GpuBroadcastJoinMeta). <= 0 disables the conversion.")

ADAPTIVE_SKEW_FACTOR = conf_float(
    "spark.rapids.sql.adaptive.skewFactor", 4.0,
    "Skewed-partition split: a post-shuffle partition whose row count "
    "(free host ints from the compact offsets fetch) exceeds this factor "
    "times the median partition is split into median-sized sub-batches "
    "that rejoin under the existing batch semantics, bounding per-"
    "dispatch capacity (reference spark.sql.adaptive.skewJoin."
    "skewedPartitionFactor / GpuSkewJoin). <= 0 disables splitting.")

ADAPTIVE_BUILD_REUSE = conf_bool(
    "spark.rapids.sql.adaptive.buildReuse.enabled", True,
    "Cache materialized broadcast build sides ACROSS queries, keyed by "
    "build-plan digest + table registration version next to the compile "
    "cache, so a repeated join skips the build entirely (reference "
    "ReusedExchangeExec across AQE stages). Entries invalidate when any "
    "temp view is re-registered and are capped at 8.")

ADAPTIVE_MEASURED_COST = conf_bool(
    "spark.rapids.sql.adaptive.measuredCost.enabled", True,
    "Measured cost pass: before converting a plan, consult the query "
    "history store's roofline verdicts for the SAME plan digest and pick "
    "exchange partition counts, aggregate fusion boundaries, and the "
    "coalesceTinyRows threshold from what was MEASURED instead of static "
    "defaults (needs spark.rapids.obs.historyDir; a digest with no "
    "audited history keeps the static plan).")

PALLAS_ENABLED = conf_bool(
    "spark.rapids.sql.pallas.enabled", True,
    "Use hand-tiled Pallas TPU kernels for eligible inner loops "
    "(murmur3 hash, string case map); the XLA twins run otherwise. "
    "Process-wide: the first session's value wins (fused kernels are "
    "cached process-globally).", startup_only=True)

MULTIFILE_READER_TYPE = conf_str(
    "spark.rapids.sql.format.parquet.reader.type", "AUTO",
    "PERFILE, COALESCING, MULTITHREADED, or AUTO "
    "(reference RapidsConf.scala:317).")

MULTIFILE_READER_THREADS = conf_int(
    "spark.rapids.sql.multiThreadedRead.numThreads", 8,
    "Host threads for multi-file read scheduling "
    "(reference GpuMultiFileReader).")

DEVICE_DECODE_ENABLED = conf_bool(
    "spark.rapids.sql.decode.device.enabled", True,
    "Decode Parquet column chunks ON DEVICE: the scan uploads raw "
    "dictionary/RLE/bit-packed/delta chunk bytes and Pallas/XLA kernels "
    "expand them inside the fused stage body (the cuDF GPU-reader "
    "analog; io/encoded.py + ops/pallas_decode.py). Columns with "
    "unsupported types/encodings/codecs fall back per column to the "
    "host pyarrow path, with the reason surfaced in explain/history. "
    "Off = the classic host-decode scan.")

DEVICE_DECODE_DELTA = conf_bool(
    "spark.rapids.sql.decode.device.delta.enabled", True,
    "Allow DELTA_BINARY_PACKED columns on the device-decode path "
    "(decoded as a cumulative sum with per-page restarts). Off falls "
    "such columns back to host decode.")

DEVICE_DECODE_MAX_BITS = conf_int(
    "spark.rapids.sql.decode.device.maxBits", 32,
    "Widest dictionary/delta packed bit width decoded on device (the "
    "bit-slice kernel extracts from 32-bit word pairs). Columns packed "
    "wider fall back per column to host decode; values above 32 are "
    "capped at 32.")

ASYNC_WRITE_MAX_INFLIGHT = conf_int(
    "spark.rapids.sql.asyncWrite.maxInFlightHostMemoryBytes", 2 << 30,
    "Throttle for async output writes "
    "(reference io/async/TrafficController.scala).")

ASYNC_WRITE_STALL_WARN_S = conf_int(
    "spark.rapids.sql.asyncWrite.stallWarnSeconds", 60,
    "Seconds a producer may block in TrafficController.acquire before a "
    "stall diagnostic fires (one log warning + asyncWriteStalled trace "
    "instant + rapids_async_write_stalls_total obs counter). Admission "
    "semantics are unchanged — the producer keeps waiting. 0 disables "
    "the diagnostic.")

IMPROVED_FLOAT_OPS = conf_bool(
    "spark.rapids.sql.improvedFloatOps.enabled", True,
    "Allow float aggregation orderings that may differ from CPU Spark in "
    "ULP-level ways (reference incompat float handling).")

ANSI_ENABLED = conf_bool(
    "spark.sql.ansi.enabled", False,
    "ANSI mode: arithmetic overflow and invalid casts raise instead of "
    "returning null (Spark conf honored by the expression compiler).")

CASE_SENSITIVE = conf_bool(
    "spark.sql.caseSensitive", False,
    "Column resolution case sensitivity (Spark conf).")

SESSION_TIMEZONE = conf_str(
    "spark.sql.session.timeZone", "UTC",
    "Session timezone. This engine evaluates timestamps in UTC only: any "
    "other value makes timezone-sensitive expressions raise at planning "
    "instead of silently returning UTC answers (reference: GpuOverrides "
    "tags non-UTC ops as unsupported).")

TEST_MODE = conf_bool(
    "spark.rapids.sql.test.enabled", False,
    "Assert that everything that should be on TPU is on TPU "
    "(reference GpuTransitionOverrides assertIsOnTheGpu).", internal=True)

ALLOW_NON_TPU = conf_str(
    "spark.rapids.sql.test.allowedNonTpu", "",
    "Comma-separated exec names allowed to fall back in test mode.",
    internal=True)

CPU_RANGE_PARTITION_SAMPLE = conf_int(
    "spark.rapids.sql.rangePartitioning.sampleSizePerPartition", 1024,
    "Rows sampled per partition to compute range bounds "
    "(reference GpuRangePartitioner/SamplingUtils).")

AGG_FORCE_SINGLE_PASS = conf_bool(
    "spark.rapids.sql.agg.forceSinglePassPartialSort", False,
    "Concat all input batches and run the partial aggregation as ONE update "
    "pass instead of per-batch update + merge (testing knob, reference "
    "forceSinglePassPartialSortAgg).", internal=True)

MAX_RECORDS_PER_FILE = conf_int(
    "spark.sql.files.maxRecordsPerFile", 0,
    "Maximum rows per output file (0 = unlimited). Writers split output "
    "batches into numbered part files past the limit (reference "
    "GpuFileFormatDataWriter maxRecordsPerFile).")

PY_WORKER_POOL_ENABLED = conf_bool(
    "spark.rapids.sql.python.workerPool.enabled", True,
    "Evaluate large row-UDF batches on a persistent multiprocessing "
    "worker pool (reference PySpark daemon analog). Unpicklable UDFs "
    "and small batches stay in-process.")

PY_WORKER_POOL_PARALLELISM = conf_int(
    "spark.rapids.sql.python.workerPool.parallelism", 0,
    "Worker processes for the python UDF pool (0 = cpu count, cap 8).")

UDF_COMPILER_ENABLED = conf_bool(
    "spark.rapids.sql.udfCompiler.enabled", False,
    "Translate simple Python UDF bytecode (arithmetic, comparisons, "
    "conditionals, math builtins) into fused device expressions "
    "(reference udf-compiler). Untranslatable UDFs stay on the row tier. "
    "Semantics note (same tradeoff as the reference compiler): compiled "
    "UDFs null-propagate instead of calling fn(None), and arithmetic "
    "errors yield null instead of raising (non-ANSI Spark semantics) — "
    "a row-tier UDF that RAISES on bad input behaves differently. "
    "Off by default for that reason (matching the reference).")

SKIP_AGG_PASS_RATIO = conf_float(
    "spark.rapids.sql.agg.skipAggPassReductionRatio", 1.0,
    "Skip later agg passes when a pass reduces rows by less than this ratio "
    "(reference skipAggPassReductionRatio).")

METRICS_LEVEL = conf_str(
    "spark.rapids.sql.metrics.level", "MODERATE",
    "ESSENTIAL, MODERATE, or DEBUG metric collection "
    "(reference spark.rapids.sql.metrics.level).")

INCOMPAT_ENABLED = conf_bool(
    "spark.rapids.sql.incompatibleOps.enabled", True,
    "Enable operators whose results can differ from CPU Spark in documented "
    "corner cases (reference incompatOps).")

PIPELINE_ENABLED = conf_bool(
    "spark.rapids.sql.pipeline.enabled", True,
    "Overlap host-side batch production (pyarrow decode, pad/H2D upload, "
    "shuffle deserialization) with device compute: a planner pass inserts "
    "bounded producer/consumer pipeline boundaries at scan->compute edges, "
    "running the upstream generator on the shared host pool so batch i+1 "
    "is decoded/uploaded while the device computes batch i (reference "
    "MultiFileReaderThreadPool / ThrottlingExecutor overlap). Also gates "
    "the deferred per-batch scalar fetches (shuffle offsets, LIMIT carry) "
    "and the async throttled serialized-shuffle writer. A stage whose "
    "pipeline setup fails falls back to the synchronous path.",
    commonly_used=True)

PIPELINE_DEPTH = conf_int(
    "spark.rapids.sql.pipeline.depth", 2,
    "Bounded lookahead of each pipeline boundary: how many produced "
    "batches may sit decoded/uploaded ahead of the consumer. 0 disables "
    "pipelining (identical to pipeline.enabled=false).")

COMPILE_CACHE_DIR = conf_str(
    "spark.rapids.compile.cacheDir", "",
    "Directory of jax's persistent compilation cache (entry thresholds "
    "zeroed): compiled XLA executables are reused ACROSS processes, so "
    "a restarted engine pays trace + deserialize instead of a full "
    "backend compile on its first run of a known computation. "
    "JAX_COMPILATION_CACHE_DIR, when the environment sets it, wins over "
    "this conf (logged once). Empty means the default: a fixed "
    ".jax_cache directory inside the checkout on an accelerator, no "
    "persistent layer on the CPU simulator. Process-global — the first "
    "session to place it wins (jax config is global); "
    "tools/compile_smoke.py CI-gates that the cross-process hits "
    "actually happen. The in-process warm-trace cache in "
    "runtime/compile_cache.py is always on.", commonly_used=True)

COMPILE_WARMUP_ENABLED = conf_bool(
    "spark.rapids.compile.warmup.enabled", False,
    "AOT warmup (runtime/warmup.py): at session start, replay the most "
    "recurrent successful queries recorded in spark.rapids.obs."
    "historyDir (their SQL text rides in the history records) on a "
    "background service thread as each referenced table is registered, "
    "pre-tracing and pre-compiling the hot exec set before the first "
    "user query needs it. Replays run on a shadow session: they touch "
    "no user-visible session state, produce no history records, and "
    "never fail the session. Progress is surfaced on /healthz "
    "(warmup document) and as warmupReplay trace instants.",
    commonly_used=True)

COMPILE_WARMUP_MAX_PLANS = conf_int(
    "spark.rapids.compile.warmup.maxPlans", 8,
    "Upper bound on distinct recurring plans the AOT warmup replays "
    "(ranked by recurrence count, most-recurrent first).")

COMPILE_WARMUP_MIN_RUNS = conf_int(
    "spark.rapids.compile.warmup.minRuns", 2,
    "Successful history runs of a plan digest required before warmup "
    "considers it recurring (1 replays everything ever run once).")

COMPILE_SHAPES_GROWTH = conf_float(
    "spark.rapids.compile.shapes.growthFactor", 2.0,
    "Geometric growth factor of the capacity padding buckets "
    "(runtime/shapes.py): every device batch capacity snaps to the "
    "smallest bucket >= its row count so XLA traces are shared across "
    "batches and queries. 2.0 (default) is next-power-of-two (up to 2x "
    "padding waste, fewest buckets/compiles); smaller factors (1.25, "
    "1.5) pad tighter at the cost of more distinct shapes to compile. "
    "Clamped to (1.06, 4.0].")

COMPILE_SHAPES_DTYPE_ALIGN = conf_bool(
    "spark.rapids.compile.shapes.dtypeAlign", True,
    "Round capacity buckets up to whole native TPU tiles for the "
    "plane's dtype width (8x128 elements for 4-byte lanes, 16x128 for "
    "2-byte, 32x128 for 1-byte) on bucket requests that carry an "
    "itemsize — today the string/byte planes; dtype-agnostic row "
    "buckets are unaligned. Power-of-two buckets are always aligned "
    "already; this keeps non-2.0 growth factors from paying a "
    "partial-tile relayout on byte-plane kernels.")

SHUFFLE_COALESCE_TINY_ROWS = conf_int(
    "spark.rapids.shuffle.coalesceTinyRows", 1024,
    "Post-shuffle tiny-partition coalescing: after a compact exchange, "
    "adjacent device sub-batches carrying fewer than this many rows "
    "each merge into one batch (bounded by 4x this target) before "
    "downstream dispatch — ragged post-shuffle slice sizes otherwise "
    "make nearly every batch shape a fresh trace AND a separate "
    "dispatch. The decision is free: the compact path's already-"
    "fetched offsets vector supplies exact host-side row counts. "
    "Merges count into the shuffleCoalescedBatches metric (visible in "
    "EXPLAIN ANALYZE). 0 disables coalescing.")

QUERY_TIMEOUT_S = conf_float(
    "spark.rapids.query.timeoutSeconds", 0.0,
    "Per-query deadline in seconds (0 disables). A watchdog-style "
    "sweeper over the live query registry (runtime/lifecycle.py) fires "
    "the query's cancel token with reason 'deadline' when the budget "
    "lapses; the query terminates at its next cooperative checkpoint "
    "with status=cancelled, and its wall-time attribution breakdown is "
    "recorded at death so the history/trace show WHERE the budget went. "
    "session.collect(plan, timeout_seconds=...) overrides per action.",
    commonly_used=True)

QUERY_MAX_CONCURRENT = conf_int(
    "spark.rapids.query.maxConcurrent", 0,
    "Admission control over top-level actions (0 = unlimited): at most "
    "this many queries execute concurrently; excess queries park in a "
    "bounded FIFO queue in the 'queued' live state. The complement of "
    "spark.rapids.sql.concurrentTpuTasks (which bounds TASKS inside "
    "admitted queries on the device semaphore) — the reference's "
    "GpuSemaphore model lifted to whole queries for the serving layer.",
    commonly_used=True)

QUERY_MAX_QUEUED = conf_int(
    "spark.rapids.query.maxQueued", 16,
    "Bound on the admission queue behind spark.rapids.query."
    "maxConcurrent: a query arriving past it is refused immediately "
    "with a typed QueryRejectedError (the HTTP 503/429 analog).")

QUERY_QUEUE_TIMEOUT_S = conf_float(
    "spark.rapids.query.queueTimeoutSeconds", 30.0,
    "Longest a query may wait in the admission queue before it is "
    "refused with QueryRejectedError (0 = wait forever). Queued "
    "queries remain cancellable while they wait.")

QUERY_DEVICE_BUDGET = conf_int(
    "spark.rapids.query.deviceBudgetBytes", 0,
    "Per-query cooperative device-bytes quota (0 disables): the spill "
    "framework keeps a per-query-id ledger of registered device "
    "batches, and a query exceeding its own quota spills ITS OWN "
    "batches (largest first) — or raises a retryable quota OOM that "
    "drains only its own handles — instead of evicting its neighbors' "
    "(the isolation primitive concurrent serving requires; composes "
    "with the process-wide spark.rapids.memory.tpu.budgetBytes).")

SERVING_ENABLED = conf_bool(
    "spark.rapids.serving.enabled", False,
    "Attach the query-serving layer to the obs HTTP endpoint: POST /sql "
    "accepts {sql, session?, conf?, timeout_seconds?} documents, runs "
    "each request as a top-level action through the admission gate / "
    "per-query device quotas / deadlines / cancellation, and returns the "
    "result as Arrow IPC bytes plus the wall-time attribution breakdown. "
    "Requires spark.rapids.obs.enabled with a bindable port. The long-"
    "lived-driver serving model of the reference (one plugin process, "
    "many sessions, concurrentGpuTasks bounding device work) lifted to "
    "an HTTP surface.", commonly_used=True)

SERVING_MAX_SESSIONS = conf_int(
    "spark.rapids.serving.maxSessions", 16,
    "Bound on named client sessions the server materializes (each is a "
    "conf-overlay session sharing the root session's temp views). A "
    "request naming a session past the bound is refused with HTTP 429 "
    "and a typed error doc rather than growing without limit.")

SERVING_MAX_INFLIGHT = conf_int(
    "spark.rapids.serving.maxInflight", 32,
    "Bound on HTTP /sql requests concurrently inside the server (admitted "
    "OR parked in the admission queue). A request arriving past it is "
    "refused immediately with HTTP 429 — the serving layer rejects "
    "rather than piles up, mirroring spark.rapids.query.maxQueued one "
    "level out.")

SERVING_RESULT_CACHE_ENABLED = conf_bool(
    "spark.rapids.serving.resultCache.enabled", True,
    "Plan-digest-keyed result cache for the serving layer: a hit returns "
    "the byte-identical Arrow IPC stream of a prior execution with the "
    "same (plan digest, table-version epoch, compile fingerprint) key. "
    "Invalidated by the table-version epoch the broadcast-reuse cache "
    "established (any create_or_replace_temp_view bumps it). Plans "
    "containing non-deterministic expressions (rand) bypass the cache; "
    "ANSI-divergent plans never share entries (the compile fingerprint "
    "is in the key).")

SERVING_RESULT_CACHE_MAX_BYTES = conf_int(
    "spark.rapids.serving.resultCache.maxBytes", 256 << 20,
    "Byte bound on cached result payloads (Arrow IPC stream bytes, "
    "exact len() accounting). Least-recently-used entries evict to "
    "admit new ones; every eviction is a counter.")

SERVING_RESULT_CACHE_MAX_ENTRIES = conf_int(
    "spark.rapids.serving.resultCache.maxEntries", 64,
    "Entry bound on the result cache (LRU eviction, counted), "
    "independent of the byte bound — many tiny results must not grow "
    "the key set without limit.")

SERVING_WARM_BOOT_ENABLED = conf_bool(
    "spark.rapids.serving.warmBoot.enabled", True,
    "Block server start on the compile-warmup replay when warmup is "
    "armed (spark.rapids.compile.warmup.enabled + obs.historyDir): a "
    "fresh replica pointed at a shared historyDir and persistent "
    "compile cache then serves its first hot-digest query with zero "
    "backend compiles — PR 10's session-construction warmup "
    "generalized to server boot, gated by rapids_xla_compiles_total.")

SERVING_WARM_BOOT_TIMEOUT_S = conf_float(
    "spark.rapids.serving.warmBoot.timeoutSeconds", 60.0,
    "Longest server start waits for the warmup replay before serving "
    "anyway (0 = don't wait). A timeout degrades to cold serving, it "
    "never fails the boot.")

SERVING_REQUEST_NICE = conf_int(
    "spark.rapids.serving.requestNice", 0,
    "OS niceness (0-19) applied to the handler thread for the duration "
    "of each request on this session — the serving QoS tier. A batch "
    "session sets this in its conf overlay to declare itself "
    "background: its host-side work (and on the CPU sim, its device "
    "compute, which runs on the dispatching thread) then yields to "
    "latency-tier requests under CPU contention. Best-effort: applied "
    "per-thread via setpriority, silently skipped where unsupported.")

STAGE_FUSION_ENABLED = conf_bool(
    "spark.rapids.sql.stageFusion.enabled", True,
    "Collapse maximal linear chains of narrow operators (project, filter, "
    "expand, limit, and the partial phase of hash aggregation) into ONE "
    "traced device computation per pipeline stage, so the host issues "
    "exactly one XLA dispatch per input batch per stage — the TPU-idiomatic "
    "analog of Spark's whole-stage codegen (which the reference GPU plugin "
    "deliberately lacks). A stage whose composed trace fails falls back to "
    "the unfused operator chain.", commonly_used=True)

MULTICHIP_ENABLED = conf_bool(
    "spark.rapids.sql.multichip.enabled", None,
    "Shard whole fused stages, and the scan-filter-partial-aggregate over "
    "a cached table, across the `part` axis of the device mesh and run "
    "them as ONE SPMD dispatch per batch-wave (exec/sharded.py), with the "
    "hash exchange executing as an in-program ICI all-to-all instead of a "
    "host-side round-trip — the TPU analog of the reference's UCX/RDMA "
    "shuffle manager. Unset (None), the engine takes the mesh it is "
    "given: on when the process has more than one accelerator device, off "
    "with one device and on the CPU simulator (parallel/mesh.py "
    "`multichip_on`); `true` forces it (one device gives the degenerate "
    "one-device mesh), `false` turns it off. Under a mesh `df.cache()` "
    "places an in-memory source's row ranges one per device. Stages the "
    "planner cannot shard (carries, LIMIT early-exit, flat string planes) "
    "fall back per-shard to the single-device path through the tagging "
    "tree. Compile-cache keys gain a mesh fingerprint while this is on, "
    "so sharded and single-device executables never collide.",
    commonly_used=True)

MULTICHIP_DEVICES = conf_int(
    "spark.rapids.sql.multichip.devices", 0,
    "Devices to place on the `part` axis of the execution mesh when "
    "multichip is enabled: 0 means all of jax.devices(), any other "
    "value is clamped to what the process actually has. 1 is a valid "
    "degenerate mesh — the full shard/wave machinery runs over a "
    "single device, which is how tier-1 exercises the sharded path "
    "without virtual devices.")


class RapidsConf:
    """A snapshot of config values: defaults, then environment overrides
    (SPARK_RAPIDS_TPU_<KEY with dots as underscores>), then explicit dict.

    The reference re-reads a fresh RapidsConf per rule application
    (GpuOverrides.scala:4748); we do the same per plan rewrite.
    """

    def __init__(self, overrides: Optional[dict] = None):
        self._values: Dict[str, Any] = {}
        for key, entry in _REGISTRY.items():
            env_key = "SPARK_RAPIDS_TPU_" + key.replace(".", "_").upper()
            if env_key in os.environ:
                self._values[key] = entry.conv(os.environ[env_key])
            else:
                self._values[key] = entry.default
        for k, v in (overrides or {}).items():
            if k in _REGISTRY:
                entry = _REGISTRY[k]
                self._values[k] = entry.conv(v) if isinstance(v, str) else v
            else:
                self._values[k] = v  # passthrough for op-enable keys

    def get(self, entry_or_key) -> Any:
        key = entry_or_key.key if isinstance(entry_or_key, ConfEntry) else entry_or_key
        return self._values.get(key, _REGISTRY[key].default if key in _REGISTRY else None)

    def set(self, entry_or_key, value) -> "RapidsConf":
        key = entry_or_key.key if isinstance(entry_or_key, ConfEntry) else entry_or_key
        # string values convert through the registry exactly like
        # constructor overrides ("false" must not read back truthy)
        if key in _REGISTRY and isinstance(value, str):
            value = _REGISTRY[key].conv(value)
        self._values[key] = value
        # the compile cache memoizes its conf fingerprint on this object
        # (runtime/compile_cache._conf_fingerprint): any mutation must
        # drop it, or an ANSI/float-mode flip would keep hitting
        # executables compiled under the old semantics
        self.__dict__.pop("_compile_fp", None)
        return self

    def is_op_enabled(self, op_key: str, default: bool = True) -> bool:
        """Per-op enable keys are auto-derived from rule names, e.g.
        spark.rapids.sql.exec.TpuSortExec (reference auto-derived keys)."""
        v = self._values.get(op_key)
        if v is None:
            return default
        return _bool_conv(v) if isinstance(v, str) else bool(v)

    def copy(self, **overrides) -> "RapidsConf":
        c = RapidsConf()
        c._values = dict(self._values)
        for k, v in overrides.items():
            c._values[k] = v
        return c


_local = threading.local()
_GLOBAL = RapidsConf()


def conf() -> RapidsConf:
    """Active session conf (thread-local override or global default)."""
    return getattr(_local, "conf", _GLOBAL)


def set_session_conf(c: RapidsConf) -> None:
    _local.conf = c
    # capacity bucketing policy is consulted deep inside kernels where no
    # conf rides along: publish the floor and the bucket shape as module
    # globals (runtime/shapes.py is the one home of the policy)
    from spark_rapids_tpu.columnar import batch as _b
    from spark_rapids_tpu.runtime import compile_cache as _cc
    from spark_rapids_tpu.runtime import shapes as _sh
    _b.MIN_CAPACITY = max(8, int(c.get(BATCH_CAPACITY_MIN)))
    _sh.configure(c.get(COMPILE_SHAPES_GROWTH),
                  c.get(COMPILE_SHAPES_DTYPE_ALIGN))
    _cc.publish_conf(c)


class session_conf:
    """Context manager scoping config overrides, used by tests to flip
    between CPU and TPU sessions (reference integration_tests
    spark_session.py with_cpu_session/with_gpu_session)."""

    def __init__(self, **overrides):
        full = {}
        for k, v in overrides.items():
            full[k] = v
        self._new = conf().copy(**full)

    def __enter__(self):
        self._old = getattr(_local, "conf", None)
        _local.conf = self._new
        return self._new

    def __exit__(self, *exc):
        if self._old is None:
            if hasattr(_local, "conf"):
                del _local.conf
        else:
            _local.conf = self._old
        return False


def registry() -> Dict[str, ConfEntry]:
    return dict(_REGISTRY)


def generate_docs() -> str:
    """Render the registry to markdown (reference RapidsConf.help:2505
    emitting docs/configs.md)."""
    lines = [
        "# spark-rapids-tpu configuration",
        "",
        "Generated by `spark_rapids_tpu.config.generate_docs()`; do not edit.",
        "",
        "| key | default | description |",
        "|---|---|---|",
    ]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.internal:
            continue
        doc = e.doc.replace("|", "\\|").replace("\n", " ")
        lines.append(f"| `{e.key}` | {e.render_default()} | {doc} |")
    lines.append("")
    return "\n".join(lines)
