"""TpuSession: the driver (reference Plugin.scala driver/executor plugin
bootstrap + the collect path). Owns config, converts plans through the
overrides engine, and runs root partitions as concurrent tasks."""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import pyarrow as pa

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import to_arrow
from spark_rapids_tpu.expr.core import SparkException
from spark_rapids_tpu.plan import nodes as P
from spark_rapids_tpu.runtime.metrics import walk_exec_tree
from spark_rapids_tpu.runtime.task import TaskContext
from spark_rapids_tpu.sql.dataframe import DataFrame

#: per-thread collect nesting depth: degradation policy and breaker
#: accounting apply only to top-level actions (depth 0 at entry) — a
#: nested collect's failure propagates to its enclosing query
_COLLECT_DEPTH = threading.local()


def nested_action_scope():
    """Context manager making collects on the CURRENT thread run as
    nested actions: no attribution aggregate open/reset, no breaker
    probe consumption, no degradation policy, no last_action_status.
    The AOT warmup replays (runtime/warmup.py) run under this — they
    are cache-priming work sharing the process with real queries."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        d = getattr(_COLLECT_DEPTH, "d", 0)
        _COLLECT_DEPTH.d = d + 1
        try:
            yield
        finally:
            _COLLECT_DEPTH.d = d

    return _cm()


def _discover_hive(root: str):
    """Walk a directory for hive-layout partitions (k=v subdirs). Returns
    (files, per_file_partition_values) or (files, None) when the layout is
    flat (reference: Spark's PartitioningAwareFileIndex)."""
    import os
    from urllib.parse import unquote
    files, vals = [], []
    found_parts = False
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        rel = os.path.relpath(dirpath, root)
        parts = {}
        if rel != ".":
            for seg in rel.split(os.sep):
                if "=" not in seg:
                    if any(f.endswith(".parquet") for f in filenames):
                        raise ValueError(
                            f"mixed layout under {root!r}: parquet files in "
                            f"non-partition directory {dirpath!r}")
                    parts = None
                    break
                k, _, v = seg.partition("=")
                parts[k] = (None if v == "__HIVE_DEFAULT_PARTITION__"
                            else unquote(v))
            if parts:
                found_parts = True
        if parts is None:
            continue
        for f in sorted(filenames):
            if f.endswith(".parquet") and not f.startswith("_"):
                files.append(os.path.join(dirpath, f))
                vals.append(parts)
    if not files:
        raise FileNotFoundError(f"no parquet files under {root!r}")
    return files, (vals if found_parts else None)


class TpuSession:
    def __init__(self, conf_overrides: Optional[Dict] = None):
        self.conf = C.RapidsConf(conf_overrides)
        self._views: Dict = {}
        self._last_meta = None
        #: artifact paths of the most recent traced action
        #: ({"trace","events","metrics"}; None until a traced collect runs)
        self.last_trace_paths = None
        #: (status, degraded_reason) of the most recent top-level action:
        #: ("ok", None), ("failed", None), or ("degraded", reason)
        self.last_action_status = ("ok", None)
        from spark_rapids_tpu.ops import pallas_kernels as PK
        PK.set_enabled(self.conf.get(C.PALLAS_ENABLED))
        # live observability (spark.rapids.obs.*): process-wide registry,
        # optional /metrics+/healthz endpoint, optional history store
        from spark_rapids_tpu.runtime import obs
        obs.install(self.conf)
        # persistent compilation cache + AOT warmup
        # (spark.rapids.compile.*): the cache dir applies immediately;
        # warmup arms now and launches replays as tables register
        from spark_rapids_tpu.runtime import compile_cache, warmup
        compile_cache.configure(self.conf)
        # arm the kernel cost auditor NOW, not first at
        # prepare_execution: the audit's per-query tally opens at
        # collect entry, before the plan converts — a session's first
        # query must already be audited
        from spark_rapids_tpu.analysis import kernel_audit
        kernel_audit.configure(self.conf)
        warmup.maybe_arm(self)
        # the serving layer (spark.rapids.serving.*): POST /sql on the
        # obs endpoint, result cache, warm-boot wait. Installs AFTER
        # warmup arms so a warm-boot server can block on the replay
        from spark_rapids_tpu.runtime import serving
        serving.maybe_install(self)

    def _activate(self):
        # name binding (case sensitivity) consults the active session conf
        # at plan-construction time
        from spark_rapids_tpu.config import set_session_conf
        set_session_conf(self.conf)

    # -- sources -----------------------------------------------------------
    def create_or_replace_temp_view(self, name: str, df) -> None:
        """Register a DataFrame for session.sql() FROM resolution."""
        self._views[name.lower()] = df
        # re-registering a view is the one way table data changes under a
        # stable plan digest: advance the table epoch so the adaptive
        # build-reuse cache (exec/adaptive.py) drops every cached build
        from spark_rapids_tpu.exec import adaptive as AQ
        AQ.bump_table_version()
        # a new table may unblock pending AOT warmup replays (one
        # module-global read when warmup is unarmed)
        from spark_rapids_tpu.runtime import warmup
        warmup.notify_view_registered(self)

    createOrReplaceTempView = create_or_replace_temp_view

    def table(self, name: str):
        if name.lower() not in self._views:
            raise SparkException(f"table or view not found: {name}")
        return self._views[name.lower()]

    def sql(self, query: str):
        """Run a SQL string over registered temp views (the analytic
        subset grammar — sql/parser.py)."""
        from spark_rapids_tpu.runtime.obs import phases as PH
        from spark_rapids_tpu.sql.parser import parse_sql
        parse_clk = PH.clock("parse")
        with PH.span("parse", parse_clk):
            df = parse_sql(query, self)
        try:
            # the replayable spec: history records carry the SQL text so
            # AOT warmup (runtime/warmup.py) can re-execute recurring
            # plans at session start
            df.plan._sql_text = query
            # and the parse's nanoseconds, for the action's phase account
            df.plan._sql_parse_ns = parse_clk.peek()
        except Exception:  # noqa: BLE001 - a slotted plan node just
            pass  # isn't warmup-replayable
        return df

    def create_dataframe(self, data,
                         num_partitions: Optional[int] = None) -> DataFrame:
        self._activate()
        if isinstance(data, dict):
            table = pa.table(data)
        elif isinstance(data, pa.Table):
            table = data
        else:
            raise TypeError(type(data))
        return DataFrame(P.InMemorySource(table, num_partitions), self)

    createDataFrame = create_dataframe

    def read_parquet(self, *paths, columns=None) -> DataFrame:
        self._activate()
        import os
        # hive-style partition discovery: dir of k=v subdirs -> recursive
        # file walk with the partition column reconstructed from the path
        if len(paths) == 1 and os.path.isdir(paths[0]):
            files, part_vals = _discover_hive(paths[0])
            if part_vals is not None:
                return DataFrame(P.ParquetScan(files, columns=columns,
                                               partition_values=part_vals),
                                 self)
        return DataFrame(P.ParquetScan(
            self._expand_paths(paths, suffix=".parquet"), columns=columns),
            self)

    def _expand_paths(self, paths, suffix: str = ""):
        import glob as _glob
        import os
        expanded: List[str] = []
        for p in paths:
            if os.path.isdir(p):
                expanded.extend(sorted(
                    f for f in _glob.glob(os.path.join(p, "*" + suffix))
                    if os.path.isfile(f) and not os.path.basename(f).startswith("_")))
            elif any(ch in p for ch in "*?["):
                expanded.extend(sorted(_glob.glob(p)))
            else:
                expanded.append(p)
        if not expanded:
            raise FileNotFoundError(f"no input files matched {list(paths)!r}")
        return expanded

    def read_csv(self, *paths, header: bool = True, sep: str = ",",
                 columns=None) -> DataFrame:
        return DataFrame(P.TextScan("csv", self._expand_paths(paths),
                                    columns=columns,
                                    options={"header": header, "sep": sep}),
                         self)

    def read_json(self, *paths, columns=None) -> DataFrame:
        return DataFrame(P.TextScan("json", self._expand_paths(paths),
                                    columns=columns), self)

    def read_avro(self, *paths, columns=None) -> DataFrame:
        return DataFrame(P.TextScan("avro", self._expand_paths(paths),
                                    columns=columns), self)

    def read_orc(self, *paths, columns=None) -> DataFrame:
        return DataFrame(P.TextScan("orc", self._expand_paths(paths),
                                    columns=columns), self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(P.Range(start, end, step, num_partitions), self)

    # -- execution ---------------------------------------------------------
    def prepare_execution(self, plan: P.PlanNode):
        """Session preamble shared by every action (collect, write):
        activate this session's conf, sync the spill budgets, arm fault
        injection (general sites + the legacy OOM injector), sync the
        retry backoff and the dispatch watchdog/breaker, convert the
        plan. Returns (exec_root, meta)."""
        from spark_rapids_tpu.analysis import kernel_audit, sanitizer
        from spark_rapids_tpu.config import set_session_conf
        from spark_rapids_tpu.plan.overrides import convert_plan
        from spark_rapids_tpu.runtime import faults, watchdog
        from spark_rapids_tpu.runtime.memory import get_spill_framework
        from spark_rapids_tpu.runtime.retry import (
            OomInjector, backoff_from_conf,
        )
        set_session_conf(self.conf)
        sanitizer.maybe_install(self.conf)
        kernel_audit.configure(self.conf)
        OomInjector.from_conf(self.conf)
        faults.from_conf(self.conf)
        backoff_from_conf(self.conf)
        watchdog.maybe_install(self.conf)
        get_spill_framework(self.conf)  # sync budgets to this session
        # measured cost pass: audited history for this plan's digest may
        # override partition counts / coalescing / fusion boundaries
        # during conversion (thread-local — concurrent sessions convert
        # under their own hints)
        from spark_rapids_tpu.exec import adaptive as AQ
        from spark_rapids_tpu.plan import cost as COST
        hints = COST.measured_hints(plan, self.conf)
        COST.install_hints(hints)
        try:
            exec_root, meta = convert_plan(plan, self.conf)
        finally:
            COST.clear_hints()
        if hints is not None and AQ.enabled(self.conf):
            AQ.record(AQ.MEASURED_COST, **hints.detail())
        self._last_meta = meta
        self._last_exec = exec_root
        # attach the converted tree to THIS query's live context (the
        # thread's bound query id) so /queries progress walks the
        # query's OWN execs — not session._last_exec, which concurrent
        # queries in one session clobber. First attach wins: a nested
        # collect re-enters here while the outer query executes
        from spark_rapids_tpu.runtime.obs import live as _live
        qc = _live.current_context()
        if qc is not None:
            qc.attach_exec(exec_root)
        return exec_root, meta

    def last_metrics(self):
        """Per-exec metrics of the most recent action (the SQL-UI metrics
        surface; reference GpuMetric / GpuTaskMetrics §5.5). Returns
        {exec_name#i: {metric: value}} in walk_exec_tree order (fused
        members and absorbed pre-chains snapshot alone — recursing their
        original child links would re-walk shared subtrees)."""
        out = {}
        if getattr(self, "_last_exec", None) is not None:
            for key, node, _d, _role, _sid in walk_exec_tree(
                    self._last_exec):
                snap = node.metrics.snapshot()
                if snap:
                    out[key] = snap
        return out

    def collect(self, plan: P.PlanNode,
                timeout_seconds: Optional[float] = None) -> pa.Table:
        import time as _time

        from spark_rapids_tpu.runtime import lifecycle as LC
        from spark_rapids_tpu.runtime import obs as OBS
        from spark_rapids_tpu.runtime import trace as TR
        from spark_rapids_tpu.runtime.obs import phases as PH
        # structured trace per action (spark.rapids.sql.trace.*): spans +
        # instants + the task event log, finalized with this action's
        # metrics snapshot so the offline report can reconcile the two.
        # A nested collect (broadcast materialization) returns qt=None and
        # joins the enclosing query's trace.
        qt = TR.start_query(self.conf)
        if qt is None and self.conf.get(C.TRACE_ENABLED):
            # tracing was requested but another query owns the tracer
            # (nested collect, or a concurrent session): this action gets
            # no artifacts of its own — never leave a PREVIOUS query's
            # paths looking like this one's. A same-session outer collect
            # restores its own paths when it finalizes.
            self.last_trace_paths = None
        # degradation is a TOP-LEVEL policy, and so is the phase account
        # (runtime/obs/phases.py): a nested collect (broadcast
        # materialization inside a running device query) propagates its
        # failure to the outer query, which then degrades whole, and its
        # time stays inside the outer query's query.execute
        depth = getattr(_COLLECT_DEPTH, "d", 0)
        ph = PH.QueryPhases(plan) if depth == 0 else PH.NESTED
        with ph.span("admit"):
            # live-observability token: None when obs is off or this is a
            # nested collect (only top-level actions publish + make history).
            # The digest is computed UP FRONT (a cheap logical-tree hash) so
            # the live registry and the queryStart marker can carry it while
            # the query is still running — a hung query's flight dump needs
            # its t0 and identity without waiting for the epilogue
            start_digest = None
            if depth == 0:
                try:
                    start_digest = OBS.plan_digest(plan)
                except Exception:  # noqa: BLE001 - an undigestable plan
                    pass  # still runs and registers
            ot = OBS.on_query_start(plan_digest=start_digest,
                                    sql=getattr(plan, "_sql_text", None))
            if depth == 0:
                # queryStart instant for EVERY top-level action, traced or
                # not (the flight ring records it too): ring timelines of a
                # hung or failed query get a t0 marker with the query's
                # identity, pairing with the queryError/queryDegraded
                # epilogue markers
                try:
                    TR.instant("queryStart", cat="query", args={
                        "query_id": ot if isinstance(ot, int) else None,
                        "plan_digest": start_digest},
                        level=TR.ESSENTIAL)
                except Exception:  # noqa: BLE001 - a marker failure must
                    pass  # not fail the query

            if qt is not None or (ot is not None and ot is not OBS.NESTED):
                # drop the PREVIOUS action's exec tree before this one runs:
                # a failure before convert_plan rebuilds it must publish
                # nothing — republishing the old tree's (unchanged) metrics
                # would double the registry counters and attach the previous
                # query's plan to this query's history record
                self._last_exec = None
                self._last_meta = None
            t0 = _time.perf_counter_ns()
            wall0 = _time.time()
            error: Optional[BaseException] = None
            status = "ok"
            degraded_reason: Optional[str] = None
            cancel_reason: Optional[str] = None
            tok = None  # this action's CancelToken (top-level only)
            _COLLECT_DEPTH.d = depth + 1
            if depth == 0:
                # open the per-query attribution aggregate (compile timing,
                # task accumulators) — runs regardless of obs state so
                # explain(mode="analyze") always has a breakdown
                from spark_rapids_tpu.runtime.obs import attribution as ATTR
                ATTR.on_query_start()
                # and the kernel cost auditor's dispatch tally (one global
                # read when the audit is off; the conf rides along so a
                # mid-session enable covers THIS query)
                from spark_rapids_tpu.analysis import kernel_audit as KA
                KA.on_query_start(self.conf)
                # and the adaptive decision recorder: every AQE decision this
                # query makes (conversion, skew split, build reuse, measured
                # cost) lands in one per-query doc
                from spark_rapids_tpu.exec import adaptive as AQ
                AQ.on_query_start(self.conf)
        cpu_gate_failed = False
        try:
            if depth == 0:
                # query lifecycle control (runtime/lifecycle.py): the
                # cancel token (deadline-armed from the conf or the
                # per-action override) registers FIRST so the query is
                # cancellable even while queued for admission; admit()
                # then parks this thread in the bounded `queued` state
                # when spark.rapids.query.maxConcurrent is saturated —
                # raising QueryRejectedError (queue full / wait timeout)
                # or QueryCancelledError (cancelled while queued)
                with ph.span("admit"):
                    tok = LC.begin_action(
                        ot if isinstance(ot, int) else None, self.conf,
                        timeout_seconds=timeout_seconds)
                    LC.admit(tok, self.conf)
                    if isinstance(ot, int):
                        try:
                            from spark_rapids_tpu.runtime.obs import (
                                live as _live,
                            )
                            qc = _live.get(ot)
                            if qc is not None:
                                qc.transition("planning")
                        except Exception:  # noqa: BLE001 - registry is
                            pass  # advisory
            if depth == 0 and self._fallback_enabled():
                from spark_rapids_tpu.runtime import watchdog as WD
                brk = WD.peek_breaker()
                if brk is not None and not brk.allow():
                    # breaker open: skip the device entirely instead of
                    # feeding queries into a known-bad backend; allow()
                    # lets exactly one probe query through per backoff
                    # window to test recovery (half-open)
                    status = "degraded"
                    degraded_reason = "circuit_open"
                    try:
                        return self._execute_cpu_fallback(plan)
                    except BaseException:
                        # a CPU-path failure: the device never ran, so
                        # the outer handler must neither record a device
                        # breaker failure nor re-run the identical CPU
                        # fallback a second time
                        cpu_gate_failed = True
                        status = "failed"
                        degraded_reason = None
                        raise
            prof_dir = self.conf.get(C.PROFILE_DIR)
            if prof_dir:
                # XProf trace per action (reference ProfilerOnExecutor /
                # NVTX); structured spans forward TraceAnnotations into
                # this capture so both timelines share operator names
                import jax
                with jax.profiler.trace(prof_dir):
                    result = self._collect_inner(plan, ph)
            else:
                result = self._collect_inner(plan, ph)
            if depth == 0:
                self._record_device_success()
            return result
        except BaseException as e:
            error = e
            if depth == 0 and isinstance(e, LC.QueryCancelledError):
                # a cooperative cancel (user, deadline, or injected
                # fault) is its own terminal status — never degraded to
                # a CPU re-execution, never counted as a plain failure
                status = "cancelled"
                cancel_reason = e.reason
                raise
            fallback = self._maybe_degrade_cpu(plan, e) \
                if depth == 0 and not cpu_gate_failed else None
            if fallback is None:
                status = "failed"
                raise
            status = "degraded"
            degraded_reason = type(e).__name__
            return fallback
        finally:
            _COLLECT_DEPTH.d = depth
            duration_ns = _time.perf_counter_ns() - t0
            with ph.span("epilogue"):
                #: (status, reason) of the most recent top-level action —
                #: ok / failed / degraded / cancelled (chaos + serving
                #: callers read this without needing the obs registry)
                if depth == 0:
                    self.last_action_status = (
                        status, degraded_reason or cancel_reason)
                    # the token leaves the registry BEFORE the epilogue so
                    # metric snapshots / history writes can never re-raise
                    # the cancel; its admission slot releases here too
                    LC.finish_action(tok, status)
                self._finish_action(plan, qt, ot, error, duration_ns,
                                    wall0, status=status,
                                    degraded_reason=degraded_reason,
                                    cancel_reason=cancel_reason,
                                    top_level=depth == 0, phases=ph)
            if depth == 0 and isinstance(ot, int):
                # the epilogue's last line: this action's record enters
                # the obs ring (host integers only, no device sync)
                try:
                    OBS.publish_query_record(ph.record(
                        ot, status, duration_ns, error=error,
                        degraded_reason=degraded_reason,
                        extra=getattr(self, "_last_attr_extra", None)))
                except Exception:  # noqa: BLE001 - observability must
                    # never fail (or mask the real error of) a query
                    import logging
                    logging.getLogger("spark_rapids_tpu").warning(
                        "failed to record the query's phase account",
                        exc_info=True)

    def _fallback_enabled(self) -> bool:
        return bool(self.conf.get(C.FALLBACK_CPU_ENABLED))

    def _record_device_success(self) -> None:
        """Close the circuit on a successful device query (half-open
        probe succeeded, or plain success resetting the failure count).
        Only consulted when fallback is on — the breaker must not
        accumulate state from test suites that intentionally fail
        queries with fallback off."""
        if not self._fallback_enabled():
            return
        from spark_rapids_tpu.runtime import watchdog as WD
        brk = WD.peek_breaker()
        if brk is not None:
            brk.record_success()

    @staticmethod
    def _degradable(error: BaseException) -> bool:
        """Degradation policy: engine/device failures degrade (exhausted
        OOM retries, corrupted shuffle data, injected faults, wedged or
        failing device dispatch); user-semantic errors do NOT — an ANSI
        overflow or an unsupported-operation SparkException would raise
        identically on the CPU backend, so re-executing only delays the
        answer the user must see."""
        from spark_rapids_tpu.runtime.lifecycle import (
            QueryCancelledError, QueryRejectedError,
        )
        if isinstance(error, (KeyboardInterrupt, SystemExit,
                              GeneratorExit, QueryCancelledError,
                              QueryRejectedError)):
            # a cancelled query must terminate (re-executing it on the
            # CPU would resurrect exactly the work the user killed), and
            # a rejected query re-executing would bypass admission
            return False
        return not isinstance(error, SparkException)

    def _execute_cpu_fallback(self, plan: P.PlanNode) -> pa.Table:
        from spark_rapids_tpu.config import set_session_conf
        from spark_rapids_tpu.exec.cpu_backend import execute_cpu
        set_session_conf(self.conf)
        return execute_cpu(plan, self.conf.get(C.ANSI_ENABLED))

    def _maybe_degrade_cpu(self, plan: P.PlanNode,
                           error: BaseException) -> Optional[pa.Table]:
        """Graceful degradation (spark.rapids.fallback.cpu.enabled): the
        device path failed a top-level query — re-execute it on the CPU
        backend and report `degraded` instead of `failed`. Returns the
        CPU result, or None when degradation is off, the error is
        user-semantic, or the CPU re-execution itself fails (the
        original device error then propagates)."""
        import logging
        if not self._fallback_enabled() or not self._degradable(error):
            return None
        from spark_rapids_tpu.runtime import watchdog as WD
        WD.breaker().record_failure(type(error).__name__)
        log = logging.getLogger("spark_rapids_tpu")
        log.warning(
            "query failed on the device path (%s: %s); degrading to CPU "
            "re-execution", type(error).__name__, str(error)[:200])
        try:
            return self._execute_cpu_fallback(plan)
        except Exception:  # noqa: BLE001 - surface the ORIGINAL device
            # error, with the CPU failure logged beside it
            log.warning("CPU fallback re-execution also failed",
                        exc_info=True)
            return None

    def _finish_action(self, plan, qt, ot, error, duration_ns,
                       wall0, status: Optional[str] = None,
                       degraded_reason: Optional[str] = None,
                       cancel_reason: Optional[str] = None,
                       top_level: bool = False, phases=None) -> None:
        """Query epilogue: finalize the trace (success OR failure),
        compute the wall-time attribution, trigger a flight-recorder
        dump on failure/degradation, and publish the action to the live
        observability layer. Every step is fenced — a failed query must
        still flush its buffered trace events (with an `error` instant
        and status=failed), and a last_metrics() snapshot that itself
        raises (a lazy device count on a poisoned buffer) must not
        swallow the artifacts, which it previously did by raising
        between the two finalize halves."""
        import logging

        from spark_rapids_tpu.runtime import obs as OBS
        from spark_rapids_tpu.runtime import trace as TR
        from spark_rapids_tpu.runtime.obs import attribution as ATTR
        from spark_rapids_tpu.runtime.obs import flight as FLIGHT
        log = logging.getLogger("spark_rapids_tpu")
        if status is None:
            status = "ok" if error is None else "failed"
        if top_level and isinstance(ot, int):
            # the epilogue (metric snapshot, attribution, trace
            # finalize, history publish) runs with the query visible as
            # `finishing` — a scrape during a slow lazy-count resolve
            # must not show a finished query as still executing
            try:
                from spark_rapids_tpu.runtime.obs import live as _live
                qc = _live.get(ot)
                if qc is not None:
                    qc.transition("finishing")
            except Exception:  # noqa: BLE001 - registry is advisory
                pass
        # ONE metric snapshot serves the trace finalize, the registry
        # rollups, and the history record (resolving lazy device row
        # counts costs real syncs) — and it is taken at all only when
        # something consumes it: a tracer, the endpoint, or the store
        obs_top = ot is not None and ot is not OBS.NESTED
        digest = None
        lm = None
        if qt is not None or (obs_top and OBS.wants_rollups()):
            try:
                lm = self.last_metrics()
            except Exception:  # noqa: BLE001 - snapshot must not block
                log.warning("failed to snapshot last_metrics",
                            exc_info=True)
        if qt is not None:
            try:
                digest = OBS.plan_digest(plan)
            except Exception:  # noqa: BLE001
                pass
        if top_level:
            # close the attribution aggregate and record the wall time
            # whether or not anything consumes them now — last_
            # attribution() / explain(mode="analyze") recompute on
            # demand from these plus a fresh metric snapshot
            try:
                self._last_attr_extra = ATTR.finish()
            except Exception:  # noqa: BLE001
                self._last_attr_extra = None
            self._last_duration_ns = duration_ns
            self._last_attribution = None
            # attributed for EVERY query, from the phase account's peeked
            # timers (host integers: no lazy count resolves, unlike the
            # snapshot above), so last_attribution() and /healthz have a
            # breakdown whether or not a snapshot consumer exists
            try:
                self._last_attribution = ATTR.attribute(
                    phases.peek_metrics(), duration_ns,
                    extra=self._last_attr_extra,
                    phases=phases.phases_ns())
            except Exception:  # noqa: BLE001
                log.warning("failed to attribute query time",
                            exc_info=True)
            # kernel cost audit: close the dispatch tally, resolve any
            # pending cost analyses (trace-time audits deferred off the
            # dispatch path), and join with the attribution's device
            # seconds into the roofline doc. One global read when off.
            from spark_rapids_tpu.analysis import kernel_audit as KA
            self._last_audit = None
            self._last_roofline = None
            try:
                self._last_audit = KA.finish_query()
                if self._last_audit is not None and lm is not None:
                    self._last_roofline = KA.roofline(
                        self._last_audit, lm, duration_ns,
                        extra=self._last_attr_extra)
            except Exception:  # noqa: BLE001 - the audit must never
                # fail (or mask the real error of) a query
                log.warning("failed to compute kernel cost audit",
                            exc_info=True)
            # close the adaptive decision recorder: the per-query doc
            # feeds last_aqe(), EXPLAIN ANALYZE and the history record
            from spark_rapids_tpu.exec import adaptive as AQ
            self._last_aqe = None
            try:
                self._last_aqe = AQ.finish_query()
            except Exception:  # noqa: BLE001 - decision bookkeeping
                # must never fail (or mask the real error of) a query
                log.warning("failed to close adaptive decisions",
                            exc_info=True)
        flight_dump = None
        if top_level and status in ("failed", "degraded", "cancelled"):
            # emit the outcome marker (tracer AND/OR flight ring), then
            # dump the flight rings: the failing query's timeline exists
            # retroactively even with tracing off
            try:
                if status == "cancelled":
                    # the terminal marker of a cooperative cancel: the
                    # trace ends here because the token fired (reason
                    # user/deadline/fault), with the attribution
                    # breakdown computed above showing where the budget
                    # went before death
                    TR.instant("queryCancelled", cat="query", args={
                        "query_id": ot if isinstance(ot, int) else None,
                        "reason": cancel_reason},
                        level=TR.ESSENTIAL)
                elif status == "degraded":
                    # the device path failed (or the breaker was open)
                    # but the CPU fallback answered: mark the timeline
                    # so the report attributes the tail to degradation
                    TR.instant("queryDegraded", cat="query", args={
                        "reason": degraded_reason,
                        "error": (type(error).__name__
                                  if error is not None else None)},
                        level=TR.ESSENTIAL)
                else:
                    # flush-time marker: the trace ends HERE because the
                    # query raised, not because instrumentation stopped
                    TR.instant("queryError", cat="query", args={
                        "error": type(error).__name__,
                        "message": str(error)[:200]},
                        level=TR.ESSENTIAL)
            except Exception:  # noqa: BLE001 - a marker failure must
                # not mask the query's own error
                log.warning("failed to emit query outcome instant",
                            exc_info=True)
            flight_dump = FLIGHT.dump(
                "query_" + status,
                query_id=ot if isinstance(ot, int) else None,
                error=(type(error).__name__ if error is not None
                       else degraded_reason))
        if qt is not None:
            # cleared first so a finalize failure can never leave a
            # PREVIOUS query's artifacts looking like this one's
            self.last_trace_paths = None
            try:
                self.last_trace_paths = TR.end_query(
                    qt, last_metrics=lm, status=status, error=error,
                    plan_digest=digest)
            except Exception:  # noqa: BLE001 - observability must
                # never fail (or mask the real error of) a query
                log.warning("failed to finalize query trace",
                            exc_info=True)
        if ot is not None:
            try:
                OBS.on_query_end(
                    ot, session=self, plan=plan, status=status,
                    error=error, duration_ns=duration_ns,
                    wall_start_unix=wall0,
                    # only a trace finalized by THIS action may attach:
                    # an untraced query must not inherit a previous
                    # traced query's artifact paths into its history
                    # record (cross_link would then resolve that trace
                    # to the wrong query)
                    trace_paths=(self.last_trace_paths
                                 if qt is not None else None),
                    last_metrics=lm,
                    degraded_reason=degraded_reason,
                    attribution_doc=getattr(self, "_last_attribution",
                                            None),
                    roofline_doc=getattr(self, "_last_roofline", None),
                    aqe_doc=getattr(self, "_last_aqe", None),
                    flight_dump=flight_dump)
            except Exception:  # noqa: BLE001
                log.warning("failed to publish query to obs",
                            exc_info=True)

    def run_partitions(self, exec_root, per_batch):
        """Execute every partition of an exec tree (parallel tasks, up to
        16 concurrent — the Spark task-scheduler role) applying per_batch
        to each output batch. Returns the flat result list in partition
        order. Shared by collect, writes, and the ML handoff."""
        nparts = exec_root.num_partitions

        def run(p: int) -> list:
            with TaskContext(partition_id=p) as ctx:
                return [per_batch(b)
                        for b in exec_root.execute_partition(ctx, p)]

        if nparts == 1:
            return run(0)
        from spark_rapids_tpu.runtime.host_pool import run_task_wave
        out = []
        for res in run_task_wave(run, range(nparts)):
            out.extend(res)
        return out

    def _collect_inner(self, plan: P.PlanNode, ph) -> pa.Table:
        if self.conf.get(C.SQL_MODE).lower() == "explainonly":
            # plan + tag + report only; execution stays on the CPU backend
            # with no device required (reference RapidsConf "explainOnly")
            from spark_rapids_tpu.config import set_session_conf
            from spark_rapids_tpu.plan.overrides import wrap_and_tag
            from spark_rapids_tpu.exec.cpu_backend import execute_cpu
            set_session_conf(self.conf)
            meta = wrap_and_tag(plan, self.conf)
            self._last_meta = meta
            import logging
            logging.getLogger("spark_rapids_tpu").info(
                "\n%s", meta.explain(all_ops=True))
            return execute_cpu(plan, self.conf.get(C.ANSI_ENABLED))
        with ph.span("plan"):
            exec_root, meta = self.prepare_execution(plan)
        ph.attach(exec_root)
        explain_mode = self.conf.get(C.SQL_EXPLAIN).upper()
        if explain_mode in ("NOT_ON_TPU", "ALL"):
            text = meta.explain(all_ops=explain_mode == "ALL")
            if "@" in text or explain_mode == "ALL":
                import logging
                logging.getLogger("spark_rapids_tpu").info("\n%s", text)
        names = plan.schema.names

        def fetch(b):
            # compact sparse masked batches ON DEVICE before the download:
            # the transfer moves full planes, and a bucket-agg output can
            # be a few-percent-occupied 4M-capacity batch
            with ph.span("fetch"):
                if b.row_mask is not None and b.capacity > 16384:
                    from spark_rapids_tpu.ops import kernels as K
                    b = K.compact_batch(b)
                return to_arrow(b, names)

        with ph.span("execute"):
            tables = self.run_partitions(exec_root, fetch)
        if not tables:
            fields = [pa.field(f.name, T.to_arrow(f.dtype))
                      for f in plan.schema.fields]
            return pa.Table.from_arrays(
                [pa.array([], type=f.type) for f in fields], schema=pa.schema(fields))
        return pa.concat_tables(tables)

    def cancel(self, query_id, reason: str = "user") -> bool:
        """Cooperatively cancel an in-flight top-level query by id (the
        ids session.running_queries() / the /queries endpoint report).
        The query's cancel token fires: threads parked on the semaphore,
        the admission queue or a retry backoff wake immediately, and the
        next cooperative checkpoint (per-batch dispatch, pipeline
        refill, wave start, exchange fetch) raises QueryCancelledError,
        which unwinds through normal task completion — permits, pool
        slots and spill handles release on their usual paths. Returns
        False when no such query is in flight (cancel-after-finish is a
        no-op). Also exposed as POST /queries/<id>/cancel on the obs
        endpoint."""
        from spark_rapids_tpu.runtime import lifecycle as LC
        return LC.cancel(query_id, reason=reason)

    def running_queries(self) -> List[dict]:
        """Live progress snapshots of every in-flight top-level query in
        this PROCESS (runtime/obs/live.py; the registry is process-wide,
        like the obs endpoint it feeds): query id, state, elapsed,
        per-exec batches/rows, %-complete and ETA. Pull-based and
        sync-free — scraping never adds device round trips to the
        running queries. Empty when obs or progress tracking is off."""
        from spark_rapids_tpu.runtime.obs import live as _live
        return _live.running_docs(with_execs=True)

    def last_plan_explain(self) -> str:
        return self._last_meta.explain(all_ops=True) if self._last_meta else ""

    def last_attribution(self) -> Optional[dict]:
        """Wall-time attribution of the most recent top-level action
        (runtime/obs/attribution.py): named phase buckets summing to the
        measured wall time. Uses the epilogue's precomputed document
        when one exists; otherwise recomputes from a fresh metric
        snapshot plus the stored per-query aggregate (compile timing,
        task accumulators). None before any action."""
        doc = getattr(self, "_last_attribution", None)
        if doc is not None:
            return doc
        dur = getattr(self, "_last_duration_ns", 0)
        if not dur or getattr(self, "_last_exec", None) is None:
            return None
        from spark_rapids_tpu.runtime.obs import attribution as ATTR
        try:
            return ATTR.attribute(
                self.last_metrics(), dur,
                extra=getattr(self, "_last_attr_extra", None))
        except Exception:  # noqa: BLE001 - attribution is advisory: a
            # poisoned lazy count must not fail an explain
            return None

    def last_audit(self) -> Optional[dict]:
        """Kernel cost audit summary of the most recent top-level action
        (analysis/kernel_audit.py): per-kernel-family dispatches, FLOPs,
        bytes accessed, plane bytes and padding exposure. None when
        spark.rapids.obs.audit.enabled was off for the action."""
        return getattr(self, "_last_audit", None)

    def last_roofline(self) -> Optional[dict]:
        """Roofline attribution of the most recent top-level action:
        audited bytes/FLOPs joined with measured device seconds into
        achieved GB/s + FLOP/s, roofline %, boundedness, and padding
        waste. Uses the epilogue's precomputed doc when one exists;
        otherwise recomputes from the stored audit summary plus a fresh
        metric snapshot. None when the audit was off."""
        doc = getattr(self, "_last_roofline", None)
        if doc is not None:
            return doc
        summary = getattr(self, "_last_audit", None)
        dur = getattr(self, "_last_duration_ns", 0)
        if not summary or not dur \
                or getattr(self, "_last_exec", None) is None:
            return None
        from spark_rapids_tpu.analysis import kernel_audit as KA
        try:
            return KA.roofline(summary, self.last_metrics(), dur,
                               extra=getattr(self, "_last_attr_extra",
                                             None))
        except Exception:  # noqa: BLE001 - the roofline view is
            # advisory: a poisoned lazy count must not fail an explain
            return None

    def last_aqe(self) -> Optional[dict]:
        """Adaptive execution decisions of the most recent top-level
        action (exec/adaptive.py): the decision list plus per-kind
        counts and total dispatches saved. None when adaptive execution
        was off for the action or it made no decisions."""
        return getattr(self, "_last_aqe", None)

    def explain_analyze(self) -> str:
        """The physical exec tree of the MOST RECENT action annotated
        with its actual runtime metrics — rows, batches, dispatches, and
        operator time per exec, straight from last_metrics() (the
        EXPLAIN ANALYZE surface; reference: the Spark SQL tab's metric
        annotations on the live plan). Fused-stage members render
        indented under their stage with the *(N) fusion-group marker,
        each with its own attributed numbers."""
        from spark_rapids_tpu.runtime.metrics import exec_rollup
        root = getattr(self, "_last_exec", None)
        if root is None:
            return "<no executed plan: run an action first>"
        snaps = self.last_metrics()
        lines: List[str] = []
        for key, node, depth, role, sid in walk_exec_tree(root):
            r = exec_rollup(snaps.get(key, {}))
            parts = [f"rows={r['rows']}", f"batches={r['batches']}"]
            if r["dispatches"]:
                parts.append(f"dispatches={r['dispatches']}")
            parts.append(f"time={r['time_ns'] / 1e6:.3f}ms")
            annot = ", ".join(parts)
            pad = "  " * depth
            if role is None:
                mark = f"*({sid}) " if sid is not None else ""
                lines.append(f"{pad}{mark}{node.name()}  [{annot}]")
            else:
                tag = "fused" if role == "member" else role
                lines.append(f"{pad}  *({sid}) {type(node).__name__} "
                             f"[{tag}]  [{annot}]")
        attr = self.last_attribution()
        if attr is not None:
            from spark_rapids_tpu.runtime.obs import attribution as ATTR
            lines.append("")
            lines.extend(ATTR.render_text(attr))
        roof = self.last_roofline()
        if roof is not None:
            from spark_rapids_tpu.analysis import kernel_audit as KA
            lines.append("")
            lines.extend(KA.render_text(roof))
        aqe = self.last_aqe()
        if aqe is not None:
            from spark_rapids_tpu.exec import adaptive as AQ
            lines.append("")
            lines.extend(AQ.render_text(aqe))
        return "\n".join(lines)
