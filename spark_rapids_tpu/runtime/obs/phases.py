"""The per-query phase account: where one top-level action's wall went.

`TpuSession.collect` opens a `QueryPhases` for every top-level action and
times its seams through the one instrumentation point
(`trace.metric_span`, level ESSENTIAL, category `query`), so each phase is
at once a span in every trace sink (the `rapids.query.*` annotations of a
profiler capture among them) and a clock of this account. At the end of
the epilogue the account becomes one record in the obs ring
(`obs.recent_queries`), written with no device sync:

    seq, query_id, status     the ring's sequence number, the live id
    t0_ns, wall_ns            collect entry (perf_counter_ns, the flight
                              ring's clock) to the record being written
    phases_ns                 parse     sql.parse: TpuSession.sql, BEFORE
                                        the action (it rides on the plan;
                                        0 for a DataFrame-API plan)
                              admit     query.admit: digest, live
                                        registration, recorders opened,
                                        lifecycle admission
                              plan      query.plan: prepare_execution
                              execute   query.execute: run_partitions
                              fetch     query.fetch: per-batch compaction
                                        and to_arrow (the download, then
                                        the host building the table);
                                        INSIDE execute, summed over task
                                        threads
                              epilogue  query.epilogue: finish_action and
                                        _finish_action
                              unspanned wall_ns - (admit + plan + execute
                                        + epilogue): the glue between
    timers_ns                 the exec tree's *Time metrics summed by name
                              (GpuMetric.peek: host integers, never a
                              lazy device count), plus the task
                              accumulators attribution.finish() returns
                              (semaphore_wait, compile, retry_backoff,
                              spill), plus deviceWaitTime (below)
    counters                  keyed_dispatches, upload_bytes (uploadBytes),
                              shard_waves (shardWaves: SPMD waves the
                              sharded stages dispatched), mesh_put_bytes
                              (meshPutBytes: table planes moved onto or
                              between chips to feed a mesh; 0 when the
                              shards are consumed where they live),
                              exchange_bypassed (exchangeBypassed: hash
                              exchanges whose input, a few rows already
                              on the host, went whole to one partition
                              and was never exchanged),
                              scan_columns_read, scan_columns_pruned
                              (numScanColumns, numScanColumnsPruned over
                              the query's Parquet scans: columns the host
                              parsed and uploaded, and columns the
                              planner's pruning cut from the scans),
                              expand_rows (expandRows: rows ExpandExec
                              wrote once a grouping set, summed; 0
                              where a rollup runs as one sort),
                              agg_groups (aggGroups: groups the query's
                              largest aggregate emitted, where the host
                              has the number),
                              string_match_bytes (stringMatchBytes: the
                              bytes of the columns the query's Filters
                              matched against literal runs or a regex,
                              once an evaluation, from sizes the host
                              has), join_output_rows (joinOutputRows:
                              rows its hash joins handed on, from counts
                              the host has)
    mesh                      only when a sharded stage ran: devices (the
                              mesh size) and shard_rows (per stage, the
                              live rows each shard's last body put out,
                              summed over the query's waves)
    wall_ms, error_class, finished_unix[, degraded_reason, slo_breach]
                              what ObsState.last_query always carried

`timers_ns.deviceWaitTime` is the host time blocked on the device where
the engine itself brings a device value to the host (`device_wait()`):
the three doors of columnar/batch.py, which are a forced LazyRowCount and
every other scalar a host decision needs (`host_int`: join and group
sizes, string widths, ANSI error flags), the counts' bulk fetch
(`materialize_counts`) and a batch's download (`fetch_batch_host`, under
every `to_arrow`, so under every query.fetch); and the small arrays the
sort, the radix group-by and the bounds probe read back
(exec/tpu_nodes.py). Summed over threads. Still outside it, in
query.execute's host time: the read-backs of the exchange, window and
partitioning execs (an `np.asarray`/`device_get` inside one exec).
Process-wide like `keyed_dispatches`.

`timers_ns.*DeviceTime` (joinDeviceTime, aggDeviceTime,
windowSortDeviceTime, stringMatchDeviceTime) are the DEVICE's time for a step whose span times
only its enqueue (`device_mark()`): the step names one of its outputs, and
the next `device_wait()` of the thread, before its own wait, waits for
each named output in turn and stamps the host's clock. A mark's time runs
from the mark read before it, or from the step's first enqueue if that is
later, to its own: the device takes its programs in the order they were
enqueued, so that is the time it spent on the step and on whatever
unmarked program went before it. No wait is added: every output named
is ready before the value the host was about to wait for anyway. Exact
while the host is ahead of the device; a mark the device has passed when
the host comes to read it gives its timer nothing (`device_idle_share`
says which of the two a cell is).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from spark_rapids_tpu.runtime.metrics import (
    AGG_GROUPS, ESSENTIAL, EXCHANGE_BYPASSED, EXPAND_ROWS, JOIN_OUTPUT_ROWS, MESH_PUT_BYTES,
    NUM_SCAN_COLUMNS, NUM_SCAN_COLUMNS_PRUNED, SHARD_WAVES, STRING_MATCH_BYTES, UPLOAD_BYTES,
    GpuMetric, walk_exec_tree,
)

#: record counter -> the exec metric summed into it over the exec tree
COUNTERS = {"upload_bytes": UPLOAD_BYTES, "shard_waves": SHARD_WAVES,
            "mesh_put_bytes": MESH_PUT_BYTES,
            "exchange_bypassed": EXCHANGE_BYPASSED,
            "scan_columns_read": NUM_SCAN_COLUMNS,
            "scan_columns_pruned": NUM_SCAN_COLUMNS_PRUNED,
            "expand_rows": EXPAND_ROWS,
            "string_match_bytes": STRING_MATCH_BYTES,
            "join_output_rows": JOIN_OUTPUT_ROWS}

#: phase -> the span that times it
SPANS = {"parse": "sql.parse", "admit": "query.admit",
         "plan": "query.plan", "execute": "query.execute",
         "fetch": "query.fetch", "epilogue": "query.epilogue"}

#: records the obs ring keeps (the newest top-level actions)
RING_SIZE = 256

#: programs started through fuse.fused() closures and compiled.run_stage,
#: process-wide and monotonic. A plain int bumped without a lock (the
#: compile_cache._STATS pattern: a lost update costs a count).
keyed_dispatches = 0


#: host nanoseconds inside device_wait() blocks, process-wide and
#: monotonic like keyed_dispatches
device_wait_ns = 0


class _Marks(threading.local):
    """A thread's unread device marks, and when the device reached the
    last one read."""

    def __init__(self):
        self.pending: list = []   # (timer, array, since_ns)
        self.reached_ns = 0


_marks = _Marks()

#: unread marks a thread keeps (each holds one array alive): a thread
#: that never waits again forgets the oldest
_MARKS_KEPT = 16


def device_mark(timer: GpuMetric, array, since_ns: int) -> None:
    """The device's time for the step that was first enqueued at
    `since_ns` (perf_counter_ns) and whose last program computes `array`
    goes to `timer` at this thread's next device_wait() (module
    docstring). `array` is kept until then: name a small output, and never
    one the program only hands through (jit forwards those, ready at
    once)."""
    pending = _marks.pending
    pending.append((timer, array, since_ns))
    del pending[:-_MARKS_KEPT]


def _read_marks() -> None:
    m = _marks
    pending, m.pending = m.pending, []
    for timer, array, since_ns in pending:
        passed = array.is_ready()
        array.block_until_ready()
        now = time.perf_counter_ns()
        if not passed:
            timer.add(now - max(m.reached_ns, since_ns))
        m.reached_ns = now


class _DeviceWait:
    """One block in which the host waits for a device value."""

    __slots__ = ("t0",)

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        if _marks.pending:
            _read_marks()

    def __exit__(self, *exc):
        global device_wait_ns
        device_wait_ns += time.perf_counter_ns() - self.t0
        return False


def device_wait() -> _DeviceWait:
    return _DeviceWait()


def clock(phase: str) -> GpuMetric:
    return GpuMetric(phase, ESSENTIAL)


def span(phase: str, clk: GpuMetric):
    """The span of `phase`, feeding `clk` (one timed block for both)."""
    from spark_rapids_tpu.runtime import trace as TR
    return TR.metric_span(SPANS[phase], clk, cat="query", level=ESSENTIAL)


class QueryPhases:
    """One top-level action's account (module docstring)."""

    __slots__ = ("t0_ns", "parse_ns", "clocks", "dispatches0", "wait0",
                 "exec_root", "_peeked", "_shard_waves")

    def __init__(self, plan):
        self.t0_ns = time.perf_counter_ns()
        self.parse_ns = int(getattr(plan, "_sql_parse_ns", 0))
        self.clocks = {p: clock(p) for p in SPANS if p != "parse"}
        self.dispatches0 = keyed_dispatches
        self.wait0 = device_wait_ns
        self.exec_root = None
        self._peeked: Optional[Dict[str, dict]] = None
        self._shard_waves: list = []  # exec/sharded.MeshWave, by the walk

    def span(self, phase: str):
        return span(phase, self.clocks[phase])

    def attach(self, exec_root) -> None:
        self.exec_root = exec_root

    def phases_ns(self) -> Dict[str, int]:
        out = {"parse": self.parse_ns}
        out.update((p, c.peek()) for p, c in self.clocks.items())
        return out

    def peek_metrics(self) -> Dict[str, dict]:
        """{exec_key: {metric: value}} of this action's exec tree, in
        last_metrics() shape but through GpuMetric.peek: the ONE walk of
        a query's epilogue, shared by attribution and the record."""
        if self._peeked is None:
            self._peeked = {}
            for key, node, _d, _role, _sid in walk_exec_tree(
                    self.exec_root) if self.exec_root is not None else ():
                self._peeked[key] = node.metrics.peek_snapshot()
                wave = getattr(node, "shard_wave", None)
                if wave is not None:
                    self._shard_waves.append(wave)
        return self._peeked

    def record(self, query_id, status: str, duration_ns: int,
               error: Optional[BaseException] = None,
               degraded_reason: Optional[str] = None,
               extra: Optional[Dict[str, int]] = None) -> dict:
        """The ring record; `extra` is attribution.finish()'s aggregate."""
        wall_ns = time.perf_counter_ns() - self.t0_ns
        phases = self.phases_ns()
        phases["unspanned"] = wall_ns - sum(
            phases[p] for p in ("admit", "plan", "execute", "epilogue"))
        timers: Dict[str, int] = {}
        counters = {"keyed_dispatches": keyed_dispatches - self.dispatches0}
        counters.update((c, 0) for c in COUNTERS)
        counters["agg_groups"] = 0
        for snap in self.peek_metrics().values():
            for name, v in snap.items():
                if name.endswith("Time"):
                    timers[name] = timers.get(name, 0) + v
            for c, name in COUNTERS.items():
                counters[c] += snap.get(name, 0)
            counters["agg_groups"] = max(counters.get("agg_groups", 0),
                                         snap.get(AGG_GROUPS, 0))
        for bucket, ns in (extra or {}).items():
            timers[bucket] = timers.get(bucket, 0) + int(ns)
        timers["deviceWaitTime"] = device_wait_ns - self.wait0
        rec = {
            "seq": None,  # set by the ring
            "query_id": query_id, "status": status,
            "t0_ns": self.t0_ns, "wall_ns": wall_ns,
            "phases_ns": phases, "timers_ns": timers,
            "counters": counters,
            "wall_ms": round(duration_ns / 1e6, 3),
            "error_class": type(error).__name__ if error else None,
            "finished_unix": time.time(),
        }
        if degraded_reason is not None:
            rec["degraded_reason"] = degraded_reason
        ran = [w for w in self._shard_waves if w.shard_rows.any()]
        if ran:
            rec["mesh"] = {"devices": ran[0].m, "shard_rows": [
                [int(r) for r in w.shard_rows] for w in ran]}
        return rec


class _NestedPhases:
    """A nested collect's account: it stays inside its parent's
    query.execute, so it times nothing and keeps nothing."""

    __slots__ = ()

    def span(self, phase: str):
        from spark_rapids_tpu.runtime import trace as TR
        return TR._NULL

    def attach(self, exec_root) -> None:
        pass


NESTED = _NestedPhases()
