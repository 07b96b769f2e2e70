"""Metrics framework (reference GpuExec.scala:33-284 GpuMetric and
GpuTaskMetrics.scala).

Per-exec named metrics with levels (ESSENTIAL/MODERATE/DEBUG) plus per-task
accumulators (semaphore wait, retry counts, spill bytes). Rendered by
explain/debug tooling; a live-Spark adapter would surface these as SQL
metrics in the UI.
"""
from __future__ import annotations

import threading
import time
from typing import Dict

ESSENTIAL = 0
MODERATE = 1
DEBUG = 2

# Standard metric names (reference GpuExec companion object)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
NUM_INPUT_BATCHES = "numInputBatches"
NUM_ROW_GROUPS = "numRowGroups"
NUM_ROW_GROUPS_PRUNED = "numRowGroupsPruned"
READ_BYTES = "readBytes"
#: raw ENCODED Parquet bytes a device-decode scan uploaded — the bytes
#: that actually crossed the host->device link (compare decodedBytes:
#: the ratio is the link traffic the device decoder saved)
ENCODED_BYTES = "encodedBytes"
#: decoded plane bytes a device-decode scan produced on device — what
#: the host path would have uploaded instead
DECODED_BYTES = "decodedBytes"
#: bytes of the device arrays a scan's upload built (the planes handed to
#: the device inside its copyToDeviceTime spans, from their shapes: no
#: sync): encoded planes plus host-decoded fallback columns on the
#: device-decode scan, decoded planes everywhere else
UPLOAD_BYTES = "uploadBytes"
#: columns a device-decode scan host-decoded instead (unsupported
#: type/encoding/codec; per-column reasons in explain/history)
NUM_DECODE_FALLBACK_COLUMNS = "numDecodeFallbackColumns"
#: columns a Parquet scan reads: its plan node's schema, partition-value
#: columns included. Set once, where the exec is built
NUM_SCAN_COLUMNS = "numScanColumns"
#: columns of a Parquet scan's view (or caller-given list) that the
#: planner's column pruning (plan/prune.py) cut from it: what the host
#: neither parses nor uploads
NUM_SCAN_COLUMNS_PRUNED = "numScanColumnsPruned"
#: live rows an ExpandExec handed on: a batch's rows once a projection
#: (grouping set), from sizes the host has. A rollup that runs as one sort
#: (RollupAggregateExec) expands nothing and counts nothing here
EXPAND_ROWS = "expandRows"
#: groups an aggregate emitted, where the host has the number (the
#: rollup's read-back; an aggregate whose output count is a host int):
#: the query's record keeps the largest
AGG_GROUPS = "aggGroups"
OP_TIME = "opTime"
#: ns ExpandExec spent issuing its projections (inside no other span)
EXPAND_TIME = "expandTime"
#: ns WindowExec spent issuing its sort (keys packed, the shared argsort
#: a plane), apart from the scans that follow; inside opTime
WINDOW_SORT_TIME = "windowSortTime"
#: ns the DEVICE took over WindowExec's sort by two planes (the key
#: program and the shared argsort's passes), read at the next read-back
#: that exists (runtime/obs/phases.device_mark); windowSortTime is their
#: enqueue
WINDOW_SORT_DEVICE_TIME = "windowSortDeviceTime"
#: ns the DEVICE took over a rollup's programs (pack, argsort and scan up
#: to the read-back of the levels' counts, then the emit), with whatever
#: unmarked program its child enqueued just before them (device_mark); and
#: over a HashAggregateExec's update a batch, then its merge and evaluate
AGG_DEVICE_TIME = "aggDeviceTime"
#: ns the DEVICE took over a mask-through inner, semi or anti join's
#: probe: the cut to the build keys' span and its compaction where taken,
#: the look-up and the gathers of the build's columns (device_mark);
#: joinTime is their enqueue
JOIN_DEVICE_TIME = "joinDeviceTime"
#: ns a Filter spent issuing a string match over a column's byte plane
#: (expr/strings._LiteralMatch, Like, RLike): its enqueue
STRING_MATCH_TIME = "stringMatchTime"
#: ns the DEVICE took over that Filter's program (device_mark: read at
#: the next read-back that exists)
STRING_MATCH_DEVICE_TIME = "stringMatchDeviceTime"
#: bytes of the columns such a Filter matched, once an evaluation, from
#: sizes the host has: a flat column's live bytes and its offsets (4 a row
#: and one more); a dictionary column's vocabulary planes and its codes
STRING_MATCH_BYTES = "stringMatchBytes"
#: rows the hash joins handed on, from counts the host has or comes to
#: have anyway (a lazy count nobody forces is left out)
JOIN_OUTPUT_ROWS = "joinOutputRows"
SORT_TIME = "sortTime"
AGG_TIME = "aggTime"
JOIN_TIME = "joinTime"
CONCAT_TIME = "concatTime"
DECODE_TIME = "tpuDecodeTime"
COPY_TO_DEVICE_TIME = "copyToDeviceTime"
COPY_FROM_DEVICE_TIME = "copyFromDeviceTime"
FILTER_TIME = "filterTime"
BUILD_TIME = "buildTime"
SEMAPHORE_WAIT_TIME = "semaphoreWaitTime"
SPILL_TO_HOST_BYTES = "spillToHostBytes"
SPILL_TO_DISK_BYTES = "spillToDiskBytes"
RETRY_COUNT = "retryCount"
SPLIT_RETRY_COUNT = "splitAndRetryCount"
PARTITION_TIME = "partitionTime"
#: PARTITIONING-KERNEL dispatches per input batch (the pid + sort +
#: offsets computation, NOT output assembly): 'compact' launches ONE
#: fused counting-sort program, 'masked' emits n_out full-capacity
#: mask-sliced sub-batches (each a separate downstream computation).
#: The compact path's per-slice assembly gathers are sized by output
#: rows and are not partitioning kernels — they are not counted here.
PARTITION_DISPATCHES = "partitionDispatches"
#: host round trips needed to size an input batch's partitions: 'compact'
#: fetches the n_out+1 offsets vector ONCE, 'masked' defers one lazy row
#: count per sub-batch (n_out syncs when they materialize)
PARTITION_HOST_FETCHES = "partitionHostFetches"
#: fused-stage entries issued per input batch: a vertically fused pipeline
#: stage (exec/stage_fusion.py) dispatches exactly ONE composed XLA
#: computation per batch; the unfused chain pays one per member operator.
#: Dispatch-budget tests assert stageDispatches == input batch count.
STAGE_DISPATCHES = "stageDispatches"
#: SPMD waves a sharded stage (exec/sharded.py) dispatched: each wave
#: runs up to n_shards partition batches as ONE shard_map program over
#: the mesh, so shardWaves * n_shards bounds the partition batches the
#: multichip path absorbed into collective dispatches
SHARD_WAVES = "shardWaves"
#: ns a sharded stage spent issuing its SPMD program: operands assembled
#: (in place from resident shards, or packed on the host and put over the
#: mesh), the keyed dispatch and its retry. No host sync inside
SHARD_DISPATCH_TIME = "shardDispatchTime"
#: ns a sharded stage spent bringing its output (partial states, or the
#: chain's planes) back to the host: the sync on the SPMD program
SHARD_READBACK_TIME = "shardReadbackTime"
#: bytes a query moved onto or between chips to feed a mesh: the planes a
#: sharded stage packed on the host and put over the mesh, and the
#: shards of a placed cache handed to an operator that computes on the
#: default device. 0 when the shards are consumed where they live
MESH_PUT_BYTES = "meshPutBytes"
#: ns a shuffle exchange spent inside the in-program ICI all_to_all
#: dispatch (the shard_map'd collective itself, issued with NO host
#: sync in the span). NESTED inside partitionTime — rollups and
#: attribution exclude it so exchange time is never double-counted;
#: the attribution 'ici_exchange' view reports it separately.
ICI_EXCHANGE_TIME = "iciExchangeTime"
#: hash exchanges a query did not execute: the planner allowed it (the
#: sole consumer is the final aggregate of the same plan node), every
#: input batch was on the host with a host-int row count, and the rows in
#: all stayed within the tiny-coalescing budget, so they went as ONE
#: batch to partition 0 (ShuffleExchangeExec._bypass); its rows count
#: into numOutputRows as any exchange's do
EXCHANGE_BYPASSED = "exchangeBypassed"
#: post-shuffle sub-batches merged by tiny-partition coalescing
#: (spark.rapids.shuffle.coalesceTinyRows): adjacent device sub-batches
#: under the threshold concat into one batch before downstream
#: dispatch, shrinking both the dispatch count and the shape zoo the
#: compile cache must cover
SHUFFLE_COALESCED_BATCHES = "shuffleCoalescedBatches"
#: serialized-shuffle bytes an exchange wrote into its host store
#: (post-compression wire bytes; reference shuffle write metrics)
SHUFFLE_BYTES_WRITTEN = "shuffleBytesWritten"
#: serialized-shuffle bytes the host store overflowed to disk files
SHUFFLE_BYTES_SPILLED = "shuffleBytesSpilled"
#: lookahead of a pipeline boundary as executed (0 = ran synchronously:
#: pipelining disabled, or the per-stage setup fallback fired)
PIPELINE_DEPTH = "pipelineDepth"
#: ns the CONSUMER side of a pipeline boundary spent blocked waiting for
#: the producer (device starved by host decode — the number a deeper
#: lookahead or more reader threads would shrink)
PIPELINE_STALL_TIME = "pipelineStallTime"
#: ns the producer side spent decoding/uploading upstream batches on the
#: host pool — work that overlapped downstream compute instead of
#: sitting serially in the critical path
PIPELINE_PRODUCER_TIME = "pipelineProducerTime"

#: *Time metrics that record WAITING or overlapped work, not exclusive
#: operator work: folding them into an operator-time rollup would make
#: hot-path comparisons lie (wait is scheduling; producer time is the
#: upstream's own decode/upload time, already on the upstream's metrics)
WAIT_TIME_METRICS = frozenset((
    SEMAPHORE_WAIT_TIME, PIPELINE_STALL_TIME, PIPELINE_PRODUCER_TIME))

#: *Time metrics that are NESTED inside another *Time metric on the same
#: exec (iciExchangeTime runs inside partitionTime's span): folding both
#: into a rollup would count the nested interval twice
NESTED_TIME_METRICS = frozenset((ICI_EXCHANGE_TIME,))


class GpuMetric:
    __slots__ = ("name", "level", "_value", "_lock", "_deferred")

    def __init__(self, name: str, level: int = MODERATE):
        self.name = name
        self.level = level
        self._value = 0
        self._lock = threading.Lock()
        self._deferred = []

    def add(self, v) -> None:
        """Accepts ints or LazyRowCount; lazy counts are NOT synchronized
        here — they resolve when the metric is read (metrics must never
        add device round trips to the hot path)."""
        from spark_rapids_tpu.columnar.batch import LazyRowCount
        if isinstance(v, LazyRowCount) and not v.is_materialized:
            with self._lock:
                self._deferred.append(v)
            return
        with self._lock:
            self._value += int(v)

    def set(self, v: int) -> None:
        with self._lock:
            self._value = int(v)
            self._deferred = []

    def set_max(self, v: int) -> None:
        """High-water-mark semantics (maxDeviceBytesHeld in the task
        accumulators; reference GpuTaskMetrics maxDeviceMemoryBytes)."""
        with self._lock:
            if int(v) > self._value:
                self._value = int(v)

    @property
    def value(self) -> int:
        with self._lock:
            if self._deferred:
                from spark_rapids_tpu.columnar.batch import LazyRowCount
                import jax as _jax
                pending = [v for v in self._deferred
                           if isinstance(v, LazyRowCount) and not v.is_materialized]
                if pending:  # ONE bulk fetch, not one round trip per count
                    for lz, val in zip(pending,
                                       _jax.device_get([p._dev for p in pending])):
                        lz._val = int(val)
                self._value += sum(int(v) for v in self._deferred)
                self._deferred = []
            return self._value

    def peek(self) -> int:
        """Materialized value WITHOUT resolving deferred lazy device
        counts (no device sync, unlike .value): the live-progress read.
        A scrape of a RUNNING query must never inject host round trips
        into its dispatch stream, so deferred counts that have not
        materialized on their own yet are simply not included."""
        with self._lock:
            v = self._value
            for d in self._deferred:
                if d.is_materialized:
                    v += int(d)
            return v

    def ns(self):
        """Context manager timing a block in nanoseconds."""
        return _Timer(self)


class _Timer:
    def __init__(self, metric: GpuMetric):
        self.metric = metric

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.metric.add(time.perf_counter_ns() - self.t0)
        return False


class MetricsRegistry:
    """Per-exec metric set filtered by the configured level."""

    def __init__(self, level: int = MODERATE):
        self.level = level
        self.metrics: Dict[str, GpuMetric] = {}

    def metric(self, name: str, level: int = MODERATE) -> GpuMetric:
        if name not in self.metrics:
            m = GpuMetric(name, level)
            self.metrics[name] = m
        return self.metrics[name]

    def snapshot(self) -> Dict[str, int]:
        return {k: m.value for k, m in self.metrics.items()
                if m.level <= self.level}

    def peek_snapshot(self) -> Dict[str, int]:
        """snapshot() without resolving lazy device counts (GpuMetric.
        peek) — what live-progress scrapes of a running query read."""
        return {k: m.peek() for k, m in self.metrics.items()
                if m.level <= self.level}


def walk_exec_tree(root):
    """THE canonical exec-tree metric walk: each node, then its
    vertically fused members, then its absorbed pre-chain members, then
    its children — yielding `(key, node, depth, role, stage_id)` with
    keys `ClsName#i` in visit order. `TpuSession.last_metrics()` /
    `explain_analyze()` and `stage_fusion.fusion_groups()` (and through
    them the history records and the history server's plan annotation)
    all derive from this ONE generator, so the walk-order invariant
    cannot drift between hand-written copies. Fused members' original
    child links point into the collapsed chain — they are yielded
    alone, never recursed. Duck-typed: no exec imports."""
    counter = [0]

    def key_of(n):
        k = f"{type(n).__name__}#{counter[0]}"
        counter[0] += 1
        return k

    def walk(n, depth):
        members = getattr(n, "members", None) or []
        pre = getattr(n, "pre_chain_members", None) or []
        sid = (getattr(n, "stage_id", None) if members
               else getattr(n, "fused_stage_id", None) if pre else None)
        yield key_of(n), n, depth, None, sid
        for m in members:
            yield key_of(m), m, depth, "member", sid
        for m in pre:
            yield key_of(m), m, depth, "absorbed", sid
        for c in n.children:
            yield from walk(c, depth + 1)

    yield from walk(root, 0)


def exec_rollup(snapshot: Dict[str, int]) -> Dict[str, int]:
    """Fold one exec's metric snapshot into the standard rollup the
    observability surfaces share (EXPLAIN ANALYZE annotations, history
    records, /metrics per-operator series): output rows, batches,
    device dispatches, and total operator time.

    time_ns sums every *Time metric EXCEPT the WAIT_TIME_METRICS
    (semaphore wait, pipeline stall, pipeline producer time) — wait is
    scheduling and producer time is overlapped upstream work, not this
    operator's own; folding either in would make every hot-path
    comparison lie under contention — and the NESTED_TIME_METRICS,
    whose intervals already sit inside another metric's span."""
    rows = int(snapshot.get(NUM_OUTPUT_ROWS, 0))
    # presence-based fallback, NOT falsy-or: an exec that RECORDED zero
    # output batches (every input row filtered away) must report 0, not
    # its input batch count — the zero-output case is exactly what a
    # reader of these numbers is usually debugging
    batches = int(snapshot[NUM_OUTPUT_BATCHES]
                  if NUM_OUTPUT_BATCHES in snapshot
                  else snapshot.get(NUM_INPUT_BATCHES, 0))
    dispatches = int(snapshot[STAGE_DISPATCHES]
                     if STAGE_DISPATCHES in snapshot
                     else snapshot.get(PARTITION_DISPATCHES, 0))
    time_ns = sum(int(v) for k, v in snapshot.items()
                  if k.endswith("Time") and k not in WAIT_TIME_METRICS
                  and k not in NESTED_TIME_METRICS)
    return {"rows": rows, "batches": batches, "dispatches": dispatches,
            "time_ns": time_ns}


def metrics_level_from_conf(conf) -> int:
    from spark_rapids_tpu import config as C
    s = conf.get(C.METRICS_LEVEL).upper()
    return {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE, "DEBUG": DEBUG}.get(s, MODERATE)
