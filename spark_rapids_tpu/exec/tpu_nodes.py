"""TPU exec operator library.

Reference parity: the GpuExec hierarchy (GpuExec.scala:286 producing
RDD[ColumnarBatch]) and the operator inventory of SURVEY.md §2.4:
project/filter (basicPhysicalOperators.scala), hash aggregate
(GpuAggregateExec.scala), sort (GpuSortExec.scala), joins (GpuHashJoin /
GpuBroadcastHashJoinExec), coalesce (GpuCoalesceBatches.scala), exchanges
(GpuShuffleExchangeExecBase), expand, limit, union.

Execution model: each exec transforms per-partition iterators of device
ColumnarBatches. Exchanges are stage barriers that materialize their child
(running its partitions as tasks) and re-partition -- the role Spark's
shuffle plays for the reference. Device admission is gated by the
TpuSemaphore; projection/filter expression lists run as single fused XLA
stages (exec/compiled.py).
"""
from __future__ import annotations

import threading
import time
from typing import Iterator, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnVector, ColumnarBatch, LazyRowCount, carry_host_stats, from_arrow,
    host_int, host_planes, to_arrow, round_capacity, rows_int, traced_rows,
)
from spark_rapids_tpu.exec import compiled
from spark_rapids_tpu.exec import cpu_backend as CPU
from spark_rapids_tpu.exec import fuse
from spark_rapids_tpu.expr.core import Alias, BoundRef, Cast, EvalCtx, Expression
from spark_rapids_tpu.expr.aggregates import CountAll
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.ops import radix as R
from spark_rapids_tpu.ops import repartition as RP
from spark_rapids_tpu.plan import nodes as P
from spark_rapids_tpu.runtime import faults as FLT
from spark_rapids_tpu.runtime import lifecycle as LC
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import trace as TR
from spark_rapids_tpu.runtime.obs.phases import device_mark, device_wait
from spark_rapids_tpu.runtime.semaphore import get_semaphore
from spark_rapids_tpu.runtime.task import TaskContext


class TpuExec:
    def __init__(self, plan: P.PlanNode, children: List["TpuExec"], conf):
        self.plan = plan
        self.children = children
        self.conf = conf
        self.metrics = M.MetricsRegistry(M.metrics_level_from_conf(conf))

    @property
    def schema(self) -> T.Schema:
        return self.plan.schema

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions if self.children else 1

    def execute_partition(self, ctx: TaskContext, pidx: int
                          ) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    def name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        lines = [f"{pad}{self.name()} <- {self.plan.describe()}"]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def span(self, metric):
        """Trace span + the paired GpuMetric timer as ONE instrumentation
        point (the NvtxWithMetrics contract): with no sink it returns the
        metric's own timer; a tracer gets an `ExecName.metricName`
        complete event on this task's track, the flight ring an entry,
        and a running jax.profiler capture a
        `rapids.ExecName.metricName` TraceAnnotation (trace._sinks)."""
        return TR.exec_span(self, metric)

    def _acquire(self, ctx: TaskContext) -> None:
        get_semaphore(self.conf).acquire_if_necessary(ctx)
        ctx.holds_device_data = True


def _split_rows(total: int, parts: int) -> List[tuple]:
    base = total // parts
    rem = total % parts
    out = []
    start = 0
    for i in range(parts):
        n = base + (1 if i < rem else 0)
        out.append((start, n))
        start += n
    return out


class InMemoryScanExec(TpuExec):
    """Local-mode source: slice a pyarrow table into partitions/batches and
    upload (reference HostColumnarToGpu-ish boundary)."""

    @property
    def num_partitions(self):
        return self.plan.num_partitions

    def execute_partition(self, ctx, pidx):
        table = self.plan.table
        start, n = _split_rows(table.num_rows, self.num_partitions)[pidx]
        max_rows = self.conf.get(C.MAX_READER_BATCH_SIZE_ROWS)
        out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        out_batches = self.metrics.metric(M.NUM_OUTPUT_BATCHES)
        copy_t = self.metrics.metric(M.COPY_TO_DEVICE_TIME)
        up_bytes = self.metrics.metric(M.UPLOAD_BYTES)
        off = 0
        while off < n or (n == 0 and off == 0):
            take = min(max_rows, n - off)
            chunk = table.slice(start + off, take)
            self._acquire(ctx)
            FLT.site("scan.decode")
            with self.span(copy_t):
                b = from_arrow(chunk, device=ctx.device)
            up_bytes.add(b.device_memory_size())
            yield b
            out_rows.add(take)
            out_batches.add(1)
            off += max(take, 1)
            if n == 0:
                break


def _note_scan_columns(ex: TpuExec) -> None:
    """numScanColumns / numScanColumnsPruned of a Parquet scan exec."""
    read = len(ex.plan.schema.fields)
    whole = len((ex.plan.narrowed_from or ex.plan).schema.fields)
    ex.metrics.metric(M.NUM_SCAN_COLUMNS).set(read)
    ex.metrics.metric(M.NUM_SCAN_COLUMNS_PRUNED).set(whole - read)


class ParquetScanExec(TpuExec):
    """Parquet scan: host-side read (pyarrow footer+decode) then one device
    upload per batch. Pushed-down filters prune hive-partition files at
    plan time and row groups by footer min/max statistics at execute time
    (reference GpuParquetScan.scala:673 filterBlocks). Reader strategies
    (reference MULTIFILE_READER_TYPE, GpuMultiFileReader):
      PERFILE       sequential row-group loads, no lookahead
      MULTITHREADED bounded prefetch pool overlapping decode with upload
      COALESCING    prefetch + host-side concat of row groups up to the
                    reader batch size, so each upload is one big batch
      AUTO          COALESCING (local files; no cloud path distinction)
    """

    def __init__(self, plan, children, conf):
        super().__init__(plan, children, conf)
        from spark_rapids_tpu.io.parquet_pruning import prune_partition_file
        pv = self.plan.partition_values
        paths = list(self.plan.paths)
        # snapshot: a later wrap_and_tag/explain of a sibling plan sharing
        # this scan object must not rewrite the filters under a
        # converted exec
        self._pushed = list(self.plan.pushed_filters)
        if pv and self._pushed:
            kept = [i for i in range(len(paths)) if prune_partition_file(
                pv[i], self.plan.schema, self._pushed)]
        else:
            kept = list(range(len(paths)))
        self._kept_files = kept
        _note_scan_columns(self)

    @property
    def num_partitions(self):
        return max(1, len(self._kept_files))

    def execute_partition(self, ctx, pidx):
        import pyarrow.parquet as pq
        from spark_rapids_tpu.io.parquet_pruning import prune_row_groups
        if not self._kept_files:
            return
        fidx = self._kept_files[pidx]
        path = self.plan.paths[fidx]
        decode_t = self.metrics.metric(M.DECODE_TIME)
        copy_t = self.metrics.metric(M.COPY_TO_DEVICE_TIME)
        up_bytes = self.metrics.metric(M.UPLOAD_BYTES)
        out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        rg_total = self.metrics.metric(M.NUM_ROW_GROUPS)
        rg_pruned = self.metrics.metric(M.NUM_ROW_GROUPS_PRUNED)
        read_bytes = self.metrics.metric(M.READ_BYTES)
        cols = getattr(self.plan, "file_columns", self.plan.columns)
        mode = str(self.conf.get(C.MULTIFILE_READER_TYPE)).upper()
        threads = 1 if mode == "PERFILE" \
            else self.conf.get(C.MULTIFILE_READER_THREADS)

        metadata = pq.ParquetFile(path).metadata
        groups, total = prune_row_groups(metadata, self._pushed)
        rg_total.add(total)
        rg_pruned.add(total - len(groups))
        for g in groups:
            read_bytes.add(metadata.row_group(g).total_byte_size)
        if not groups:
            if total:
                return  # every row group statically refuted
            groups = [-1]  # row-group-less file: read whole

        def load(g):
            # one ParquetFile per call: parquet-cpp FileReader is NOT
            # thread-safe and loads run on prefetch workers
            FLT.site("scan.decode")
            with self.span(decode_t):
                f = pq.ParquetFile(path)
                if g < 0:
                    return f.read(columns=cols)
                return f.read_row_group(g, columns=cols)

        # host decode of row group g+1.. overlaps device upload of g
        batch_rows = self.conf.get(C.MAX_READER_BATCH_SIZE_ROWS)
        tables = _prefetched(groups, load, threads, conf=self.conf)
        if mode in ("COALESCING", "AUTO"):
            tables = _host_coalesced(tables, batch_rows)
        for tbl in tables:
            tbl = self.plan.with_partition_cols(tbl, fidx)
            off = 0
            while off < tbl.num_rows or (tbl.num_rows == 0 and off == 0):
                chunk = tbl.slice(off, batch_rows)
                self._acquire(ctx)
                with self.span(copy_t):
                    b = from_arrow(chunk)
                up_bytes.add(b.device_memory_size())
                yield b
                out_rows.add(chunk.num_rows)
                off += max(chunk.num_rows, 1)
                if tbl.num_rows == 0:
                    break


def _host_coalesced(tables, target_rows: int):
    """Concat host tables until the target row count is reached, so one
    device upload carries many small row groups (COALESCING strategy)."""
    import pyarrow as pa
    pending, rows = [], 0
    for t in tables:
        pending.append(t)
        rows += t.num_rows
        if rows >= target_rows:
            yield pa.concat_tables(pending) if len(pending) > 1 else pending[0]
            pending, rows = [], 0
    if pending:
        yield pa.concat_tables(pending) if len(pending) > 1 else pending[0]


def _prefetched(items, load_fn, n_threads: int, conf=None):
    """Iterator over load_fn(item) with BOUNDED background lookahead on the
    process-wide host pool (reference MultiFileReaderThreadPool: host parse
    overlaps device upload/compute; lookahead is capped so a large input
    cannot buffer itself entirely into host memory, and the pool is shared
    by every scan instead of constructed per call)."""
    if n_threads <= 1 or len(items) <= 1:
        for it in items:
            yield load_fn(it)
        return
    from spark_rapids_tpu.runtime.host_pool import get_host_pool
    yield from get_host_pool(conf).map_ordered(load_fn, items,
                                               max_concurrency=n_threads)


def device_decode_stage_body() -> fuse.StageBody:
    """Decode-on-device as a fusable stage body: the fused trace's INPUT
    is the EncodedBatch pytree (raw chunk planes) and its first stage is
    the pallas_decode expansion, so downstream bodies (Filter, partial
    agg) compose after it and Scan→Filter→partial-agg stays ONE dispatch
    per batch over encoded bytes. The builder captures no exec state;
    already-decoded batches (replay/fallback paths) pass through — a
    trace-time structure distinction, not a runtime branch."""
    def build():
        from spark_rapids_tpu.ops import pallas_decode as PD

        def fn(batch, pid, carry):
            if isinstance(batch, ColumnarBatch):
                return batch, {}, carry
            return PD.decode_batch(batch), {}, carry
        return fn

    return fuse.StageBody(("device_decode",), build,
                          bounds_map=lambda bs: list(bs),
                          name="DeviceDecode")


class EncodedParquetSourceExec(TpuExec):
    """Leaf half of the device-decode scan pair: footer read + partition
    -file and row-group pruning exactly as ParquetScanExec, but instead
    of host-decoding through pyarrow it extracts the still-ENCODED
    column chunk bytes (io/encoded.py) and uploads them as EncodedBatch
    planes — what crosses the host->device link is the compressed
    encoding, not decoded plates. Columns outside the supported matrix
    host-decode HERE (the per-column fallback) and ride inside the
    EncodedBatch as ready ColumnVectors; reasons accumulate in
    `fallback_columns` for explain/history. DeviceDecodeScanExec is the
    paired unary exec expanding the planes inside the fused stage body
    (reference: the host half of libcudf's GPU Parquet reader —
    gpu::DecodePageHeaders feeding gpuDecodePages)."""

    def __init__(self, plan, children, conf):
        super().__init__(plan, children, conf)
        from spark_rapids_tpu.io.parquet_pruning import prune_partition_file
        pv = plan.partition_values
        paths = list(plan.paths)
        self._pushed = list(plan.pushed_filters)
        if pv and self._pushed:
            kept = [i for i in range(len(paths)) if prune_partition_file(
                pv[i], plan.schema, self._pushed)]
        else:
            kept = list(range(len(paths)))
        self._kept_files = kept
        _note_scan_columns(self)
        #: column -> fallback reason (plan-time probe + execute-time
        #: page surprises): the explain/history surface
        self.fallback_columns: dict = {}
        if kept:
            # static footer probe of the first kept file: fallback
            # reasons are visible in explain BEFORE the query runs
            # (page-level surprises still merge in at execute time)
            from spark_rapids_tpu.io import encoded as ENC
            try:
                self.fallback_columns.update(ENC.probe_support(
                    paths[kept[0]], self._file_fields()))
            except Exception:  # noqa: BLE001 - probe is advisory only
                pass

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        note = ""
        if self.fallback_columns:
            note = " host-fallback{" + ", ".join(
                f"{k}: {v}" for k, v in
                sorted(self.fallback_columns.items())) + "}"
        lines = [f"{pad}{self.name()}{note} <- {self.plan.describe()}"]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    @property
    def num_partitions(self):
        return max(1, len(self._kept_files))

    def _file_fields(self):
        n_part = len(self.plan.partition_fields())
        fields = list(self.plan.schema.fields)
        return fields[: len(fields) - n_part] if n_part else fields

    def _partition_columns(self, fidx, n, cap):
        """Constant partition-value columns as ready (decoded) planes —
        the same arrays with_partition_cols + from_arrow would build."""
        import pyarrow as pa
        from spark_rapids_tpu.columnar.batch import column_from_arrow
        from spark_rapids_tpu.io import encoded as ENC
        out = []
        if not self.plan.partition_values:
            return out
        vals = self.plan.partition_values[fidx]
        for f in self.plan.partition_fields():
            v = vals.get(f.name)
            if v is not None and f.dtype == T.INT64:
                v = int(v)
            arr = pa.array([v] * n, type=T.to_arrow(f.dtype))
            cv = column_from_arrow(arr, f.dtype, cap)
            out.append(ENC.EncodedColumn("decoded", f.dtype, {}, (),
                                         cv=cv, bounds=cv.bounds))
        return out

    def execute_partition(self, ctx, pidx):
        import pyarrow as pa
        import pyarrow.parquet as pq
        from spark_rapids_tpu.columnar.batch import column_from_arrow
        from spark_rapids_tpu.io import encoded as ENC
        from spark_rapids_tpu.io.parquet_pruning import prune_row_groups
        if not self._kept_files:
            return
        fidx = self._kept_files[pidx]
        path = self.plan.paths[fidx]
        decode_t = self.metrics.metric(M.DECODE_TIME)
        copy_t = self.metrics.metric(M.COPY_TO_DEVICE_TIME)
        up_bytes = self.metrics.metric(M.UPLOAD_BYTES)
        out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        out_batches = self.metrics.metric(M.NUM_OUTPUT_BATCHES)
        rg_total = self.metrics.metric(M.NUM_ROW_GROUPS)
        rg_pruned = self.metrics.metric(M.NUM_ROW_GROUPS_PRUNED)
        read_bytes = self.metrics.metric(M.READ_BYTES)
        enc_bytes = self.metrics.metric(M.ENCODED_BYTES)
        dec_bytes = self.metrics.metric(M.DECODED_BYTES)
        fb_cols = self.metrics.metric(M.NUM_DECODE_FALLBACK_COLUMNS)
        fields = self._file_fields()

        pf = pq.ParquetFile(path)
        metadata = pf.metadata
        groups, total = prune_row_groups(metadata, self._pushed)
        rg_total.add(total)
        rg_pruned.add(total - len(groups))
        for g in groups:
            read_bytes.add(metadata.row_group(g).total_byte_size)
        if not groups:
            if total:
                return  # every row group statically refuted: nothing
                # read, nothing uploaded (pruning composes)
            # row-group-less / empty file: host read, all-decoded batch
            FLT.site("scan.decode")
            with self.span(decode_t):
                tbl = pf.read(columns=[f.name for f in fields] or None)
            tbl = self.plan.with_partition_cols(tbl, fidx)
            self._acquire(ctx)
            with self.span(copy_t):
                b = from_arrow(tbl)
            up_bytes.add(b.device_memory_size())
            cols = [ENC.EncodedColumn("decoded", c.dtype, {}, (), cv=c,
                                      bounds=c.bounds) for c in b.columns]
            yield ENC.EncodedBatch(cols, rows_int(b.num_rows), b.capacity)
            out_rows.add(rows_int(b.num_rows))
            out_batches.add(1)
            return

        batch_rows = self.conf.get(C.MAX_READER_BATCH_SIZE_ROWS)
        max_bits = min(32, int(self.conf.get(C.DEVICE_DECODE_MAX_BITS)))
        delta_ok = bool(self.conf.get(C.DEVICE_DECODE_DELTA))
        hbs = ENC.read_encoded_batches(path, metadata, groups, fields,
                                       batch_rows, max_bits, delta_ok)
        while True:
            FLT.site("scan.decode")
            with self.span(decode_t):
                hb = next(hbs, None)
            if hb is None:
                return
            self.fallback_columns.update(hb.fallback)
            decoded = {}
            fb_idx = [i for i, c in enumerate(hb.columns) if c is None]
            if fb_idx:
                fb_cols.add(len(fb_idx))
                names = [fields[i].name for i in fb_idx]
                with self.span(decode_t):
                    parts = [pf.read_row_group(g, columns=names)
                             for g in hb.groups]
                    tbl = (pa.concat_tables(parts) if len(parts) > 1
                           else parts[0]).combine_chunks()
            self._acquire(ctx)
            with self.span(copy_t):
                for j, i in enumerate(fb_idx):
                    col = tbl.column(j)
                    arr = col.chunk(0) if col.num_chunks \
                        else col.combine_chunks()
                    decoded[i] = column_from_arrow(arr, fields[i].dtype,
                                                   hb.cap)
                eb = ENC.upload(hb, decoded)
            up_bytes.add(eb.device_memory_size())
            eb.columns.extend(
                self._partition_columns(fidx, hb.num_rows, hb.cap))
            enc_bytes.add(hb.encoded_bytes)
            # decoded footprint is static (cap x itemsize): recorded HERE
            # because on the fused path the decode body runs inside
            # FusedStageExec's dispatch, not DeviceDecodeScanExec's
            dec_bytes.add(eb.decoded_size())
            out_rows.add(hb.num_rows)
            out_batches.add(1)
            yield eb


class DeviceDecodeScanExec(TpuExec):
    """Unary half of the device-decode scan pair (the PR's tentpole):
    expands the child's EncodedBatches into decoded ColumnarBatches ON
    DEVICE via a fuse.StageBody, so stage_fusion composes Filter /
    partial-agg bodies behind the decode into one dispatch per batch
    over encoded bytes (the cuDF gpuDecodePages analog). The kernel
    cost auditor sees the encoded planes as the dispatch inputs, so the
    roofline credits encoded-input bytes and decode time lands in
    opTime -> device_compute: the host_decode bucket collapses
    structurally for device-decoded scans."""

    def stage_body(self) -> fuse.StageBody:
        return device_decode_stage_body()

    def execute_partition(self, ctx, pidx):
        op_t = self.metrics.metric(M.OP_TIME)
        out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        out_batches = self.metrics.metric(M.NUM_OUTPUT_BATCHES)
        body = self.stage_body()
        fn = fuse.fused(body.key, body.builder)
        carry = body.init_carry()
        pid = jnp.int32(pidx)
        for batch in self.children[0].execute_partition(ctx, pidx):
            self._acquire(ctx)
            n = batch.num_rows  # host int on the encoded source path
            with self.span(op_t):
                out, errs, carry = fn(batch, pid, carry)
            compiled.raise_errors(errs)
            if isinstance(n, int):
                # keep the row count host-side: the source knew it
                # exactly, so no device sync is ever needed for it
                out = ColumnarBatch(out.columns, n, out.row_mask)
            out_rows.add(n if isinstance(n, int) else out.num_rows)
            out_batches.add(1)
            yield out


class TextScanExec(TpuExec):
    """CSV/JSON/ORC scan: prefetched host parse, chunked device upload
    (reference GpuCSVScan / GpuJsonScan / GpuOrcScan MULTITHREADED)."""

    @property
    def num_partitions(self):
        return max(1, len(self.plan.paths))

    def execute_partition(self, ctx, pidx):
        decode_t = self.metrics.metric(M.DECODE_TIME)
        copy_t = self.metrics.metric(M.COPY_TO_DEVICE_TIME)
        up_bytes = self.metrics.metric(M.UPLOAD_BYTES)
        out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        FLT.site("scan.decode")
        with self.span(decode_t):
            table = self.plan.read_host(self.plan.paths[pidx])
        batch_rows = self.conf.get(C.MAX_READER_BATCH_SIZE_ROWS)
        n = table.num_rows
        off = 0
        while off < n or (n == 0 and off == 0):
            take = min(batch_rows, n - off)
            chunk = table.slice(off, take)
            self._acquire(ctx)
            with self.span(copy_t):
                b = from_arrow(chunk)
            up_bytes.add(b.device_memory_size())
            yield b
            out_rows.add(take)
            off += max(take, 1)
            if n == 0:
                break


class CachedScanExec(TpuExec):
    """Materializes the child once into HBM-resident batches stored on the
    CachedRelation plan node (shared across collects of the same
    DataFrame); later scans stream straight from device memory.

    Under a mesh (parallel/mesh.placement_devices) partition p's merged
    batch is built ON device p mod n and committed to it, so a table
    larger than one chip is never whole on one; the shards of a column
    are made uniform planes (one capacity, one vocabulary a string
    column, validity on all or none) so that a sharded stage consumes
    them in place (`resident_shards`). Every other consumer gets a
    partition through `execute_partition`, moved to the default device
    when it lives on another chip (counted in meshPutBytes)."""

    _lock = threading.Lock()

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def num_partitions(self):
        if self.plan.materialized is not None:
            return len(self.plan.materialized)
        return self.children[0].num_partitions

    def _load_partition(self, p: int, dev) -> Optional[ColumnarBatch]:
        """Partition p of the child as ONE batch: every query over the
        cache then costs a fixed handful of fused dispatches instead of
        one chain per source chunk. On `dev` when the cache is placed."""
        child = self.children[0]
        with TaskContext(partition_id=p) as tctx:
            tctx.device = dev
            batches = list(child.execute_partition(tctx, p))
        if not batches:
            return None
        if dev is None:
            return K.compact_batch(K.concat_batches(batches))
        with jax.default_device(dev):
            # a source that took no notice of tctx.device left its
            # batches on the default device: bring them over one at a
            # time (already in place, device_put copies nothing)
            batches = [ColumnarBatch(
                jax.device_put(b.columns, dev), b.num_rows,
                None if b.row_mask is None
                else jax.device_put(b.row_mask, dev)) for b in batches]
            return K.compact_batch(K.concat_batches(batches))

    def _materialize(self):
        from spark_rapids_tpu.parallel.mesh import placement_devices
        from spark_rapids_tpu.runtime.memory import SpillableColumnarBatch
        with CachedScanExec._lock:
            if self.plan.materialized is None:
                devs = placement_devices(self.conf)
                nparts = self.children[0].num_partitions

                def load(p):
                    return self._load_partition(
                        p, devs[p % len(devs)] if devs else None)

                if len(devs) > 1 and nparts > 1:
                    # one upload stream a chip: the row ranges decode and
                    # upload side by side, each into its own HBM
                    from spark_rapids_tpu.runtime.host_pool import (
                        get_host_pool,
                    )
                    merged = list(get_host_pool(self.conf).map_ordered(
                        load, range(nparts), max_concurrency=len(devs)))
                else:
                    merged = [load(p) for p in range(nparts)]
                if devs:
                    merged = _uniform_shards(merged, devs)
                out = []
                for p, m in enumerate(merged):
                    if m is None:
                        out.append([])
                        continue
                    _attach_column_stats(m)
                    # Registered spillable: under HBM pressure the cache
                    # pages out to host/disk instead of OOMing.
                    out.append([SpillableColumnarBatch(m)])
                self.plan.placed_on = tuple(devs)
                self.plan.materialized = out
        return self.plan.materialized

    def resident_shards(self, pids) -> Optional[List[ColumnarBatch]]:
        """The merged batch of each partition in `pids`, where it lives
        (no copy), for a sharded stage that computes where the shards
        are; None if the cache is not placed over a mesh or a partition
        is not exactly one batch."""
        mat = self._materialize()
        if not self.plan.placed_on:
            return None
        out = []
        for p in pids:
            if len(mat[p]) != 1:
                return None
            out.append(mat[p][0].get_batch())
        return out

    def execute_partition(self, ctx, pidx):
        mat = self._materialize()
        home = self.plan.placed_on
        for sb in mat[pidx]:
            b = sb.get_batch()
            if len(home) > 1 and pidx % len(home):
                # an operator that knows no mesh computes on the default
                # device: the shard crosses the interconnect, each query
                self.metrics.metric(M.MESH_PUT_BYTES).add(
                    b.device_memory_size())
                cols = jax.device_put(b.columns, home[0])
                carry_host_stats(b.columns, cols)
                b = ColumnarBatch(cols, b.num_rows, b.row_mask)
            yield b


def _uniform_shards(shards: List[Optional[ColumnarBatch]], devs
                    ) -> List[Optional[ColumnarBatch]]:
    """Make the per-device shards of a placed cache uniform planes, each
    staying on its device: one capacity; a string column dictionary-coded
    against ONE vocabulary for the whole table (replicated on every
    chip, int32 codes a shard), so that equal strings have equal codes
    in every shard; a validity plane on every shard of a column or on
    none. Shard p lives on devs[p % len(devs)]."""
    live = [(p, b) for p, b in enumerate(shards) if b is not None]
    if len(live) < 2:
        return shards
    cap = max(b.capacity for _p, b in live)
    ncols = live[0][1].num_cols
    cols_of = {p: list(b.columns) for p, b in live}

    def on(p):
        return jax.default_device(devs[p % len(devs)])

    for p, b in live:
        if b.capacity != cap:
            with on(p):
                cols_of[p] = [_resize_col(c, cap) for c in cols_of[p]]
    for j in range(ncols):
        col_j = [cols_of[p][j] for p, _b in live]
        if all(c.is_dict for c in col_j):
            uoff, ubytes, remaps = K.unify_vocabs(col_j)
            unique = all(c.dict_unique for c in col_j)
            for (p, _b), c, remap in zip(live, col_j, remaps):
                dev = devs[p % len(devs)]
                codes = c.data["codes"]
                if len(remap) and not np.array_equal(
                        remap, np.arange(len(remap))):
                    with on(p):
                        codes = jnp.asarray(remap)[
                            jnp.clip(codes, 0, len(remap) - 1)]
                cols_of[p][j] = ColumnVector(
                    c.dtype, {"codes": codes,
                              "dict_offsets": jax.device_put(uoff, dev),
                              "dict_bytes": jax.device_put(ubytes, dev)},
                    c.validity, dict_unique=unique)
        if any(cols_of[p][j].validity is not None for p, _b in live) \
                and not cols_of[live[0][0]][j].is_nested:
            for p, b in live:
                c = cols_of[p][j]
                if c.validity is None:
                    with on(p):
                        cols_of[p][j] = ColumnVector(
                            c.dtype, c.data,
                            c.validity_or_default(b.num_rows),
                            dict_unique=c.dict_unique)
    out = list(shards)
    for p, b in live:
        out[p] = ColumnarBatch(cols_of[p], b.num_rows, b.row_mask)
    return out


def _attach_column_stats(batch: ColumnarBatch) -> None:
    """Cache-time column stats (the ParquetCachedBatchSerializer-stats
    analog): one bulk fetch of per-int-column min/max at materialization,
    carried as ColumnVector.bounds so later radix packing over these
    columns skips its per-batch device range probe (a ~90ms sync)."""
    idxs, pending = [], []
    for i, c in enumerate(batch.columns):
        if c.is_dict or c.is_nested or c.is_string:
            continue
        if not isinstance(c.dtype, (T.Int8Type, T.Int16Type, T.Int32Type,
                                    T.Int64Type, T.DateType,
                                    T.TimestampType, T.DecimalType)):
            continue
        v = c.data.astype(jnp.int64)
        valid = c.validity_or_default(batch.num_rows)
        lo = jnp.min(jnp.where(valid, v, jnp.int64(2**62)))
        hi = jnp.max(jnp.where(valid, v, -jnp.int64(2**62)))
        idxs.append(i)
        pending.extend([lo, hi])
    if not idxs:
        return
    vals = host_planes(pending, "range")
    for j, i in enumerate(idxs):
        lo, hi = int(vals[2 * j]), int(vals[2 * j + 1])
        if lo <= hi:
            batch.columns[i].bounds = (lo, hi)


class RangeExec(TpuExec):
    @property
    def num_partitions(self):
        return self.plan.num_partitions

    def execute_partition(self, ctx, pidx):
        p = self.plan
        total = max(0, -(-(p.end - p.start) // p.step))
        start_i, n = _split_rows(total, self.num_partitions)[pidx]
        self._acquire(ctx)
        max_rows = self.conf.get(C.MAX_READER_BATCH_SIZE_ROWS)
        off = 0
        while off < n or (n == 0 and off == 0):
            take = min(max_rows, n - off) if n else 0
            cap = round_capacity(max(take, 1))
            base = p.start + (start_i + off) * p.step
            vals = base + jnp.arange(cap, dtype=jnp.int64) * p.step
            yield ColumnarBatch(
                [ColumnVector(T.INT64, vals, jnp.arange(cap) < take)], take)
            off += max(take, 1)
            if n == 0:
                break


# ---------------------------------------------------------------------------
# Stage bodies (whole-stage vertical fusion, exec/stage_fusion.py)
#
# Each fusable exec separates its traced per-batch body from its driver
# loop as a fuse.StageBody with the uniform signature
#     fn(batch, pid, carry) -> (batch, errors, carry)
# so a planner pass can compose a Scan→Filter→Project→partial-agg chain
# into ONE dispatch per batch. Builders are module-level and capture only
# expressions/static config — never the exec (the fuse-cache pinning
# hazard documented on _AggKernels).
# ---------------------------------------------------------------------------

def _project_bounds_map(exprs):
    """Column-stat bounds across a projection: passthrough refs carry
    their input column's bounds (the host half of compiled.carry_bounds)."""
    def bmap(in_bounds):
        out = []
        for e in exprs:
            inner = e.children[0] if isinstance(e, Alias) else e
            if isinstance(inner, BoundRef) and inner.index < len(in_bounds):
                out.append(in_bounds[inner.index])
            else:
                out.append(None)
        return out
    return bmap


def matched_columns(exprs) -> list:
    """The columns that a string match over a byte plane in `exprs`
    (expr/strings: plane_match) reads directly, in order."""
    from spark_rapids_tpu.expr.strings import plane_matches
    return sorted({m.children[0].index for e in exprs
                   for m in plane_matches(e)
                   if isinstance(m.children[0], BoundRef)})


def holds_string_match(exprs) -> bool:
    from spark_rapids_tpu.expr.strings import plane_matches
    return any(plane_matches(e) for e in exprs)


def stage_matches_strings(node) -> bool:
    """Does this Filter or Project match strings over a byte plane?"""
    if isinstance(node, FilterExec):
        return holds_string_match([node.plan.condition])
    return isinstance(node, ProjectExec) \
        and holds_string_match(node.plan.exprs)


def meets_flat_string(batch: ColumnarBatch) -> bool:
    """What a FUSED stage that matches strings looks for in each batch: over
    dictionary columns the match is a look-up a row and stays in the
    stage's one program; a flat column makes it passes over the column's
    whole byte plane, and the stage then runs its operators apart
    (FusedStageExec and HashAggregateExec, at run time, from what the
    batch shows): the Filter alone takes the column's width from the host
    (`match_widths`) and is timed (`_FilterRun`). In a batch still
    encoded (a chain rooted at a device-decode scan) the strings are the
    columns the host decoded, riding along (`EncodedColumn.cv`)."""
    cols = (getattr(c, "cv", c) for c in batch.columns)
    return any(getattr(c, "is_string", False) and not c.is_dict
               for c in cols)


def match_widths(matched, columns) -> tuple:
    """((column, width), ...) of the flat string columns among `matched`
    (`matched_columns`): the host's bound on their longest string
    (ColumnVector.str_width), rounded up to the doubling window that
    ops/strmatch takes (64 to 127 bytes are one program). Host stats do
    not cross a jit boundary, so a stage body that matches strings takes
    them as part of its key and stamps them back inside its trace
    (`_stamp_widths`); a column without the stamp is left out and the
    kernel falls back to the plane's own size."""
    return tuple((i, (1 << columns[i].str_width.bit_length()) - 1)
                 for i in matched if columns[i].is_string
                 and not columns[i].is_dict
                 and columns[i].str_width is not None)


class _WidthKeyed:
    """A lone stage's program: its body keyed by `match_widths`, built
    again only when a batch brings other widths."""

    def __init__(self, exprs, make_body):
        self.matched = matched_columns(exprs)
        self._make, self._widths, self._fn = make_body, None, None

    def fn(self, columns):
        widths = match_widths(self.matched, columns)
        if self._fn is None or widths != self._widths:
            body = self._make(widths)
            self._fn = fuse.fused(body.key, body.builder)
            self._widths = widths
        return self._fn


def _stamp_widths(batch: ColumnarBatch, str_widths) -> None:
    for i, w in str_widths:
        batch.columns[i].str_width = w


def project_stage_body(exprs, ansi: bool, trivial=None,
                       str_widths=()) -> fuse.StageBody:
    if trivial is not None:
        idx = tuple(trivial)

        def build_trivial():
            def fn(batch, pid, carry):
                return (ColumnarBatch([batch.columns[i] for i in idx],
                                      batch.num_rows, batch.row_mask),
                        {}, carry)
            return fn

        return fuse.StageBody(
            ("project_trivial", idx), build_trivial,
            bounds_map=lambda bs: [bs[i] if i < len(bs) else None
                                   for i in idx],
            name="Project")

    from spark_rapids_tpu.plan.overrides import _contains_project_only
    needs_part_ctx = any(_contains_project_only(e) for e in exprs)

    def build():
        def fn(batch, pid, row_base):
            _stamp_widths(batch, str_widths)
            ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                           batch.capacity, ansi, live=batch.live_mask(),
                           partition_id=pid, row_base=row_base)
            cols = [e.eval_tpu(ectx) for e in exprs]
            if needs_part_ctx:  # only pay the count when ids need it
                row_base = row_base + jnp.sum(
                    batch.live_mask().astype(jnp.int64))
            return (ColumnarBatch(cols, batch.num_rows, batch.row_mask),
                    dict(ectx.errors), row_base)
        return fn

    key = ("project", tuple(e.fingerprint() for e in exprs), ansi,
           needs_part_ctx, str_widths)
    return fuse.StageBody(key, build, bounds_map=_project_bounds_map(exprs),
                          has_carry=needs_part_ctx, name="Project")


def filter_stage_body(cond, ansi: bool, str_widths=()) -> fuse.StageBody:
    def build():
        def fn(batch, pid, carry):
            _stamp_widths(batch, str_widths)
            ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                           batch.capacity, ansi, live=batch.live_mask())
            pred = cond.eval_tpu(ectx)
            # validity=None means "valid on every live row"; the live
            # rows of a masked batch (chained filter, exchange output)
            # sit at positions >= live_count, so arange<num_rows would
            # silently drop them — use the live mask instead.
            valid = (pred.validity if pred.validity is not None
                     else ectx.row_mask)
            mask = pred.data.astype(jnp.bool_) & valid
            return K.mask_filter_batch(batch, mask), dict(ectx.errors), carry
        return fn

    # a filter's output columns are 1:1 row subsets of its input: bounds
    # (host metadata, valid under any row subset) pass straight through
    return fuse.StageBody(("filter", cond.fingerprint(), ansi, str_widths),
                          build,
                          bounds_map=lambda bs: list(bs), name="Filter")


def expand_stage_body(proj_exprs, n_cols: int) -> fuse.StageBody:
    """All projections of an Expand evaluated and stacked in ONE traced
    computation (the unfused exec dispatches once per projection). Output
    capacity is n_proj * input capacity with a tiled selection mask; only
    built for fixed-width output schemas (stage_fusion gates strings —
    cross-projection vocab unification cannot run inside a trace)."""
    nproj = len(proj_exprs)

    def build():
        def fn(batch, pid, carry):
            live = batch.live_mask()
            nr = traced_rows(batch.num_rows)
            errs = {}
            per_proj = []
            for exprs in proj_exprs:
                ectx = EvalCtx(batch.columns, nr, batch.capacity, False,
                               live=live)
                per_proj.append([e.eval_tpu(ectx) for e in exprs])
                errs.update(ectx.errors)
            out_cols = []
            for ci in range(n_cols):
                cols = [p[ci] for p in per_proj]
                data = jnp.concatenate([c.data for c in cols])
                # validity=None means "valid on every LIVE row"; a masked
                # input (chained filter) keeps live rows at positions >=
                # live_count, so arange<num_rows would null them — use the
                # live mask as the default plane
                valid = jnp.concatenate(
                    [c.validity if c.validity is not None else live
                     for c in cols])
                out_cols.append(ColumnVector(cols[0].dtype, data, valid))
            mask = jnp.concatenate([live] * nproj)
            count = jnp.sum(mask.astype(jnp.int32))
            return (ColumnarBatch(out_cols, LazyRowCount(count), mask),
                    errs, carry)
        return fn

    key = ("expand_stage",
           tuple(tuple(e.fingerprint() for e in p) for p in proj_exprs))
    return fuse.StageBody(key, build,
                          bounds_map=lambda bs: [None] * n_cols,
                          name="Expand")


def limit_stage_body(n: int) -> fuse.StageBody:
    """Device-side LIMIT: rows past the remaining budget are masked dead;
    the budget rides as a device carry. The fused driver fetches the
    carry per batch to stop consuming input once it hits zero (exhausts=
    True) — the same one-scalar-per-batch sync the unfused LimitExec
    already pays materializing each batch's row count."""
    def build():
        def fn(batch, pid, remaining):
            live = batch.live_mask()
            pos = jnp.cumsum(live.astype(jnp.int64))
            keep = live & (pos <= remaining)
            taken = jnp.sum(keep.astype(jnp.int64))
            count = jnp.sum(keep.astype(jnp.int32))
            return (ColumnarBatch(batch.columns, LazyRowCount(count), keep),
                    {}, jnp.maximum(remaining - taken, 0))
        return fn

    # n reaches the trace only as the carried device scalar, so one cache
    # entry serves every LIMIT value (no per-n recompiles)
    return fuse.StageBody(("limit_stage",), build,
                          carry_init=lambda: jnp.int64(n),
                          bounds_map=lambda bs: list(bs),
                          has_carry=True, exhausts=True, name="Limit")


class ProjectExec(TpuExec):
    def _trivial_indices(self):
        """Pure column selection (only BoundRef / Alias(BoundRef)) costs no
        kernel at all: planes are shared, just re-listed."""
        idx = []
        for e in self.plan.exprs:
            inner = e.children[0] if isinstance(e, Alias) else e
            if isinstance(inner, BoundRef) and inner.dtype == e.data_type():
                idx.append(inner.index)
            else:
                return None
        return idx

    def stage_body(self, str_widths=()) -> fuse.StageBody:
        return project_stage_body(self.plan.exprs,
                                  self.conf.get(C.ANSI_ENABLED),
                                  trivial=self._trivial_indices(),
                                  str_widths=str_widths)

    def execute_partition(self, ctx, pidx):
        op_t = self.metrics.metric(M.OP_TIME)
        exprs = self.plan.exprs
        trivial = self._trivial_indices()
        if trivial is not None:
            for batch in self.children[0].execute_partition(ctx, pidx):
                yield ColumnarBatch([batch.columns[i] for i in trivial],
                                    batch.num_rows, batch.row_mask)
            return

        stage = _WidthKeyed(exprs, self.stage_body)
        row_base = self.stage_body().init_carry()
        pid = jnp.int32(pidx)
        for batch in self.children[0].execute_partition(ctx, pidx):
            self._acquire(ctx)
            fn = stage.fn(batch.columns)
            with self.span(op_t):
                out, errs, row_base = fn(batch, pid, row_base)
            compiled.raise_errors(errs)
            compiled.carry_bounds(exprs, batch.columns, out.columns)
            yield out


def _string_match_bytes(col: ColumnVector, num_rows) -> int:
    """stringMatchBytes of one matched column, from sizes the host has."""
    if col.is_dict:
        return int(col.data["dict_bytes"].shape[0]) \
            + 4 * (col.dict_size + 1) + 4 * col.capacity
    rows = num_rows if isinstance(num_rows, int) else col.capacity
    live = col.str_bytes if col.str_bytes is not None \
        else int(col.data["bytes"].shape[0])
    return live + 4 * (rows + 1)


class _FilterRun:
    """One partition's runs of a Filter's program for `owner`: a FilterExec,
    or the HashAggregateExec that took the Filter into its update kernel
    and runs it apart where it meets a flat column. Where the condition
    matches a column's byte plane directly the program is timed as the
    match: stringMatchTime its enqueue, stringMatchDeviceTime the device's
    time for it, stringMatchBytes what it had to read."""

    def __init__(self, owner, cond):
        ansi = owner.conf.get(C.ANSI_ENABLED)
        self.stage = _WidthKeyed(
            [cond], lambda widths: filter_stage_body(cond, ansi, widths))
        self.owner = owner
        if self.stage.matched:
            m = owner.metrics
            self.match_t = m.metric(M.STRING_MATCH_TIME)
            self.match_dev_t = m.metric(M.STRING_MATCH_DEVICE_TIME)
            self.match_bytes = m.metric(M.STRING_MATCH_BYTES)

    def __call__(self, batch, pid, carry):
        fn = self.stage.fn(batch.columns)
        matched = self.stage.matched
        if not matched:
            return fn(batch, pid, carry)
        t0 = time.perf_counter_ns()
        with self.owner.span(self.match_t):
            out, errs, carry = fn(batch, pid, carry)
        device_mark(self.match_dev_t, out.row_mask, t0)
        self.match_bytes.add(sum(
            _string_match_bytes(batch.columns[i], batch.num_rows)
            for i in matched))
        return out, errs, carry


class FilterExec(TpuExec):
    """Predicate eval + compaction fused into ONE jitted computation per
    batch; the surviving-row count stays on device (LazyRowCount). A
    condition that matches strings over a byte plane is timed as the
    match (`_FilterRun`)."""

    def stage_body(self, str_widths=()) -> fuse.StageBody:
        return filter_stage_body(self.plan.condition,
                                 self.conf.get(C.ANSI_ENABLED), str_widths)

    def tree_string(self, indent: int = 0) -> str:
        head, nl, rest = super().tree_string(indent).partition("\n")
        if holds_string_match([self.plan.condition]):
            head += " [string match: own stage]"
        return f"{head}{nl}{rest}"

    def execute_partition(self, ctx, pidx):
        op_t = self.metrics.metric(M.FILTER_TIME)
        out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        run = _FilterRun(self, self.plan.condition)
        carry = self.stage_body().init_carry()
        pid = jnp.int32(pidx)
        for batch in self.children[0].execute_partition(ctx, pidx):
            self._acquire(ctx)
            with self.span(op_t):
                out, errs, carry = run(batch, pid, carry)
            compiled.raise_errors(errs)
            # column-stat bounds are host metadata (not pytree leaves):
            # a filter's output columns are 1:1 row subsets of its input
            carry_host_stats(batch.columns, out.columns)
            out_rows.add(out.num_rows)
            yield out


class LimitExec(TpuExec):
    def stage_body(self) -> fuse.StageBody:
        return limit_stage_body(self.plan.n)

    def execute_partition(self, ctx, pidx):
        remaining = self.plan.n
        for batch in self.children[0].execute_partition(ctx, pidx):
            if remaining <= 0:
                break
            if batch.row_mask is not None:
                batch = K.compact_batch(batch)
            if batch.num_rows <= remaining:
                remaining -= batch.num_rows
                yield batch
            else:
                self._acquire(ctx)
                yield K.slice_batch(batch, 0, remaining)
                remaining = 0


class UnionExec(TpuExec):
    """Concatenate children partition-spaces; each child's output is cast to
    the union schema (reference GpuUnionExec)."""

    @property
    def num_partitions(self):
        return sum(c.num_partitions for c in self.children)

    def _cast_exprs(self, child_schema):
        out = []
        for i, (f_out, f_in) in enumerate(zip(self.plan.schema.fields, child_schema.fields)):
            ref = BoundRef(i, f_in.dtype, f_in.name)
            out.append(ref if f_in.dtype == f_out.dtype else Cast(ref, f_out.dtype))
        return out

    def execute_partition(self, ctx, pidx):
        for child in self.children:
            if pidx < child.num_partitions:
                exprs = self._cast_exprs(child.schema)
                needs_cast = any(isinstance(e, Cast) for e in exprs)
                for batch in child.execute_partition(ctx, pidx):
                    if needs_cast:
                        self._acquire(ctx)
                        yield compiled.run_projection(exprs, batch)
                    else:
                        yield batch
                return
            pidx -= child.num_partitions
        raise IndexError(pidx)


class ExpandExec(TpuExec):
    def _proj_exprs(self):
        out_types = self.plan.schema.types
        return [[e if e.data_type() == dt else Cast(e, dt)
                 for e, dt in zip(proj, out_types)]
                for proj in self.plan.projections]

    def stage_body(self) -> fuse.StageBody:
        return expand_stage_body(self._proj_exprs(),
                                 len(self.plan.schema.types))

    def execute_partition(self, ctx, pidx):
        exp_t = self.metrics.metric(M.EXPAND_TIME)
        exp_rows = self.metrics.metric(M.EXPAND_ROWS)
        projections = self._proj_exprs()
        for batch in self.children[0].execute_partition(ctx, pidx):
            self._acquire(ctx)
            for exprs in projections:
                # a lazy count is not forced for the counter's sake: it
                # joins the metric's deferred list and counts once
                # something else has read it
                exp_rows.add(batch.num_rows)
                with self.span(exp_t):
                    out = compiled.run_projection(exprs, batch)
                yield out


class ShuffleFileScanExec(TpuExec):
    """Reads a cross-process shuffle directory: each reduce partition
    streams its kudo frames straight onto the device (reference: shuffle
    reader fetching map outputs)."""

    @property
    def num_partitions(self):
        return max(1, self.plan.n_reduce)

    def execute_partition(self, ctx, pidx):
        from spark_rapids_tpu.shuffle.exchange_files import (
            read_partition_batches,
        )
        copy_t = self.metrics.metric(M.COPY_TO_DEVICE_TIME)
        up_bytes = self.metrics.metric(M.UPLOAD_BYTES)
        out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        self._acquire(ctx)
        it = read_partition_batches(self.plan.root, pidx)
        while True:
            with self.span(copy_t):
                batch = next(it, None)
            if batch is None:
                return
            up_bytes.add(batch.device_memory_size())
            out_rows.add(rows_int(batch.num_rows))
            yield batch


class GenerateExec(TpuExec):
    """explode / posexplode over array and map columns, incl. _outer
    (reference GpuGenerateExec.scala).

    TPU-first: the output stays at the CHILD planes' static capacity — the
    generated column IS the child planes (zero copy), parent columns gather
    by an element->row segment map, and liveness is a selection mask
    (elements of dead/null parent rows are masked, not compacted). The
    outer variant emits a second masked batch carrying one null-generated
    row per empty/null input instead of rebuilding offsets."""

    def execute_partition(self, ctx, pidx):
        op_t = self.metrics.metric(M.OP_TIME)
        out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        gen = self.plan.generator
        src = gen.children[0]
        is_map = isinstance(src.data_type(), T.MapType)
        position = bool(getattr(gen, "position", False))
        outer = bool(gen.outer)

        def build():
            def fn(batch):
                ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                               batch.capacity, False, live=batch.live_mask())
                arr = src.eval_tpu(ectx)
                cap = batch.capacity
                off = arr.data["offsets"][: cap + 1]
                kids = ([arr.data["keys"], arr.data["values"]] if is_map
                        else [arr.data["child"]])
                child_cap = kids[0].capacity
                e = jnp.arange(child_cap, dtype=jnp.int32)
                seg = jnp.clip(
                    jnp.searchsorted(off, e, side="right").astype(jnp.int32) - 1,
                    0, cap - 1)
                live = batch.live_mask()
                arr_valid = (arr.validity if arr.validity is not None
                             else jnp.ones(cap, jnp.bool_))
                elem_live = (e < off[cap]) & live[seg] & arr_valid[seg]
                req = [batch.columns[i] for i in self.plan.required]
                if not outer:
                    parent = [K.gather_column(c, seg, batch.num_rows,
                                              src_live=live)
                              for c in req]
                    gen_cols = []
                    if position:
                        pos = (e - off[seg]).astype(jnp.int32)
                        gen_cols.append(ColumnVector(T.INT32, pos, None))
                    gen_cols.extend(kids)
                    n_live = jnp.sum(elem_live.astype(jnp.int32))
                    return ColumnarBatch(parent + gen_cols, n_live, elem_live)
                # OUTER: null/empty rows still emit one row, in input
                # order. One order-preserving scatter builds a combined
                # source map: output slot off[i]+empties_before(i)+j for
                # element j of row i, slot off[i]+empties_before(i) for an
                # empty row i.
                out_cap = round_capacity(child_cap + cap)
                empty = live & (~arr_valid | ((off[1:] - off[:-1]) == 0))
                cume = (jnp.cumsum(empty.astype(jnp.int32))
                        - empty.astype(jnp.int32))
                src_row = jnp.full(out_cap, -1, jnp.int32)
                src_elem = jnp.full(out_cap, -1, jnp.int32)
                dest_e = jnp.where(elem_live, e + cume[seg], out_cap)
                src_row = src_row.at[dest_e].set(seg, mode="drop")
                src_elem = src_elem.at[dest_e].set(e, mode="drop")
                i = jnp.arange(cap, dtype=jnp.int32)
                dest_r = jnp.where(empty, off[:cap] + cume, out_cap)
                src_row = src_row.at[dest_r].set(i, mode="drop")
                live_out = src_row >= 0
                parent = [K.gather_column(c, src_row, batch.num_rows,
                                          src_live=live)
                          for c in req]
                gen_cols = []
                if position:
                    safe_row = jnp.clip(src_row, 0, cap - 1)
                    pos = (src_elem - off[safe_row]).astype(jnp.int32)
                    gen_cols.append(ColumnVector(T.INT32, pos,
                                                 src_elem >= 0))
                for k in kids:
                    gen_cols.append(K.gather_column(k, src_elem, child_cap))
                n_live = jnp.sum(live_out.astype(jnp.int32))
                return ColumnarBatch(parent + gen_cols, n_live, live_out)
            return fn

        key = ("generate", src.fingerprint(), is_map, position, outer,
               tuple(self.plan.required))
        fn = fuse.fused(key, build)
        for batch in self.children[0].execute_partition(ctx, pidx):
            self._acquire(ctx)
            with self.span(op_t):
                out = fn(batch)
            out_rows.add(rows_int(out.num_rows))
            yield out


class CoalesceBatchesExec(TpuExec):
    """Concat small batches up to the target size (reference
    GpuCoalesceBatches.scala TargetSize goal)."""

    def __init__(self, plan, children, conf, target_bytes: Optional[int] = None,
                 require_single: bool = False):
        super().__init__(plan, children, conf)
        self.target_bytes = target_bytes or conf.get(C.TARGET_BATCH_SIZE)
        self.require_single = require_single

    @property
    def schema(self):
        # concat never changes columns: like ExchangeExec, report the
        # child's schema even when self.plan is a downstream node (the
        # collected-complete-agg wrapper hands us the aggregate's plan)
        return self.children[0].schema

    def execute_partition(self, ctx, pidx):
        concat_t = self.metrics.metric(M.CONCAT_TIME)
        n_in = self.metrics.metric(M.NUM_INPUT_BATCHES)
        n_out = self.metrics.metric(M.NUM_OUTPUT_BATCHES)
        pending: List[ColumnarBatch] = []
        pending_bytes = 0

        def flush():
            n_out.add(1)
            if len(pending) == 1:
                # single-batch passthrough: no concat kernel runs, so no
                # semaphore acquire either
                return pending[0]
            self._acquire(ctx)
            with self.span(concat_t):
                return K.concat_batches(pending)

        for batch in self.children[0].execute_partition(ctx, pidx):
            pending.append(batch)
            n_in.add(1)
            pending_bytes += batch.device_memory_size()
            if not self.require_single and pending_bytes >= self.target_bytes:
                yield flush()
                pending, pending_bytes = [], 0
        if pending:
            yield flush()


def _order_keys(kc: ColumnVector, o, num_rows, live=None, n_chunks=None):
    """(key_u64, nulls, asc, nulls_first) list for one sort order: one
    entry for fixed-width types, one per 8-byte chunk for strings (EXACT
    lexicographic device ordering via kernels.string_chunk_keys)."""
    if isinstance(kc.dtype, T.StringType):
        if n_chunks is None:
            n_chunks = K.string_chunk_count(kc)
        return [(k, nulls, o.ascending, o.resolved_nulls_first())
                for k, nulls in K.string_chunk_keys(kc, num_rows, n_chunks,
                                                    live=live)]
    k, nulls = K.normalize_key(kc, num_rows, live=live)
    return [(k, nulls, o.ascending, o.resolved_nulls_first())]


#: a masked batch above this capacity is compacted (one count read-back)
#: before the keyed sort: the program's output keeps its input's
#: capacity, and what follows (the result's download first of all,
#: session.fetch's own rule) moves full planes
_SORT_COMPACT_ABOVE = 16384


def _shrunk_for_sort(batch: ColumnarBatch) -> ColumnarBatch:
    if batch.row_mask is not None and batch.capacity > _SORT_COMPACT_ABOVE:
        return K.compact_batch(batch)
    return batch


def _argsort_planes(planes: List[jax.Array], bits: List[int]) -> jax.Array:
    """Stable argsort (int32 permutation) by several planes of
    non-negative integers, the first the most significant, `bits[i]` the
    bits plane i's values take: one pass a 32-bit digit from the least
    significant up, every pass the SAME keyed program over one uint32
    plane. XLA's TPU compiler takes most of a minute over a sort of
    millions of rows, twice that when the key is 64 bits wide and longer
    again with several key operands (PERF.md section 7), so the large
    sorts that need more than one packed word (the rollup's keys, the
    ranked window's partition and double order key) share this one
    program a capacity instead of each tracing a sort of its own; a pass
    more costs a sort of 8 M rows (22 ms) and two gathers."""
    shifts = [(i, s) for i in reversed(range(len(planes)))
              for s in range(0, bits[i], 32)]
    def sort_digits(*ps):
        return [(ps[i].astype(jnp.uint64) >> jnp.uint64(s)).astype(jnp.uint32)
                for i, s in shifts]

    def argsort(x):
        return jnp.argsort(x, stable=True).astype(jnp.int32)

    def take(x, i):
        return x[i]

    digits = fuse.fused(("sort_digits", tuple(shifts)),
                        lambda: sort_digits)(*planes)
    sort = fuse.fused(("argsort",), lambda: argsort)
    gather = fuse.fused(("take",), lambda: take)
    perm = sort(digits[0])
    for digit in digits[1:]:
        perm = gather(perm, sort(gather(digit, perm)))
    return perm


def _sort_in_core(orders, batch: ColumnarBatch,
                  limit: Optional[int] = None) -> ColumnarBatch:
    """ORDER BY over one batch as ONE keyed program and no host sync: the
    order expressions, normalisation (_order_keys), the stable lexsort
    and the gather of every column trace into `fuse.fused(("sort", ...))`.
    Dead rows of a masked batch sort to the end, so the output is
    compacted and carries the input's (possibly lazy) row count. A string
    key's width is static in the trace and part of the key: it is settled
    before the program by K.string_chunk_count, which reads a device
    value only where the host does not know the width. With `limit` (a
    top-N's exact sort) the same program keeps the first `limit` rows, at
    that capacity, and counts them on the device."""
    widths = []
    for o in orders:
        if not isinstance(o.expr.data_type(), T.StringType):
            widths.append(None)
            continue
        # a computed string key is evaluated once for its width
        kc = (batch.columns[o.expr.index] if isinstance(o.expr, BoundRef)
              else compiled.run_stage([o.expr], batch)[0])
        widths.append(K.string_chunk_count(kc))
    fp = tuple((o.expr.fingerprint(), o.ascending, o.resolved_nulls_first())
               for o in orders)

    def build():
        def fn(b):
            live = b.live_mask()
            n = traced_rows(b.num_rows)
            ectx = EvalCtx(b.columns, n, b.capacity, False, live=live)
            keys = []
            for o, w in zip(orders, widths):
                keys.extend(_order_keys(o.expr.eval_tpu(ectx), o, n,
                                        live=live, n_chunks=w))
            perm = K.lexsort_indices(keys, n, live=live)
            if limit is None:
                return K.gather_batch(b, perm, b.num_rows).columns, n
            out_cap = min(round_capacity(max(limit, 1)), b.capacity)
            kept = jnp.minimum(n, limit).astype(jnp.int32)
            first = jnp.where(jnp.arange(out_cap, dtype=jnp.int32) < kept,
                              perm[:out_cap], -1)
            return K.gather_batch(b, first, b.num_rows).columns, kept
        return fn

    cols, kept = fuse.fused(("sort", fp, tuple(widths), limit), build)(batch)
    carry_host_stats(batch.columns, cols)
    return ColumnarBatch(cols, batch.num_rows if limit is None
                         else LazyRowCount(kept))


def _topn_image(kc: ColumnVector, order, live) -> Optional[jax.Array]:
    """Monotone int32 'goodness' image of a sort key: rows that belong
    EARLIER in the output get LARGER values (so lax.top_k selects them).
    Ties may collapse (f32-rounded 64-bit keys) — the image only gates a
    candidate threshold; exact order comes from the final small sort.
    Returns None for types without a cheap image (strings, nested)."""
    d = kc.dtype
    min32 = jnp.int32(np.int32(-2**31))
    if kc.is_string or kc.is_nested:
        return None
    if isinstance(d, (T.Float32Type, T.Float64Type)):
        x = kc.data.astype(jnp.float32)
        x = jnp.where(jnp.isnan(x), jnp.float32(np.nan), x)
        x = jnp.where(x == 0.0, jnp.zeros_like(x), x)
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        img = jnp.where(bits < 0, ~bits ^ min32, bits)
    elif isinstance(d, (T.Int64Type, T.TimestampType, T.DecimalType)):
        x = kc.data.astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        img = jnp.where(bits < 0, ~bits ^ min32, bits)
    else:
        img = kc.data.astype(jnp.int32)
    if order.ascending:
        img = ~img  # monotone reversal, no INT_MIN overflow
    valid = kc.validity
    if valid is not None:
        null_img = (jnp.int32(np.int32(2**31 - 1))
                    if order.resolved_nulls_first() else min32)
        img = jnp.where(valid, img, null_img)
    return jnp.where(live, img, min32)


class TopNExec(TpuExec):
    """ORDER BY + LIMIT n without sorting the full input (reference
    GpuTopN): lax.top_k over a monotone 32-bit image of the primary key
    gives a threshold; only the <= ~n surviving candidate rows get the
    exact multi-key sort. Ties and image collapse just widen the
    candidate set; a pathological width falls back to the full sort.
    Two fused dispatches + ONE host sync (the candidate count) — each
    dispatch and sync has a fixed cost, so each stage is a single jit."""

    def __init__(self, plan, children, conf, orders, n: int):
        super().__init__(plan, children, conf)
        self.orders = orders
        self.n = n
        self._fusable = all(
            not isinstance(o.expr.data_type(),
                           (T.StringType, T.ArrayType, T.MapType,
                            T.StructType))
            for o in orders)

    def _fp(self):
        return (tuple((o.expr.fingerprint(), o.ascending,
                       o.resolved_nulls_first()) for o in self.orders),
                self.n)

    def execute_partition(self, ctx, pidx):
        sort_t = self.metrics.metric(M.SORT_TIME)
        batches = list(self.children[0].execute_partition(ctx, pidx))
        if not batches:
            return
        self._acquire(ctx)
        batch = K.concat_batches(batches) if len(batches) > 1 else batches[0]
        n = self.n
        bound = max(4 * n, 4096)
        with self.span(sort_t):
            if self._fusable and batch.capacity > bound:
                orders = self.orders

                def build_select():
                    def fn(b):
                        live = b.live_mask()
                        ectx = EvalCtx(b.columns, traced_rows(b.num_rows),
                                       b.capacity, False, live=live)
                        kc = orders[0].expr.eval_tpu(ectx)
                        img = _topn_image(kc, orders[0], live)
                        k = min(n, b.capacity)
                        thr = jax.lax.top_k(img, k)[0][-1]
                        cand = live & (img >= thr)
                        return cand, jnp.sum(cand.astype(jnp.int32))
                    return fn

                sel = fuse.fused(("topn_select", self._fp()), build_select)
                cand, cnt_d = sel(batch)
                # start the count D2H before blocking on it: the transfer
                # overlaps the tail of the select computation instead of
                # waiting for an idle device to begin
                from spark_rapids_tpu.runtime.pipeline import start_d2h
                start_d2h(cnt_d)
                cnt = host_int(cnt_d, "size")
                if cnt <= bound:
                    out_cap = round_capacity(bound)

                    def build_sort():
                        def fn(b, cand, cnt):
                            idx = K._compact_indices(cand, b.capacity,
                                                     out_cap)
                            small = K.gather_batch(b, idx, cnt)
                            keys = []
                            sctx = EvalCtx(small.columns, cnt, out_cap,
                                           False)
                            for o in orders:
                                kc = o.expr.eval_tpu(sctx)
                                keys.extend(_order_keys(kc, o, cnt))
                            perm = K.lexsort_indices(keys, cnt)
                            ncap = round_capacity(n)  # <= out_cap (bound >= 4n)
                            sel_idx = jnp.where(
                                jnp.arange(ncap, dtype=jnp.int32)
                                < jnp.minimum(cnt, n), perm[:ncap], -1)
                            out = K.gather_batch(small, sel_idx, cnt)
                            return ColumnarBatch(
                                out.columns,
                                LazyRowCount(jnp.minimum(cnt, n)))
                        return fn

                    srt = fuse.fused(("topn_sort", self._fp()), build_sort)
                    yield srt(batch, cand, cnt_d)
                    return
            # fallback: exact full sort (string keys, tiny inputs, or a
            # pathologically wide tie set)
            yield _sort_in_core(self.orders, _shrunk_for_sort(batch),
                                limit=n)


class SortExec(TpuExec):
    """Whole-partition sort (reference GpuSortExec). In core (input up
    to sort.outOfCoreBytes) it is one keyed program a batch and no host
    sync (_sort_in_core; `explain("stages")` marks it `[keyed: sort]`);
    above that, the out-of-core path. The width of a string key comes
    from the host (K.static_string_chunks: the width stamped at upload,
    or a vocabulary too small to need more than one chunk); a read-back
    remains, once a key and before the program, for a computed string
    key or a column whose stamp an operator dropped, and the count
    read-back of K.compact_batch for a masked batch whose capacity
    exceeds _SORT_COMPACT_ABOVE."""

    def tree_string(self, indent: int = 0) -> str:
        head, nl, rest = super().tree_string(indent).partition("\n")
        return f"{head} [keyed: sort]{nl}{rest}"

    def execute_partition(self, ctx, pidx):
        sort_t = self.metrics.metric(M.SORT_TIME)
        batches = list(self.children[0].execute_partition(ctx, pidx))
        if not batches:
            return
        self._acquire(ctx)
        total = sum(b.device_memory_size() for b in batches)
        if total > self.conf.get(C.SORT_OOC_BYTES):
            it = self._out_of_core(batches)
            while True:
                with self.span(sort_t):
                    b = next(it, None)
                if b is None:
                    return
                yield b
        batch = K.concat_batches(batches) if len(batches) > 1 else batches[0]
        batch = _shrunk_for_sort(batch)
        with self.span(sort_t):
            out = _sort_in_core(self.plan.orders, batch)
        yield out

    def _out_of_core(self, batches):
        """Out-of-core sort (reference GpuSortExec.scala:281 merge path,
        TPU-shaped): only the u64 key planes live on device — per-chunk
        keys are computed and the row data immediately staged to host
        (pyarrow); one global argsort of the keys yields the permutation,
        and pyarrow assembles the sorted output host-side, re-uploaded in
        reader-sized slices."""
        import pyarrow as pa
        names = self.schema.names
        compacted, per_batch_keycols = [], []
        for b in batches:
            if b.row_mask is not None:
                b = K.compact_batch(b)
            if int(b.num_rows) == 0:
                continue
            compacted.append(b)
            per_batch_keycols.append(
                compiled.run_stage([o.expr for o in self.plan.orders], b))
        if not compacted:
            return
        # string chunk counts can differ per batch: fix each order's width
        # to the max across batches so key planes align
        widths = []
        for ci, o in enumerate(self.plan.orders):
            if isinstance(o.expr.data_type(), T.StringType):
                widths.append(max(K.string_chunk_count(kc[ci])
                                  for kc in per_batch_keycols))
            else:
                widths.append(1)
        key_planes, tables = [], []
        for b, key_cols in zip(compacted, per_batch_keycols):
            per_col = []
            for o, kc, w in zip(self.plan.orders, key_cols, widths):
                for k, nulls, _, _ in _order_keys(kc, o, b.num_rows,
                                                  n_chunks=w):
                    per_col.append((k[: int(b.num_rows)],
                                    nulls[: int(b.num_rows)]))
            key_planes.append(per_col)
            tables.append(to_arrow(b, names, "sort"))  # stages the data off-device
        keys = []
        pi = 0
        for o, w in zip(self.plan.orders, widths):
            for _ in range(w):
                k = jnp.concatenate([kp[pi][0] for kp in key_planes])
                nl = jnp.concatenate([kp[pi][1] for kp in key_planes])
                keys.append((k, nl, o.ascending, o.resolved_nulls_first()))
                pi += 1
        n = int(keys[0][0].shape[0])
        perm_d = K.lexsort_indices(keys, n)
        perm = host_planes(perm_d, "sort")[:n]
        table = pa.concat_tables(tables) if len(tables) > 1 else tables[0]
        sorted_table = table.take(perm)
        step = self.conf.get(C.MAX_READER_BATCH_SIZE_ROWS)
        for off in range(0, n, step):
            yield from_arrow(sorted_table.slice(off, min(step, n - off)))



def _static_expr_ranges(key_cols, kinds, key_exprs):
    """Host-known (lo, hi) bounds for every KIND_INT key — from the
    expression (``x % 1000``) or from cache-time column stats riding on
    the ColumnVector — or None if any is underivable. Skips the
    per-batch device min/max probe (a ~90ms sync)."""
    rs = []
    for i, (c, kind) in enumerate(zip(key_cols, kinds)):
        if kind == R.KIND_INT:
            r = key_exprs[i].static_range() if key_exprs is not None else None
            if r is None:
                r = c.bounds
            if r is None:
                return None
            rs.extend(r)
        else:
            rs.extend((0, 0))
    return np.asarray(rs, np.int64)


def _attach_key_bounds(out_batch, spec, ranges_host) -> None:
    """Stamp (lo, hi) column-stat bounds on a radix agg output's key
    columns so downstream radix consumers (post-exchange merge, window
    sort) skip their own device range probe."""
    if ranges_host is None:
        return
    for i, kind in enumerate(spec.kinds):
        if kind == R.KIND_INT and i < len(out_batch.columns):
            lo = int(ranges_host[2 * i])
            hi = int(ranges_host[2 * i + 1])
            if lo <= hi:
                out_batch.columns[i].bounds = (lo, hi)


def _probe_key_ranges(key_cols, live, key_exprs=None):
    """(ranges on the device, ranges on the host) of packable key columns,
    or None where one does not pack (R.static_kinds). The integer keys'
    (min, max) come from the expression or the column stats where the
    host has them; else from one small device fetch."""
    kinds = R.static_kinds(key_cols)
    if kinds is None:
        return None
    if not R.needs_range_probe(kinds):
        return (jnp.zeros(2 * len(key_cols), jnp.int64),
                np.zeros(2 * len(key_cols), np.int64))
    ranges_host = _static_expr_ranges(key_cols, kinds, key_exprs)
    if ranges_host is not None:
        return jnp.asarray(ranges_host), ranges_host
    probe = fuse.fused(("radix_probe", tuple(kinds)),
                       lambda: R.probe_ranges)
    ranges = probe(key_cols, live)
    return ranges, host_planes(ranges, "range")


def _probe_pack_spec(key_cols, live, key_exprs=None):
    """Host decision: can these key columns pack into one int64 plane?
    Returns (spec, ranges_device, ranges_host) or (None, None, None).
    Costs one small device fetch when integer key ranges are involved and
    not statically derivable — from the expression or from column-stat
    bounds (shared by the aggregate, window, and sort radix paths)."""
    probed = _probe_key_ranges(key_cols, live, key_exprs)
    if probed is None:
        return None, None, None
    ranges, ranges_host = probed
    return R.plan_packing(key_cols, ranges_host), ranges, ranges_host


def _with_inputs(fn, refs):
    """Aggregate `fn` (a distinct level's: a plain aggregate or a
    PartialState of one) over the inputs `refs`."""
    from spark_rapids_tpu.expr.aggregates import PartialState
    if isinstance(fn, PartialState):
        return PartialState(_with_inputs(fn.fn, refs), fn.j)
    return type(fn)(*refs)


class _AggKernels:
    """Aggregation kernel builders holding ONLY expression-level state.

    Deliberately separate from the exec node: the jitted closures built
    here live in the global fuse cache; if they captured the exec they
    would pin its child tree — including HBM-resident cached batches —
    for the process lifetime.
    """

    _BUCKET_LIMIT = 4096
    _MATMUL_LIMIT = 64

    #: segmented-reduction ops the packed radix path implements
    _SIMPLE_OPS = frozenset({"sum", "sumsq", "count", "count_all", "min",
                             "max", "first", "last", "any", "all"})

    def __init__(self, group_exprs, group_names, aggs, pre_filter):
        self.group_exprs = group_exprs
        self.group_names = group_names
        self.aggs = aggs
        self.pre_filter = pre_filter
        self._packed_ok = self._packed_static_ok()
        self._over = None   # over_inputs(), built once

    def _fp(self):
        return (tuple(e.fingerprint() for e in self.group_exprs),
                tuple(a.fn.fingerprint() for a in self.aggs),
                self.pre_filter.fingerprint() if self.pre_filter is not None
                else None)

    def _packed_static_ok(self) -> bool:
        """Static (plan-time) half of the radix fast-path eligibility:
        simple reduction ops over fixed-width states, packable-looking key
        types. The runtime half (spans fit 62 bits, strings are
        dict-encoded) is decided per batch in update()/merge()."""
        from spark_rapids_tpu.expr.aggregates import SegmentedAgg
        if not self.group_exprs:
            return False
        for e in self.group_exprs:
            dt = e.data_type()
            if not isinstance(dt, (T.Int8Type, T.Int16Type, T.Int32Type,
                                   T.Int64Type, T.DateType, T.TimestampType,
                                   T.BooleanType, T.DecimalType,
                                   T.StringType)):
                return False
        for a in self.aggs:
            if isinstance(a.fn, SegmentedAgg):
                return False
            for (sname, sdt), (op, idx) in zip(a.fn.state_schema(),
                                               a.fn.update_ops()):
                if op not in self._SIMPLE_OPS:
                    return False
                if isinstance(sdt, (T.StringType, T.ArrayType, T.MapType,
                                    T.StructType)):
                    return False
        return True

    # -- radix fast-path dispatch (see ops/radix.py) ------------------------

    def _probe_spec(self, key_cols, live, key_exprs=None):
        return _probe_pack_spec(key_cols, live, key_exprs)

    def update(self, batch: ColumnarBatch, ansi: bool):
        """The update phase entry: picks (in order) the tiny-bucket MXU
        path, the packed radix path, or the general sort path. Returns
        (state_batch, errors)."""
        if self._packed_ok:
            key_cols = compiled.run_stage(self.group_exprs, batch)
            if self._bucket_layout(key_cols) is None:
                spec, ranges, rh = self._probe_spec(key_cols,
                                                    batch.live_mask(),
                                                    self.group_exprs)
                if spec is not None:
                    fn = fuse.fused(
                        ("hashagg_packed_update", self._fp(), spec.key, ansi),
                        lambda: self._build_packed_update(ansi, spec))
                    out, errs = fn(batch, ranges)
                    _attach_key_bounds(out, spec, rh)
                    return out, errs
        fn = fuse.fused(("hashagg_update", self._fp(), ansi),
                        lambda: self._build_update(ansi))
        return fn(batch)

    def merge(self, batch: ColumnarBatch) -> ColumnarBatch:
        nkeys = len(self.group_exprs)
        if self._packed_ok and nkeys:
            key_cols = list(batch.columns[:nkeys])
            spec, ranges, rh = self._probe_spec(key_cols, batch.live_mask())
            if spec is not None:
                fn = fuse.fused(
                    ("hashagg_packed_merge", self._fp(), spec.key),
                    lambda: self._build_packed_merge(spec))
                out = fn(batch, ranges)
                _attach_key_bounds(out, spec, rh)
                return out
        fn = fuse.fused(("hashagg_merge", self._fp()),
                        lambda: self._merge_states)
        return fn(batch)

    def _build_packed_update(self, ansi: bool, spec):
        def fn(batch, ranges):
            batch, errs = self._filtered(batch, ansi)
            live = batch.live_mask()
            ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                           batch.capacity, ansi, live=live)
            nkeys = len(self.group_exprs)
            exprs = [e for e in self._state_input_exprs() if e is not None]
            cols = [e.eval_tpu(ectx) for e in exprs]
            key_cols = cols[:nkeys]
            input_cols = {}
            ci = nkeys
            for ai, a in enumerate(self.aggs):
                input_cols[ai] = cols[ci: ci + len(a.fn.children)]
                ci += len(a.fn.children)
            errs.update(ectx.errors)
            state_specs = []
            for ai, a in enumerate(self.aggs):
                for (sname, sdt), (op, idx) in zip(a.fn.state_schema(),
                                                   a.fn.update_ops()):
                    src = input_cols[ai][idx] if idx >= 0 else None
                    state_specs.append((op, src, sdt))
            out = self._packed_agg(batch, live, key_cols, state_specs,
                                   spec, ranges)
            return out, errs
        return fn

    def _build_packed_merge(self, spec):
        def fn(batch, ranges):
            live = batch.live_mask()
            nkeys = len(self.group_exprs)
            key_cols = list(batch.columns[:nkeys])
            state_specs = []
            ci = nkeys
            for a in self.aggs:
                for (sname, sdt), op in zip(a.fn.state_schema(),
                                            a.fn.merge_ops()):
                    state_specs.append((op, batch.columns[ci], sdt))
                    ci += 1
            return self._packed_agg(batch, live, key_cols, state_specs,
                                    spec, ranges)
        return fn

    def _packed_agg(self, batch, live, key_cols, state_specs, spec, ranges):
        """Shared packed-radix reduction core for update and merge. Small
        packed key spaces (<= 2^23 buckets) take the SORT-FREE scatter
        path; wider ones pack + sort + cumsum reductions (ops/radix.py)."""
        if spec.total_bits <= R.BUCKET_BITS:
            return self._bucket_scatter_agg(live, key_cols, state_specs,
                                            spec, ranges)
        packed = R.pack_keys(spec, key_cols, ranges, live)
        lay = R.group_layout(packed, live)
        sg = jnp.clip(lay.starts, 0, lay.cap - 1)
        group_packed = lay.sorted_packed[sg]
        pad_ok = lay.starts >= 0
        out_cols: List[ColumnVector] = []
        for c in R.unpack_keys(spec, group_packed, ranges, key_cols):
            v = c.validity & pad_ok if c.validity is not None else pad_ok
            out_cols.append(ColumnVector(c.dtype, c.data, v,
                                         dict_unique=c.dict_unique))
        for op, src, sdt in state_specs:
            ov, oval = self._packed_op(op, src, sdt, live, lay)
            out_cols.append(ColumnVector(sdt, ov.astype(sdt.np_dtype)
                                         if ov.dtype != np.dtype(sdt.np_dtype)
                                         else ov, oval))
        return ColumnarBatch(out_cols, LazyRowCount(lay.n_groups))

    #: pallas sorted-window path gate: packed key bits in [11, 24] keeps
    #: the bucket space 2*TILE-aligned and the key-digit lanes <= 3
    _PALLAS_SEG_MIN_BITS = 11
    _PALLAS_SEG_MAX_BITS = 24

    def _pallas_ops_ok(self, state_specs) -> bool:
        n_sums = 0
        for op, src, sdt in state_specs:
            if op in ("count", "count_all"):
                continue
            if op == "sum" and src is not None and not src.is_string                     and not src.is_nested and np.dtype(sdt.np_dtype) in (
                        np.dtype(np.float64), np.dtype(np.float32)):
                n_sums += 1
                continue
            return False
        return 1 <= n_sums <= 2

    def _pallas_seg_eligible(self, live, state_specs, spec) -> bool:
        from spark_rapids_tpu.ops import pallas_kernels as PK
        if not PK.enabled():
            return False
        if not (self._PALLAS_SEG_MIN_BITS <= spec.total_bits
                <= self._PALLAS_SEG_MAX_BITS):
            return False
        cap = live.shape[0]
        from spark_rapids_tpu.ops.pallas_segsum import CHUNK_ROWS, TILE
        # HBM budget: the fused stage carries the sorted planes, digit
        # lanes, accumulators, AND the cond fallback's scatter temps; the
        # 32M q3 shape measured 18.5G against the v5e's 15.75G —
        # larger batches take the CHUNKED kernel path (below) when the
        # partial merge is cheap, else the scatter path
        if cap % TILE or cap < 4 * TILE or cap > CHUNK_ROWS:
            return False
        return self._pallas_ops_ok(state_specs)

    def _pallas_chunk_plan(self, live, state_specs, spec) -> int:
        """Chunk count for the chunked kernel path (0 = ineligible).
        Batches past the kernel's whole-stage HBM ceiling run it per
        CHUNK_ROWS slice and sum-merge the k small dense partials; only
        worthwhile when that merge (k * 2^bits rows) is itself cheap."""
        from spark_rapids_tpu.ops import pallas_kernels as PK
        if not PK.enabled():
            return 0
        if not (self._PALLAS_SEG_MIN_BITS <= spec.total_bits
                <= self._PALLAS_SEG_MAX_BITS):
            return 0
        if not self._pallas_ops_ok(state_specs):
            return 0
        cap = live.shape[0]
        from spark_rapids_tpu.ops.pallas_segsum import CHUNK_ROWS
        if cap <= CHUNK_ROWS or cap % CHUNK_ROWS:
            return 0
        k = cap // CHUNK_ROWS
        if k * (1 << spec.total_bits) > CHUNK_ROWS:
            return 0
        return k

    def _chunked_pallas_agg(self, live, key_cols, state_specs, spec,
                            ranges, k: int) -> ColumnarBatch:
        """Run the Pallas sorted-window groupby per CHUNK_ROWS slice and
        merge the k dense partials with one recursive bucket agg — the
        stage split that unlocks the kernel at 30M-row shapes (the
        recursive re-aggregation pattern of GpuAggregateExec.scala:
        208-315, done by chunking instead of repartitioning)."""
        from spark_rapids_tpu.ops.pallas_segsum import (CHUNK_ROWS,
                                                        MAX_GROUP_ROWS)

        def cv_rows(c, off):
            if c is None:
                return None
            if c.is_dict:
                data = {"codes": c.data["codes"][off:off + CHUNK_ROWS],
                        "dict_offsets": c.data["dict_offsets"],
                        "dict_bytes": c.data["dict_bytes"]}
            else:
                data = c.data[off:off + CHUNK_ROWS]
            v = None if c.validity is None \
                else c.validity[off:off + CHUNK_ROWS]
            return ColumnVector(c.dtype, data, v,
                                dict_unique=c.dict_unique, bounds=c.bounds)

        nkeys = len(key_cols)
        parts: List[ColumnarBatch] = []
        for i in range(k):
            off = i * CHUNK_ROWS
            live_c = live[off:off + CHUNK_ROWS]
            keys_c = [cv_rows(c, off) for c in key_cols]
            specs_c = [(op, cv_rows(src, off), sdt)
                       for op, src, sdt in state_specs]
            post, (max_cnt, has_specials) = \
                self._pallas_seg_kernel_and_post(live_c, keys_c, specs_c,
                                                 spec, ranges)

            def fallback(lc=live_c, kc=keys_c, sc=specs_c):
                return self._bucket_scatter_agg_xla(lc, kc, sc, spec,
                                                    ranges)
            parts.append(lax.cond(
                (max_cnt <= MAX_GROUP_ROWS) & ~has_specials,
                post, fallback))
        # concatenate the k equal-capacity partials (dict key vocab
        # planes are shared across chunks) and sum-merge per bucket:
        # sum states merge by sum, count states by integer sum
        cat_cols: List[ColumnVector] = []
        for ci in range(nkeys + len(state_specs)):
            cvs = [p.columns[ci] for p in parts]
            c0 = cvs[0]
            if c0.is_dict:
                data = {"codes": jnp.concatenate(
                            [c.data["codes"] for c in cvs]),
                        "dict_offsets": c0.data["dict_offsets"],
                        "dict_bytes": c0.data["dict_bytes"]}
            else:
                data = jnp.concatenate([c.data for c in cvs])
            if any(c.validity is not None for c in cvs):
                val = jnp.concatenate([c.validity_or_default(c.capacity)
                                       for c in cvs])
            else:
                val = None
            cat_cols.append(ColumnVector(c0.dtype, data, val,
                                         dict_unique=c0.dict_unique))
        cat_live = jnp.concatenate([p.live_mask() for p in parts])
        merge_specs = [("sum", cat_cols[nkeys + j], sdt)
                       for j, (_op, _src, sdt) in enumerate(state_specs)]
        return self._bucket_scatter_agg(cat_live, cat_cols[:nkeys],
                                        merge_specs, spec, ranges)

    def _pallas_seg_kernel_and_post(self, live, key_cols, state_specs,
                                    spec, ranges):
        """Returns (postprocess_thunk, max_cnt): the Pallas kernel runs
        immediately (top level); the thunk builds the output batch from
        the accumulator and is safe to call inside lax.cond."""
        return self._pallas_seg_agg(live, key_cols, state_specs, spec,
                                    ranges)

    def _pallas_seg_agg(self, live, key_cols, state_specs, spec, ranges):
        """Sorted-window one-hot-matmul groupby (ops/pallas_segsum):
        ONE co-sortless 2-operand sort + 1-2 gathers + the Pallas kernel
        replace every scatter. Output is in DENSE GROUP-ID space (front-
        packed groups) at the same capacity as the bucket space, so the
        lax.cond overflow fallback to the scatter path keeps identical
        shapes (slot ORDER differs; downstream is order-free over the
        occupied mask)."""
        from spark_rapids_tpu.ops import pallas_segsum as PS
        cap = live.shape[0]
        nb = 1 << spec.total_bits
        packed64 = R.pack_keys(spec, key_cols, ranges, live)
        big = jnp.int32(nb + 1)
        code = jnp.where(live, packed64.astype(jnp.int32), big)
        iota = jnp.arange(cap, dtype=jnp.int32)
        sk, perm = lax.sort((code, iota), num_keys=1)
        boundary = jnp.concatenate([jnp.ones(1, jnp.bool_),
                                    sk[1:] != sk[:-1]])
        gid = (jnp.cumsum(boundary.astype(jnp.int32)) - 1).astype(jnp.int32)
        live_sorted = sk < big

        has_specials = jnp.zeros((), jnp.bool_)
        lanes = [live_sorted.astype(jnp.bfloat16)]  # lane 0: live count
        kd, kshifts = PS.int_digits(jnp.where(live_sorted, sk, 0),
                                    spec.total_bits)
        lanes.extend(kd)
        plan = []  # (op, kind, lane_slices / scales)
        for op, src, sdt in state_specs:
            if op == "count_all":
                plan.append(("count_all", None, None))
                continue
            if op == "count":
                if src is None or src.validity is None:
                    plan.append(("count_live", None, None))
                else:
                    v_s = src.validity[perm] & live_sorted
                    lanes.append(v_s.astype(jnp.bfloat16))
                    plan.append(("count_lane", len(lanes) - 1, None))
                continue
            # float sum: gather the value plane into sorted order once.
            # NaN/Inf rows are stripped BEFORE the scale (an Inf max
            # collapses every digit to zero) and instead force the
            # scatter fallback, which reconstructs specials per bucket
            # (radix.bucket_sum_f64's flag machinery).
            vals = src.data.astype(jnp.float64)[perm]
            valid_s = live_sorted if src.validity is None else                 (src.validity[perm] & live_sorted)
            finite = jnp.isfinite(vals)
            clean = jnp.where(valid_s & finite, vals, 0.0)
            has_specials = has_specials | jnp.any(valid_s & ~finite)
            m = jnp.max(jnp.abs(clean))
            scale = R._exponent_scale(m) * np.float64(2.0 ** 11)
            start = len(lanes)
            lanes.extend(PS.float_digits(clean, scale))
            some_lane = None
            if src.validity is not None:
                lanes.append(valid_s.astype(jnp.bfloat16))
                some_lane = len(lanes) - 1
            plan.append(("sum", (start, scale, some_lane), sdt))
        P = -(-len(lanes) // 8) * 8
        while len(lanes) < P:
            lanes.append(jnp.zeros(cap, jnp.bfloat16))
        # the kernel runs at TOP LEVEL (a pallas custom-call inside a
        # lax.cond branch aborts the runtime on this toolchain); only the
        # cheap postprocessing participates in the overflow cond
        payload = jnp.stack(lanes, axis=1)
        acc = PS.segsum_window(gid, payload, nb)

        def post():
            return self._pallas_seg_post(acc, state_specs, spec, ranges,
                                         key_cols, plan, len(kd), kshifts,
                                         nb)
        return post, (jnp.max(acc[:, 0]), has_specials)

    def _pallas_seg_post(self, acc, state_specs, spec, ranges, key_cols,
                         plan, nkd, kshifts, nb):
        from spark_rapids_tpu.ops import pallas_segsum as PS
        counts_live = acc[:, 0]
        key_code = PS.int_digits_to_val(
            [acc[:, 1 + i] for i in range(nkd)], kshifts, counts_live)
        occupied = counts_live > 0.5
        out_cols: List[ColumnVector] = []
        for c in R.unpack_keys(spec, key_code.astype(jnp.int64), ranges,
                               key_cols):
            v = c.validity & occupied if c.validity is not None else occupied
            out_cols.append(ColumnVector(c.dtype, c.data, v,
                                         dict_unique=c.dict_unique))
        for (op, src, sdt), (kind, info, _sdt) in zip(state_specs, plan):
            if kind in ("count_all", "count_live"):
                ov = counts_live.astype(jnp.int64)
                out_cols.append(ColumnVector(
                    sdt, ov.astype(sdt.np_dtype), jnp.ones(nb, jnp.bool_)))
                continue
            if kind == "count_lane":
                ov = acc[:, info].astype(jnp.int64)
                out_cols.append(ColumnVector(
                    sdt, ov.astype(sdt.np_dtype), jnp.ones(nb, jnp.bool_)))
                continue
            start, scale, some_lane = info
            tot = PS.digits_to_f64(
                [acc[:, start + i] for i in range(len(PS.SHIFTS))]) / scale
            some = acc[:, some_lane] > 0.5 if some_lane is not None \
                else occupied
            out_cols.append(ColumnVector(
                sdt, tot.astype(sdt.np_dtype), some))
        n_groups = jnp.sum(occupied.astype(jnp.int32))
        return ColumnarBatch(out_cols, LazyRowCount(n_groups), occupied)

    def _bucket_scatter_agg(self, live, key_cols, state_specs, spec, ranges):
        from spark_rapids_tpu.runtime import obs as _obs
        if self._pallas_seg_eligible(live, state_specs, spec):
            _obs.note_pallas_segsum("whole")
            post, (max_cnt, has_specials) = \
                self._pallas_seg_kernel_and_post(
                    live, key_cols, state_specs, spec, ranges)
            from spark_rapids_tpu.ops.pallas_segsum import MAX_GROUP_ROWS
            # One cond over the whole batch pytree: the scatter fallback
            # only EXECUTES when a group exceeds the digit-accumulation
            # bound (the count lane stays trustworthy well past the
            # threshold, so the predicate is reliable even then). Slot
            # ORDER differs between branches (dense-gid vs bucket index),
            # which downstream — occupied-masked and order-free — never
            # observes.
            return lax.cond(
                (max_cnt <= MAX_GROUP_ROWS) & ~has_specials,
                post,
                lambda: self._bucket_scatter_agg_xla(
                    live, key_cols, state_specs, spec, ranges))
        k = self._pallas_chunk_plan(live, state_specs, spec)
        if k:
            _obs.note_pallas_segsum("chunked")
            return self._chunked_pallas_agg(live, key_cols, state_specs,
                                            spec, ranges, k)
        return self._bucket_scatter_agg_xla(live, key_cols, state_specs,
                                            spec, ranges)

    def _bucket_scatter_agg_xla(self, live, key_cols, state_specs, spec,
                                ranges):
        lay = R.bucket_layout(spec, key_cols, ranges, live)
        out_cols: List[ColumnVector] = []
        for c in R.bucket_unpack_keys(spec, ranges, key_cols):
            v = c.validity & lay.occupied if c.validity is not None \
                else lay.occupied
            out_cols.append(ColumnVector(c.dtype, c.data, v,
                                         dict_unique=c.dict_unique))
        nb = lay.bucket  # noqa: F841
        ones = jnp.ones(1 << spec.total_bits, jnp.bool_)
        for op, src, sdt in state_specs:
            if src is not None:
                if (src.is_string or src.is_nested) and \
                        op not in ("count", "count_all"):
                    raise NotImplementedError(
                        "string/nested agg state on device")
                valid = live if src.validity is None \
                    else (src.validity & live)
                vals = src.data if not (src.is_string or src.is_nested) \
                    else jnp.zeros(live.shape[0], sdt.np_dtype)
            else:
                valid = live
                vals = jnp.zeros(live.shape[0], sdt.np_dtype)
            ov, oval = self._bucket_op(op, vals, valid, sdt, lay, ones)
            out_cols.append(ColumnVector(
                sdt, ov.astype(sdt.np_dtype)
                if ov.dtype != np.dtype(sdt.np_dtype) else ov, oval))
        return ColumnarBatch(out_cols, LazyRowCount(lay.n_groups),
                             lay.occupied)

    def _bucket_op(self, op, vals, valid, sdt, lay, ones):
        def nv():
            # a no-null column's validity IS the live mask, which the
            # layout already counted — skip the extra scatter
            return lay.counts.astype(jnp.int64) if valid is lay.live \
                else R.bucket_count(lay, valid)
        if op == "count":
            return nv(), ones
        if op == "count_all":
            return lay.counts.astype(jnp.int64), ones
        nvalid = nv()
        some = nvalid > 0
        if op in ("sum", "sumsq"):
            v = vals * vals if op == "sumsq" else vals
            if np.dtype(sdt.np_dtype) in (np.dtype(np.float64),
                                          np.dtype(np.float32)):
                tot = R.bucket_sum_f64(lay, v, valid)
                return tot, some
            return R.bucket_sum_int(lay, v, valid), some
        if op in ("min", "max"):
            d = np.dtype(vals.dtype)
            if d == np.dtype(np.float64):
                return R.bucket_minmax_f64(op, lay, vals, valid), some
            if d == np.dtype(np.float32):
                return R.bucket_minmax_f32(op, lay, vals, valid), some
            if d == np.dtype(np.int64):
                return R.bucket_minmax_i64(op, lay, vals, valid), some
            init = (G._MIN_INIT if op == "min" else G._MAX_INIT)[
                np.dtype(np.int32) if d == np.dtype(np.bool_) else d]
            out = R.bucket_minmax_i32(op, lay, vals, valid, int(init))
            return out.astype(vals.dtype), some
        if op in ("first", "last"):
            v, has = R.bucket_first_last(op, lay, vals, valid)
            return v, has & some
        if op == "any":
            return R.bucket_count(lay, valid & vals.astype(jnp.bool_)) > 0, \
                some
        if op == "all":
            return R.bucket_count(lay, valid & ~vals.astype(jnp.bool_)) == 0, \
                some
        raise ValueError(f"unknown bucket op {op}")

    def _packed_op(self, op, src, sdt, live, lay):
        cap = lay.cap
        if src is not None:
            if (src.is_string or src.is_nested) and \
                    op not in ("count", "count_all"):
                raise NotImplementedError(
                    "string/nested agg state on device")
            valid = (live if src.validity is None
                     else (src.validity & live))[lay.perm]
            vals = src.data[lay.perm] \
                if not (src.is_string or src.is_nested) \
                else jnp.zeros(cap, sdt.np_dtype)
        else:
            valid = live[lay.perm]
            vals = jnp.zeros(cap, sdt.np_dtype)
        if op == "count":
            return R.seg_count(valid, lay), jnp.ones(cap, jnp.bool_)
        if op == "count_all":
            return R.seg_count_all(lay), jnp.ones(cap, jnp.bool_)
        nvalid = R.seg_count(valid, lay)
        some = nvalid > 0
        if op in ("sum", "sumsq"):
            v = vals * vals if op == "sumsq" else vals
            if np.dtype(sdt.np_dtype) in (np.dtype(np.float64),
                                          np.dtype(np.float32)):
                return R.seg_sum_f64(v.astype(jnp.float64), valid, lay), some
            return R.seg_sum_int(v, valid, lay), some
        if op in ("min", "max"):
            d = np.dtype(vals.dtype)
            if d == np.dtype(np.float64):
                return R.seg_minmax_f64(op, vals, valid, lay), some
            if d == np.dtype(np.float32):
                return R.seg_minmax_f32(op, vals, valid, lay), some
            if d in (np.dtype(np.int64),):
                return R.seg_minmax_i64(op, vals, valid, lay), some
            init = (G._MIN_INIT if op == "min" else G._MAX_INIT)[
                np.dtype(np.int32) if d == np.dtype(np.bool_) else d]
            out = R.seg_minmax_i32(op, vals, valid, lay,
                                   int(init))
            return out.astype(vals.dtype), some
        if op in ("first", "last"):
            v, has = R.seg_first_last(op, vals, valid, lay)
            return v, has & some
        if op == "any":
            t = valid & vals.astype(jnp.bool_)
            return R.seg_count(t, lay) > 0, some
        if op == "all":
            f = valid & ~vals.astype(jnp.bool_)
            return R.seg_count(f, lay) == 0, some
        raise ValueError(f"unknown packed op {op}")

    def _state_input_exprs(self):
        """Expressions evaluated per input row: keys then, per agg, ALL its
        input children (min_by/max_by consume two)."""
        exprs = list(self.group_exprs)
        for a in self.aggs:
            exprs.extend(a.fn.children)
        return exprs

    @property
    def has_custom(self) -> bool:
        from spark_rapids_tpu.expr.aggregates import SegmentedAgg
        return any(isinstance(a.fn, SegmentedAgg) for a in self.aggs)

    def _filtered(self, batch: ColumnarBatch, ansi: bool):
        """(batch masked by the absorbed filter, its errors), traced."""
        if self.pre_filter is None:
            return batch, {}
        live = batch.live_mask()
        pctx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                       batch.capacity, ansi, live=live)
        pred = self.pre_filter.eval_tpu(pctx)
        live = live & pred.data.astype(jnp.bool_)
        if pred.validity is not None:
            live = live & pred.validity
        return ColumnarBatch(batch.columns,
                             LazyRowCount(jnp.sum(live.astype(jnp.int32))),
                             live), dict(pctx.errors)

    def _build_update(self, ansi: bool):
        """Build the fused update phase: expression eval + sort-group +
        segmented reductions as ONE traced computation over batch pytrees."""
        def fn(batch):
            batch, errs = self._filtered(batch, ansi)
            ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                           batch.capacity, ansi, live=batch.live_mask())
            out = self._update_batch(batch, ectx)
            errs.update(ectx.errors)
            return out, errs
        return fn

    # -- the distinct level's input (plan/distinct.py) ----------------------

    def _inputs(self) -> dict:
        """{fingerprint: expression} of the keys and aggregate inputs,
        each once (avg(x) beside count(x) reads one x)."""
        out = {}
        for e in self._state_input_exprs():
            out.setdefault(e.fingerprint(), e)
        return out

    def project_inputs(self, batch: ColumnarBatch, ansi: bool):
        """The absorbed filter, then every key and aggregate input, over
        `batch` as one program: (a batch of those columns alone, masked
        by the filter; errors). `over_inputs` aggregates it."""
        def build():
            def fn(b):
                b, errs = self._filtered(b, ansi)
                ectx = EvalCtx(b.columns, traced_rows(b.num_rows),
                               b.capacity, ansi, live=b.live_mask())
                cols = [e.eval_tpu(ectx) for e in self._inputs().values()]
                errs.update(ectx.errors)
                return ColumnarBatch(cols, b.num_rows, b.live_mask()), errs
            return fn
        return fuse.fused(("hashagg_inputs", self._fp(), ansi), build)(batch)

    def over_inputs(self) -> "_AggKernels":
        """These kernels over `project_inputs`' columns: keys and inputs
        become column references, and no filter is left."""
        if self._over is None:
            ref = {fp: BoundRef(i, e.data_type(), f"in{i}")
                   for i, (fp, e) in enumerate(self._inputs().items())}
            aggs = [P.NamedAgg(_with_inputs(a.fn, [
                ref[c.fingerprint()] for c in a.fn.children]), a.name)
                for a in self.aggs]
            self._over = _AggKernels(
                [ref[e.fingerprint()] for e in self.group_exprs],
                self.group_names, aggs, None)
        return self._over

    def _update_batch(self, batch: ColumnarBatch, ectx) -> ColumnarBatch:
        from spark_rapids_tpu.expr.aggregates import SegmentedAgg
        nkeys = len(self.group_exprs)
        exprs = [e for e in self._state_input_exprs() if e is not None]
        cols = [e.eval_tpu(ectx) for e in exprs]
        key_cols = cols[:nkeys]
        input_cols = {}
        ci = nkeys
        for ai, a in enumerate(self.aggs):
            input_cols[ai] = cols[ci: ci + len(a.fn.children)]
            ci += len(a.fn.children)
        cap = batch.capacity
        live = batch.live_mask()

        def col_valid(src):
            return live if src.validity is None else (src.validity & live)

        if nkeys == 0:
            out_cols = []
            nrows = traced_rows(batch.num_rows)
            for ai, a in enumerate(self.aggs):
                if isinstance(a.fn, SegmentedAgg):
                    # global custom agg: one segment over all rows
                    res = a.fn.segmented_eval_tpu(
                        input_cols[ai], jnp.arange(cap, dtype=jnp.int32),
                        jnp.zeros(cap, jnp.int32), 1, live, nrows)
                    out_cols.append(_resize_col(res, round_capacity(1)))
                    continue
                for (sname, sdt), (op, idx) in zip(a.fn.state_schema(),
                                                   a.fn.update_ops()):
                    if idx >= 0:
                        src = input_cols[ai][idx]
                        if src.is_string or src.is_nested:
                            if op not in ("count", "count_all"):
                                raise NotImplementedError(
                                    "string agg state on device")
                            vals = jnp.zeros(cap, sdt.np_dtype)
                        else:
                            vals = src.data
                            if vals.dtype != sdt.np_dtype:
                                vals = vals.astype(sdt.np_dtype)
                        ov, oval = G.global_agg(op, vals, col_valid(src))
                    else:
                        ov, oval = G.global_agg(op, jnp.zeros(cap, sdt.np_dtype), live)
                    out_cols.append(_resize_plane(ov, oval, sdt, round_capacity(1)))
            return ColumnarBatch(out_cols, 1)

        fast = None if any(isinstance(a.fn, SegmentedAgg) for a in self.aggs) \
            else self._bucket_layout(key_cols)
        if fast is not None:
            return self._bucket_update(batch, key_cols, input_cols, live, fast)

        if nkeys:
            # Deferred shrink: output keeps the input capacity and the group
            # count stays on device (LazyRowCount); the shrink to the true
            # size happens once, at yield, not per batch.
            perm, seg_ids, boundary = G.group_segments(key_cols, batch.num_rows,
                                                       live=live)
            n_groups = LazyRowCount(jnp.sum(boundary.astype(jnp.int32)))
            seg_cap = cap
            out_cap = cap
        else:
            perm = jnp.arange(cap, dtype=jnp.int32)
            seg_ids = jnp.zeros(cap, jnp.int32)
            boundary = jnp.zeros(cap, jnp.bool_).at[0].set(True)
            n_groups = 1
            seg_cap = 1
            out_cap = round_capacity(1)
        out_cols: List[ColumnVector] = []
        if nkeys:
            out_key_cols = G.gather_group_keys(key_cols, perm, boundary,
                                               n_groups, batch.num_rows,
                                               live=live)
            for c in out_key_cols:
                out_cols.append(_resize_col(c, out_cap))
        nrows = traced_rows(batch.num_rows)
        for ai, a in enumerate(self.aggs):
            if isinstance(a.fn, SegmentedAgg):
                res = a.fn.segmented_eval_tpu(input_cols[ai], perm, seg_ids,
                                              seg_cap, live, nrows)
                out_cols.append(_resize_col(res, out_cap))
                continue
            for (sname, sdt), (op, idx) in zip(a.fn.state_schema(), a.fn.update_ops()):
                if idx >= 0:
                    src = input_cols[ai][idx]
                    if src.is_string or src.is_nested:
                        if op not in ("count", "count_all"):
                            # min/max/first/last over strings: handled via
                            # host fallback by tagging; sum never string
                            raise NotImplementedError(
                                "string agg state on device")
                        # count reads only the validity plane
                        sorted_vals = jnp.zeros(cap, sdt.np_dtype)
                        sorted_valid = col_valid(src)[perm]
                    else:
                        vals = src.data
                        vals = vals.astype(sdt.np_dtype) \
                            if vals.dtype != sdt.np_dtype else vals
                        sorted_vals = vals[perm]
                        sorted_valid = col_valid(src)[perm]
                else:
                    sorted_vals = jnp.zeros(cap, sdt.np_dtype)
                    sorted_valid = live[perm]
                ov, oval = G.segmented_agg(op, sorted_vals, sorted_valid,
                                           seg_ids, seg_cap)
                out_cols.append(_resize_plane(ov, oval, sdt, out_cap))
        return ColumnarBatch(out_cols, n_groups)

    # -- bucketed (MXU) aggregation fast path ------------------------------

    _BUCKET_LIMIT = 4096
    _MATMUL_LIMIT = 64

    def _bucket_layout(self, key_cols):
        """When every group key has a small static cardinality (dict-encoded
        strings, booleans), groups map to dense bucket ids and aggregation
        needs NO sort: sums/counts become a one-hot matmul on the MXU (tiny
        bucket spaces) or a bounded scatter-add. Returns per-key
        (cardinality+1) strides or None if ineligible. The +1 slot per key
        encodes NULL (Spark groups null keys)."""
        sizes = []
        for c in key_cols:
            if c.is_dict and c.dict_unique:
                sizes.append(c.dict_size + 1)
            elif isinstance(c.dtype, T.BooleanType):
                sizes.append(3)
            else:
                return None
        total = 1
        for s in sizes:
            total *= s
            if total > self._BUCKET_LIMIT:
                return None
        return sizes

    def _bucket_update(self, batch, key_cols, input_cols, live, sizes):
        B = 1
        for s in sizes:
            B *= s
        bucket = jnp.zeros(batch.capacity, jnp.int32)
        for c, s in zip(key_cols, sizes):
            if c.is_dict:
                code = c.data["codes"].astype(jnp.int32)
            else:
                code = c.data.astype(jnp.int32)
            null_code = s - 1
            if c.validity is not None:
                code = jnp.where(c.validity, code, null_code)
            bucket = bucket * s + jnp.clip(code, 0, null_code)
        states = []
        # one (values, valid) pair an input expression and state dtype:
        # avg(x) beside sum(x) hands the digit path the SAME planes, which
        # it shares by identity (each evaluation traced its own arrays)
        planes = {}
        for ai, a in enumerate(self.aggs):
            for (sname, sdt), (op, idx) in zip(a.fn.state_schema(), a.fn.update_ops()):
                if idx >= 0:
                    src = input_cols[ai][idx]
                    if (src.is_string or src.is_nested) and \
                            op not in ("count", "count_all"):
                        raise NotImplementedError(
                            "string agg state on device")
                    key = (a.fn.children[idx].fingerprint(),
                           np.dtype(sdt.np_dtype).str)
                    if key not in planes:
                        vals = src.data \
                            if not (src.is_string or src.is_nested) \
                            else jnp.zeros(batch.capacity, sdt.np_dtype)
                        vals = vals.astype(sdt.np_dtype) if vals.dtype != sdt.np_dtype else vals
                        planes[key] = (vals, live if src.validity is None
                                       else (src.validity & live))
                    vals, valid = planes[key]
                else:
                    vals = jnp.zeros(batch.capacity, sdt.np_dtype)
                    valid = live
                states.append((op, vals, valid, sdt))
        from spark_rapids_tpu.ops import pallas_kernels as PK
        digit_ok = B <= self._MATMUL_LIMIT and PK.enabled() and all(
            op in G.DIGIT_OPS and (op != "sum" or np.dtype(sdt.np_dtype) in (
                np.dtype(np.float64), np.dtype(np.float32)))
            for op, _v, _valid, sdt in states)
        if digit_ok:
            # every state one exact-digit MXU contraction, occupancy
            # from its live-count lane: one pass over the planes
            outs, occupancy = G.bucket_digit_agg(
                [s[:3] for s in states], bucket, live, B)
        elif B <= self._MATMUL_LIMIT:
            # keep the rest of the tiny-B path scatter-free: one masked
            # reduction a bucket (occupancy and every state)
            occupancy = jnp.stack([jnp.any(live & (bucket == b))
                                   for b in range(B)])
        else:
            occupancy = (jax.ops.segment_sum(
                jnp.where(live, 1, 0), jnp.where(live, bucket, B),
                num_segments=B + 1)[:B] > 0)
        out_cols: List[ColumnVector] = []
        # reconstruct key columns from the bucket index (B is small)
        codes = []
        rem = jnp.arange(B, dtype=jnp.int32)
        for s in reversed(sizes):
            codes.append(rem % s)
            rem = rem // s
        codes.reverse()
        for c, s, code in zip(key_cols, sizes, codes):
            kvalid = code < (s - 1)
            if c.is_dict:
                data = {"codes": code.astype(jnp.int32),
                        "dict_offsets": c.data["dict_offsets"],
                        "dict_bytes": c.data["dict_bytes"]}
                out_cols.append(ColumnVector(c.dtype, data, kvalid))
            else:
                out_cols.append(ColumnVector(c.dtype, code.astype(c.data.dtype), kvalid))
        for i, (op, vals, valid, sdt) in enumerate(states):
            if digit_ok:
                ov, oval = outs[i]
                ov = ov.astype(sdt.np_dtype)
            else:
                ov, oval = G.bucket_agg(op, vals, valid, bucket, B,
                                        matmul_ok=B <= self._MATMUL_LIMIT)
            out_cols.append(ColumnVector(sdt, ov, oval))
        n_groups = LazyRowCount(jnp.sum(occupancy.astype(jnp.int32)))
        return ColumnarBatch(out_cols, n_groups, occupancy)

    def _merge_states(self, batch: ColumnarBatch) -> ColumnarBatch:
        nkeys = len(self.group_exprs)
        cap = batch.capacity
        live = batch.live_mask()
        if nkeys == 0:
            out_cols = []
            ci = 0
            for a in self.aggs:
                for (sname, sdt), op in zip(a.fn.state_schema(), a.fn.merge_ops()):
                    src = batch.columns[ci]
                    ci += 1
                    src_valid = live if src.validity is None else (src.validity & live)
                    ov, oval = G.global_agg(op, src.data, src_valid)
                    out_cols.append(_resize_plane(ov, oval, sdt, round_capacity(1)))
            return ColumnarBatch(out_cols, 1)
        key_cols = batch.columns[:nkeys]
        if nkeys:
            perm, seg_ids, boundary = G.group_segments(key_cols, batch.num_rows,
                                                       live=live)
            n_groups = LazyRowCount(jnp.sum(boundary.astype(jnp.int32)))
            seg_cap = cap
            out_cap = cap
        else:
            perm = jnp.arange(cap, dtype=jnp.int32)
            seg_ids = jnp.zeros(cap, jnp.int32)
            boundary = jnp.zeros(cap, jnp.bool_).at[0].set(True)
            n_groups = 1
            seg_cap = 1
            out_cap = round_capacity(1)
        out_cols = []
        if nkeys:
            for c in G.gather_group_keys(key_cols, perm, boundary, n_groups,
                                         batch.num_rows, live=live):
                out_cols.append(_resize_col(c, out_cap))
        ci = nkeys
        for a in self.aggs:
            for (sname, sdt), op in zip(a.fn.state_schema(), a.fn.merge_ops()):
                src = batch.columns[ci]
                ci += 1
                sorted_vals = src.data[perm]
                src_valid = live if src.validity is None else (src.validity & live)
                ov, oval = G.segmented_agg(op, sorted_vals, src_valid[perm],
                                           seg_ids, seg_cap)
                out_cols.append(_resize_plane(ov, oval, sdt, out_cap))
        return ColumnarBatch(out_cols, n_groups)

    def _evaluate_states(self, state: ColumnarBatch) -> ColumnarBatch:
        nkeys = len(self.group_exprs)
        out_cols = list(state.columns[:nkeys])
        ci = nkeys
        for a in self.aggs:
            n_state = len(a.fn.state_schema())
            scols = state.columns[ci: ci + n_state]
            ci += n_state
            res = a.fn.evaluate_tpu(scols, state.num_rows)
            # clamp dtype
            rt = a.fn.result_type()
            if not res.is_string and not res.is_nested \
                    and res.data.dtype != np.dtype(rt.np_dtype):
                res = ColumnVector(rt, res.data.astype(rt.np_dtype), res.validity)
            out_cols.append(res)
        return ColumnarBatch(out_cols, state.num_rows, state.row_mask)


def _float_order(spec) -> bool:
    """A window spec ordered by exactly one float key."""
    return len(spec.order_specs) == 1 and isinstance(
        spec.order_specs[0].expr.data_type(),
        (T.Float32Type, T.Float64Type))


class WindowExec(TpuExec):
    """Window evaluation: one sort by (partition, order) keys, then every
    window function as fused segmented scans (reference GpuWindowExec /
    GpuRunningWindowExec; the whole node is ONE device dispatch)."""

    def execute_partition(self, ctx, pidx):
        from spark_rapids_tpu.ops import window as W
        from spark_rapids_tpu.expr import window as WE
        win_t = self.metrics.metric(M.OP_TIME)
        batches = list(self.children[0].execute_partition(ctx, pidx))
        if not batches:
            return
        self._acquire(ctx)
        batch = K.concat_batches(batches) if len(batches) > 1 else batches[0]
        if batch.row_mask is not None:
            batch = K.compact_batch(batch)
        exprs = self.plan.window_exprs
        spec = exprs[0].spec  # one spec per node (planner groups)

        # packed-radix sort path: all (partition, order) keys compressed
        # into ONE int64 plane -> single-key stable argsort + boundary
        # diffs on the packed plane. The general multi-operand u64
        # lax.sort below takes MINUTES to compile on TPU and pays one
        # gather per key plane; this path is one sort + one gather.
        nparts = len(spec.partition_exprs)
        key_exprs = list(spec.partition_exprs) + [o.expr
                                                  for o in spec.order_specs]
        pspec = ranges = None
        if key_exprs:
            kcols = compiled.run_stage(key_exprs, batch)
            pspec, ranges, _ = _probe_pack_spec(kcols, batch.live_mask(),
                                                key_exprs)
            if pspec is not None and not all(
                    k in (R.KIND_INT, R.KIND_BOOL)
                    for k in pspec.kinds[nparts:]):
                pspec = None  # dict codes are not value-ordered
            if pspec is None and _float_order(spec):
                # one float order key (a rank over a sum): its 64-bit
                # order image is a plane of its own behind the packed
                # partition keys, and the shared single-plane argsort
                # sorts by the two (_argsort_planes)
                pk, pranges, _ = _probe_pack_spec(
                    kcols[:nparts], batch.live_mask(),
                    list(spec.partition_exprs))
                if pk is not None and pk.total_bits < R.MAX_PACK_BITS:
                    yield self._wide_sorted(batch, pk, pranges, win_t)
                    return

        def build_packed(pk):
            flags = [(True, True)] * nparts + \
                [(o.ascending, o.resolved_nulls_first())
                 for o in spec.order_specs]
            obits = sum(pk.bits[nparts:])

            def fn(batch, ranges):
                from spark_rapids_tpu.ops import window as W  # noqa: F811
                nr = traced_rows(batch.num_rows)
                cap = batch.capacity
                ectx = EvalCtx(batch.columns, nr, cap, False)
                kcols = [e.eval_tpu(ectx) for e in key_exprs]
                live = jnp.arange(cap) < nr
                packed = R.pack_keys_sort(pk, kcols, ranges, live, flags)
                perm = jnp.argsort(packed, stable=True).astype(jnp.int32)
                sp = packed[perm]
                first = jnp.zeros(cap, jnp.bool_).at[0].set(True)
                part_plane = sp >> jnp.int64(obits)
                segb = first | jnp.concatenate(
                    [jnp.zeros(1, jnp.bool_),
                     part_plane[1:] != part_plane[:-1]])
                peerb = first | jnp.concatenate(
                    [jnp.zeros(1, jnp.bool_), sp[1:] != sp[:-1]])
                seg_start, seg_end, peer_start, peer_end = \
                    W.segment_layout(segb, peerb)
                seg_end = jnp.minimum(
                    seg_end, jnp.maximum(nr - 1, 0).astype(seg_end.dtype))
                peer_end = jnp.minimum(peer_end, seg_end)
                seg_id = jnp.cumsum(segb.astype(jnp.int32))
                idx = jnp.arange(cap, dtype=jnp.int32)
                # pass-through columns stay in ORIGINAL row order (window
                # output order is unspecified); window results compute in
                # sorted space and scatter back — data columns are only
                # gathered if a frame agg / lead-lag reads them
                sctx = EvalCtx([], nr, cap, False)
                sctx.columns = K.LazyGatheredCols(batch.columns, perm,
                                                  batch.num_rows)
                out_cols = list(batch.columns)
                for w in exprs:
                    wc = _eval_window_fn(
                        w, sctx, seg_start, seg_end, peer_start, peer_end,
                        seg_id, segb, peerb, idx, live)
                    out_cols.append(_scatter_window_output(
                        wc, perm, cap, live, batch.num_rows))
                return ColumnarBatch(out_cols, batch.num_rows)
            return fn

        def _layout_of(pk, batch, ranges):
            flags = [(True, True)] * nparts + \
                [(o.ascending, o.resolved_nulls_first())
                 for o in spec.order_specs]
            obits = sum(pk.bits[nparts:])
            nr = traced_rows(batch.num_rows)
            cap = batch.capacity
            ectx = EvalCtx(batch.columns, nr, cap, False)
            kcols = [e.eval_tpu(ectx) for e in key_exprs]
            live = jnp.arange(cap) < nr
            packed = R.pack_keys_sort(pk, kcols, ranges, live, flags)
            perm = jnp.argsort(packed, stable=True).astype(jnp.int32)
            sp = packed[perm]
            first = jnp.zeros(cap, jnp.bool_).at[0].set(True)
            part_plane = sp >> jnp.int64(obits)
            segb = first | jnp.concatenate(
                [jnp.zeros(1, jnp.bool_),
                 part_plane[1:] != part_plane[:-1]])
            peerb = first | jnp.concatenate(
                [jnp.zeros(1, jnp.bool_), sp[1:] != sp[:-1]])
            seg_start, seg_end, peer_start, peer_end = \
                W.segment_layout(segb, peerb)
            seg_end = jnp.minimum(
                seg_end, jnp.maximum(nr - 1, 0).astype(seg_end.dtype))
            peer_end = jnp.minimum(peer_end, seg_end)
            seg_id = jnp.cumsum(segb.astype(jnp.int32))
            return (perm, seg_start, seg_end, peer_start, peer_end,
                    seg_id, segb, peerb, live)

        def build_sort_layout(pk):
            def fn(batch, ranges):
                from spark_rapids_tpu.ops import window as W  # noqa: F811
                return _layout_of(pk, batch, ranges)
            return fn

        def build_apply_fns(pk):
            def fn(batch, perm, seg_start, seg_end, peer_start, peer_end,
                   seg_id, segb, peerb, live):
                nr = traced_rows(batch.num_rows)
                cap = batch.capacity
                idx = jnp.arange(cap, dtype=jnp.int32)
                sctx = EvalCtx([], nr, cap, False)
                sctx.columns = K.LazyGatheredCols(batch.columns, perm,
                                                  batch.num_rows)
                out_cols = list(batch.columns)
                for w in exprs:
                    wc = _eval_window_fn(
                        w, sctx, seg_start, seg_end, peer_start, peer_end,
                        seg_id, segb, peerb, idx, live)
                    out_cols.append(_scatter_window_output(
                        wc, perm, cap, live, batch.num_rows))
                return ColumnarBatch(out_cols, batch.num_rows)
            return fn


        from spark_rapids_tpu.expr import window as WEm
        has_window_agg = any(isinstance(w.fn, WEm.WindowAgg) for w in exprs)
        if pspec is not None and has_window_agg:
            # two dispatches for frame-aggregation windows: the fully
            # fused sort+cumsum+gather pipeline for THIS shape wedged the
            # TPU compiler (observed in round 4: window-ratio NDS queries
            # hang >10 min in compile); splitting at the sort boundary
            # changes the fusion islands and compiles
            kA = ("window_sortlay", tuple(e.fingerprint()
                                          for e in key_exprs),
                  tuple((o.ascending, o.resolved_nulls_first())
                        for o in spec.order_specs), pspec.key)
            kB = ("window_fns", tuple(w.fingerprint() for w in exprs),
                  pspec.key)
            fnA = fuse.fused(kA, lambda: build_sort_layout(pspec))
            fnB = fuse.fused(kB, lambda: build_apply_fns(pspec))
            with self.span(win_t):
                lay = fnA(batch, ranges)
                out = fnB(batch, *lay)
            carry_host_stats(batch.columns, out.columns)
            yield out
            return
        if pspec is not None:
            key = ("window_packed", tuple(w.fingerprint() for w in exprs),
                   pspec.key)
            fn = fuse.fused(key, lambda: build_packed(pspec))
            with self.span(win_t):
                out = fn(batch, ranges)
            carry_host_stats(batch.columns, out.columns)
            yield out
            return

        def build():
            def fn(batch):
                nr = traced_rows(batch.num_rows)
                ectx = EvalCtx(batch.columns, nr, batch.capacity, False)
                pkeys = [e.eval_tpu(ectx) for e in spec.partition_exprs]
                okeys = [o.expr.eval_tpu(ectx) for o in spec.order_specs]
                pnorm = [K.normalize_key(c, nr) for c in pkeys]
                onorm = [K.normalize_key(c, nr) for c in okeys]
                sort_keys = [(k, nl, True, True) for k, nl in pnorm]
                sort_keys += [(k, nl, o.ascending, o.resolved_nulls_first())
                              for (k, nl), o in zip(onorm, spec.order_specs)]
                if not sort_keys:
                    perm = jnp.arange(batch.capacity, dtype=jnp.int32)
                else:
                    perm = K.lexsort_indices(sort_keys, nr)
                sorted_batch = K.gather_batch(batch, perm, batch.num_rows)
                cap = batch.capacity
                first = jnp.zeros(cap, jnp.bool_).at[0].set(True)
                segb = first
                for k, nl in pnorm:
                    ks, ns = k[perm], nl[perm]
                    segb = segb | jnp.concatenate(
                        [jnp.zeros(1, jnp.bool_),
                         (ks[1:] != ks[:-1]) | (ns[1:] != ns[:-1])])
                peerb = segb
                for k, nl in onorm:
                    ks, ns = k[perm], nl[perm]
                    peerb = peerb | jnp.concatenate(
                        [jnp.zeros(1, jnp.bool_),
                         (ks[1:] != ks[:-1]) | (ns[1:] != ns[:-1])])
                seg_start, seg_end, peer_start, peer_end = \
                    W.segment_layout(segb, peerb)
                live = jnp.arange(cap) < nr
                seg_end = jnp.minimum(seg_end,
                                      jnp.maximum(nr - 1, 0).astype(seg_end.dtype))
                peer_end = jnp.minimum(peer_end, seg_end)
                seg_id = jnp.cumsum(segb.astype(jnp.int32))
                idx = jnp.arange(cap, dtype=jnp.int32)
                sctx = EvalCtx(sorted_batch.columns, nr, cap, False)
                out_cols = list(sorted_batch.columns)
                for w in exprs:
                    out_cols.append(_eval_window_fn(
                        w, sctx, seg_start, seg_end, peer_start, peer_end,
                        seg_id, segb, peerb, idx, live))
                return ColumnarBatch(out_cols, batch.num_rows)
            return fn

        key = ("window", tuple(w.fingerprint() for w in exprs))
        fn = fuse.fused(key, build)
        with self.span(win_t):
            out = fn(batch)
        carry_host_stats(batch.columns, out.columns)
        yield out

    def _wide_sorted(self, batch, pk, pranges, win_t) -> ColumnarBatch:
        """The window over a sort by two planes: the partition keys packed
        (with the order key's null rank in the lowest bit), and the order
        image of the one float order key. Two keyed programs around the
        shared argsort; pass-through columns keep their original order."""
        exprs = self.plan.window_exprs
        spec = exprs[0].spec
        nparts = len(spec.partition_exprs)
        order = spec.order_specs[0]
        key_exprs = list(spec.partition_exprs) + [order.expr]
        asc, nulls_first = order.ascending, order.resolved_nulls_first()
        pbits = pk.total_bits + 2   # the null rank's bit, the dead rows'

        def build_keys():
            def window_keys(batch, ranges):
                nr = traced_rows(batch.num_rows)
                cap = batch.capacity
                ectx = EvalCtx(batch.columns, nr, cap, False)
                kcols = [e.eval_tpu(ectx) for e in key_exprs]
                live = jnp.arange(cap) < nr
                part = R.pack_keys_sort(pk, kcols[:nparts], ranges, live,
                                        [(True, True)] * nparts)
                oc = kcols[nparts]
                img = R._f64_order_i64(oc.data.astype(jnp.float64))
                img = img if asc else ~img
                valid = live if oc.validity is None else (oc.validity & live)
                rank = valid if nulls_first else ~valid  # 0 sorts first
                # the image's sign bit flipped: its order as an unsigned
                # word; a row past the last sorts behind every live one
                return (jnp.where(live, (part << jnp.int64(1))
                                  | rank.astype(jnp.int64),
                                  jnp.int64(1) << jnp.int64(pbits - 1)),
                        jnp.where(valid, img.astype(jnp.uint64)
                                  ^ (jnp.uint64(1) << jnp.uint64(63)),
                                  jnp.uint64(0)))
            return window_keys

        def build_apply():
            def window_apply(batch, perm, plane0, plane1):
                from spark_rapids_tpu.ops import window as W
                nr = traced_rows(batch.num_rows)
                cap = batch.capacity
                live = jnp.arange(cap) < nr
                s0, s1 = plane0[perm], plane1[perm]
                first = jnp.zeros(cap, jnp.bool_).at[0].set(True)
                tail = jnp.zeros(1, jnp.bool_)
                part = s0 >> jnp.int64(1)
                segb = first | jnp.concatenate(
                    [tail, part[1:] != part[:-1]])
                peerb = segb | jnp.concatenate(
                    [tail, (s0[1:] != s0[:-1]) | (s1[1:] != s1[:-1])])
                seg_start, seg_end, peer_start, peer_end = \
                    W.segment_layout(segb, peerb)
                seg_end = jnp.minimum(
                    seg_end, jnp.maximum(nr - 1, 0).astype(seg_end.dtype))
                peer_end = jnp.minimum(peer_end, seg_end)
                seg_id = jnp.cumsum(segb.astype(jnp.int32))
                idx = jnp.arange(cap, dtype=jnp.int32)
                sctx = EvalCtx([], nr, cap, False)
                sctx.columns = K.LazyGatheredCols(batch.columns, perm,
                                                  batch.num_rows)
                out_cols = list(batch.columns)
                for w in exprs:
                    wc = _eval_window_fn(
                        w, sctx, seg_start, seg_end, peer_start, peer_end,
                        seg_id, segb, peerb, idx, live)
                    out_cols.append(_scatter_window_output(
                        wc, perm, cap, live, batch.num_rows))
                return ColumnarBatch(out_cols, batch.num_rows)
            return window_apply

        kfp = (tuple(e.fingerprint() for e in key_exprs), asc, nulls_first,
               pk.key)
        keys = fuse.fused(("window_keys",) + kfp, build_keys)
        apply = fuse.fused(("window_apply", tuple(w.fingerprint()
                                                  for w in exprs), pk.key),
                           build_apply)
        t0 = time.perf_counter_ns()
        with self.span(win_t):
            with self.span(self.metrics.metric(M.WINDOW_SORT_TIME)):
                planes = keys(batch, pranges)
                perm = _argsort_planes(list(planes), [pbits, 64])
            device_mark(self.metrics.metric(M.WINDOW_SORT_DEVICE_TIME),
                        perm, t0)
            out = apply(batch, perm, *planes)
        carry_host_stats(batch.columns, out.columns)
        return out


# Module-level (state-free) window kernels: the fused builder closure is
# cached process-global by expr fingerprint, so it must capture only the
# bound window exprs/spec — never the exec node, whose child tree can pin
# HBM-resident cached batches for the process lifetime (same hazard the
# _AggKernels class exists to avoid).
def _scatter_window_output(col: ColumnVector, perm, cap, live_orig,
                           num_rows):
    """Sorted-space window result -> original row order (one inverse-perm
    gather instead of gathering every output column into sorted order).
    gather_column handles every plane layout (dict strings from lead/lag
    included); XLA CSEs the shared inverse permutation across outputs."""
    inv = jnp.zeros(cap, jnp.int32).at[perm].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")
    out = K.gather_column(col, inv, num_rows)
    valid = out.validity & live_orig if out.validity is not None \
        else live_orig
    return ColumnVector(out.dtype, out.data, valid,
                        dict_unique=out.dict_unique)


def _eval_window_fn(w, sctx, seg_start, seg_end, peer_start,
                    peer_end, seg_id, segb, peerb, idx, live):
    from spark_rapids_tpu.ops import window as W
    from spark_rapids_tpu.expr import window as WE
    fn = w.fn
    frame = w.spec.resolved_frame()
    rt = fn.result_type()
    if isinstance(fn, WE.RowNumber):
        return ColumnVector(rt, W.row_number(seg_start), live)
    if isinstance(fn, WE.Rank):
        return ColumnVector(rt, W.rank(seg_start, peer_start), live)
    if isinstance(fn, WE.DenseRank):
        return ColumnVector(rt, W.dense_rank(segb, peerb, seg_start), live)
    if isinstance(fn, WE.NTile):
        return ColumnVector(rt, W.ntile(fn.n, seg_start, seg_end), live)
    if isinstance(fn, WE.LeadLag):
        src = fn.children[0].eval_tpu(sctx)
        off = fn.offset if fn.is_lead else -fn.offset
        svalid = src.validity if src.validity is not None else live
        vals, valid = W.lead_lag(src.data, svalid, seg_id, off)
        if fn.default is not None:
            in_seg = (idx + off >= seg_start) & (idx + off <= seg_end)
            dv = jnp.asarray(fn.default, src.data.dtype)
            vals = jnp.where(~in_seg, dv, vals)
            valid = valid | ~in_seg
        return ColumnVector(src.dtype, vals, valid & live)
    if isinstance(fn, WE.PercentRank):
        n_seg = (seg_end - seg_start + 1).astype(jnp.float64)
        rk = W.rank(seg_start, peer_start).astype(jnp.float64)
        v = jnp.where(n_seg > 1, (rk - 1.0) / jnp.maximum(n_seg - 1.0, 1.0),
                      0.0)
        return ColumnVector(rt, v, live)
    if isinstance(fn, WE.CumeDist):
        n_seg = (seg_end - seg_start + 1).astype(jnp.float64)
        v = (peer_end - seg_start + 1).astype(jnp.float64) / n_seg
        return ColumnVector(rt, v, live)
    if isinstance(fn, (WE.NthValue, WE.FirstValue, WE.LastValue)):
        src = fn.children[0].eval_tpu(sctx)
        svalid = src.validity if src.validity is not None else live
        if frame.lower is None and frame.upper is None:
            frame_end = seg_end
        elif frame.kind == "rows":
            frame_end = idx if frame.upper == 0 else seg_end
        else:
            frame_end = peer_end if frame.upper == 0 else seg_end
        if isinstance(fn, WE.LastValue):
            pos = frame_end
            ok = live
        elif isinstance(fn, WE.FirstValue):
            pos = seg_start
            ok = live
        else:
            pos = seg_start + (fn.n - 1)
            ok = live & (pos <= frame_end)
        from spark_rapids_tpu.ops import kernels as _K
        gathered = _K.gather_column(
            src, jnp.where(ok, jnp.clip(pos, 0, idx.shape[0] - 1), -1),
            idx.shape[0], src_live=svalid)
        return ColumnVector(gathered.dtype, gathered.data, gathered.validity,
                            dict_unique=gathered.dict_unique)
    if isinstance(fn, WE.WindowAgg):
        return _eval_window_agg(fn, frame, sctx, seg_start, seg_end,
                                peer_end, seg_id, idx, live)
    raise NotImplementedError(type(fn).__name__)


def _eval_window_agg(fn, frame, sctx, seg_start, seg_end,
                     peer_end, seg_id, idx, live):
    from spark_rapids_tpu.ops import window as W
    from spark_rapids_tpu.expr import aggregates as A
    agg = fn.fn
    rt = agg.result_type()
    if agg.children:
        src = agg.children[0].eval_tpu(sctx)
        vals = src.data
        svalid = (src.validity if src.validity is not None else live) & live
    else:  # count(*)
        vals = jnp.ones(idx.shape[0], jnp.int64)
        svalid = live
    # frame end per row
    if frame.kind == "range":
        frame_end = peer_end if frame.upper == 0 else seg_end
    else:
        frame_end = idx if frame.upper == 0 else seg_end
    unbounded = frame.lower is None and frame.upper is None
    bounded_rows = frame.kind == "rows" and not (
        frame.lower is None and frame.upper == 0) and not unbounded

    def sum_count():
        if bounded_rows:
            v = vals
            if isinstance(agg, A.Average):
                v = v.astype(jnp.float64)
            elif not jnp.issubdtype(v.dtype, jnp.floating):
                v = v.astype(jnp.int64)
            return W.bounded_sum_count(v, svalid, seg_start, seg_end,
                                       frame.lower, frame.upper)
        fe = seg_end if unbounded else frame_end
        v = vals
        if isinstance(agg, (A.Sum, A.Average)) and \
                not jnp.issubdtype(v.dtype, jnp.floating):
            v = v.astype(jnp.int64)
        if isinstance(agg, A.Average):
            v = v.astype(jnp.float64)
        return W.running_sum_count(v, svalid, seg_start, fe)

    if isinstance(agg, A.Average):
        s, c = sum_count()
        return ColumnVector(rt, s / jnp.maximum(c, 1), (c > 0) & live)
    if isinstance(agg, A.Sum):
        s, c = sum_count()
        return ColumnVector(rt, s.astype(rt.np_dtype), (c > 0) & live)
    if isinstance(agg, (A.Count, A.CountAll)):
        s, c = sum_count()
        cnt = c if isinstance(agg, A.Count) else None
        if isinstance(agg, A.CountAll):
            # count(*) counts rows regardless of validity
            if bounded_rows:
                ones = jnp.ones(idx.shape[0], jnp.int64)
                s2, _ = W.bounded_sum_count(ones, live, seg_start, seg_end,
                                            frame.lower, frame.upper)
                cnt = s2
            else:
                fe = seg_end if unbounded else frame_end
                s2, _ = W.running_sum_count(
                    jnp.ones(idx.shape[0], jnp.int64), live, seg_start, fe)
                cnt = s2
        return ColumnVector(T.INT64, cnt.astype(jnp.int64),
                            jnp.ones_like(live) & live)
    if isinstance(agg, (A.Min, A.Max)):
        op = "min" if isinstance(agg, A.Min) else "max"
        fe = seg_end if unbounded else frame_end
        v, c = W.running_minmax(op, vals, svalid, seg_id, seg_start, fe)
        return ColumnVector(rt, v.astype(rt.np_dtype), (c > 0) & live)
    raise NotImplementedError(type(agg).__name__)


class HashAggregateExec(TpuExec):
    """Sort-based segmented aggregation in three phases (reference
    GpuAggregateExec.scala three-pass design §2.4):
    - partial: per input batch, evaluate keys + agg inputs as one fused
      stage, group, apply update reductions -> (keys, state) batches
    - within-partition merge: concat partials, re-group, merge reductions
    - final: merge again post-exchange and run each agg's evaluate
    State layout: [key_0..key_k, agg0_state0.., agg1_state0..].
    """

    def __init__(self, plan, children, conf, mode: str, pre_filter=None):
        super().__init__(plan, children, conf)
        assert mode in ("partial", "final", "complete")
        self.mode = mode
        self.kern = _AggKernels(plan.group_exprs, plan.group_names,
                                plan.aggs, pre_filter)
        # A filter condition absorbed into the update kernel (predicate
        # fusion): scan -> filter -> partial agg runs as ONE dispatch.
        self.pre_filter = pre_filter
        #: the same kernels without the filter, for a batch in which the
        #: filter's string match meets a flat column: the Filter then runs
        #: as its own program before them (`meets_flat_string`)
        self._kern_apart = _AggKernels(
            plan.group_exprs, plan.group_names, plan.aggs, None) \
            if pre_filter is not None and holds_string_match([pre_filter]) \
            else None
        #: whole-stage vertical fusion (exec/stage_fusion.py): traced
        #: bodies of a narrow-operator chain composed BEFORE the update
        #: phase inside one jit — scan -> filter -> project -> partial agg
        #: is then exactly one dispatch per input batch. Set by the
        #: planner pass; only carry-free bodies are absorbed (retry may
        #: re-run the composed trace on a split batch).
        self.pre_chain: Optional[List[fuse.StageBody]] = None
        self.pre_chain_members: List[TpuExec] = []
        self.fused_stage_id = 0
        self._chain_failed = False
        #: mesh size when the planner (exec/sharded.shard_stages) found
        #: this partial aggregate over a cached table: the update phase
        #: of all partitions then runs as SPMD waves over the resident
        #: shards, and only the partial states leave the chips
        self.shard_over = 0
        self._shard_out = None  # per-partition states; False = unsharded
        self._shard_lock = threading.Lock()

    # ---- schema of the partial (state) batches ----
    def state_fields(self):
        fields = [T.StructField(n, e.data_type())
                  for n, e in zip(self.plan.group_names, self.plan.group_exprs)]
        for a in self.plan.aggs:
            for sname, sdt in a.fn.state_schema():
                fields.append(T.StructField(f"{a.name}__{sname}", sdt))
        return fields

    @property
    def schema(self):
        if self.mode == "partial":
            return T.Schema(tuple(self.state_fields()))
        return self.plan.schema

    def _sig(self, phase: str, ansi: bool = False):
        p = self.plan
        gfp = tuple(e.fingerprint() for e in p.group_exprs)
        afp = tuple(a.fn.fingerprint() for a in p.aggs)
        pf = self.pre_filter.fingerprint() if self.pre_filter is not None else None
        return ("hashagg", phase, gfp, afp, ansi, pf)

    # -- whole-stage fusion (absorbed narrow-operator chain) ---------------

    def _chain_key(self, ansi: bool):
        return ("hashagg_chain_update",
                tuple(b.key for b in self.pre_chain),
                self._sig("update", ansi))

    def _build_chain_update(self, ansi: bool):
        bodies = list(self.pre_chain)
        kern = self.kern

        def build():
            fns = [b.builder() for b in bodies]
            upd = kern._build_update(ansi)
            zero = jnp.int64(0)

            def fn(batch, pid):
                errs_all, rows = [], []
                for f in fns:
                    batch, errs, _ = f(batch, pid, zero)
                    errs_all.append(errs)
                    rows.append(jnp.sum(
                        batch.live_mask().astype(jnp.int64)))
                out, uerrs = upd(batch)
                errs_all.append(uerrs)
                return out, tuple(errs_all), tuple(rows)
            return fn
        return build

    def _unfused_pre_chain(self, source):
        from spark_rapids_tpu.exec.stage_fusion import rebuild_chain
        return rebuild_chain(self.pre_chain_members, source)

    def _chain_apart(self, ctx, pidx, batch, rest):
        """`batch` and the `rest` of the input through the member chain,
        each operator its own program."""
        from spark_rapids_tpu.exec.stage_fusion import _ReplaySourceExec
        src = _ReplaySourceExec(self.children[0].schema, [batch], rest)
        return self._unfused_pre_chain(src).execute_partition(ctx, pidx)

    def tree_string(self, indent: int = 0) -> str:
        notes = (f" [sharded n={self.shard_over}]" if self.shard_over
                 else "") + (" [string match: apart over a flat column]"
                             if self._kern_apart is not None else "")
        if not self.pre_chain_members:
            head, nl, rest = super().tree_string(indent).partition("\n")
            return f"{head}{notes}{nl}{rest}"
        pad = "  " * indent
        sid = self.fused_stage_id
        lines = [f"{pad}*({sid}) {self.name()} <- {self.plan.describe()}"
                 + notes]
        for m in reversed(self.pre_chain_members):
            lines.append(f"{pad}  *({sid}) {type(m).__name__} "
                         f"<- {m.plan.describe()} [fused]")
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    # -- the update phase where the shards live (exec/sharded.py) ----------

    def _sharded_states(self, ctx) -> Optional[List[ColumnarBatch]]:
        """One partial-state batch a partition, computed by SPMD waves
        over the child cache's resident shards (once, for all
        partitions); None when the shards are not in place or the
        program does not trace: the per-partition path then runs."""
        with self._shard_lock:
            if self._shard_out is None:
                self._shard_out = self._run_sharded(ctx) or False
        return self._shard_out or None

    def _run_sharded(self, ctx) -> Optional[List[ColumnarBatch]]:
        from spark_rapids_tpu.exec.sharded import MeshWave, input_refs
        from spark_rapids_tpu.expr.core import SparkException
        from spark_rapids_tpu.parallel.mesh import MeshDeviceError
        child, m, kern = self.children[0], self.shard_over, self.kern
        nparts = child.num_partitions
        if nparts % m:
            return None
        ansi = self.conf.get(C.ANSI_ENABLED)
        wave = self.shard_wave = MeshWave(
            m, self.pre_chain or [], [f.dtype for f in child.schema.fields],
            input_refs(self.pre_chain_members,
                       kern._state_input_exprs() + [self.pre_filter]),
            tail=lambda: kern._build_update(ansi),
            tail_key=self._sig("update", ansi))
        state_dtypes = [f.dtype for f in self.state_fields()]
        disp_t = self.metrics.metric(M.SHARD_DISPATCH_TIME)
        back_t = self.metrics.metric(M.SHARD_READBACK_TIME)
        member_rows = [mb.metrics.metric(M.NUM_OUTPUT_ROWS)
                       for mb in self.pre_chain_members]
        outs: List[ColumnarBatch] = []
        for g0 in range(0, nparts, m):
            pids = list(range(g0, g0 + m))
            operands = wave.resident_operands(child.resident_shards(pids))
            if operands is None:
                return None
            self._acquire(ctx)
            try:
                with self.span(disp_t):
                    out = wave.dispatch(operands, pids)
                with self.span(back_t):
                    states, rows = wave.read_back(out, range(m),
                                                  state_dtypes)
            except (SparkException, MeshDeviceError,
                    LC.QueryCancelledError):
                raise  # typed errors are not trace failures
            except Exception:  # noqa: BLE001 - the per-stage fallback
                import logging
                logging.getLogger("spark_rapids_tpu").warning(
                    "sharded update trace failed for %s; falling back to "
                    "the per-partition update", self.name(), exc_info=True)
                from spark_rapids_tpu.runtime import obs as _obs
                _obs.note_exec_fallback("sharded_agg")
                return None
            self.metrics.metric(M.STAGE_DISPATCHES).add(1)
            self.metrics.metric(M.SHARD_WAVES).add(1)
            self.metrics.metric(M.NUM_INPUT_BATCHES).add(m)
            for mr, r in zip(member_rows, rows):
                mr.add(int(r.sum()))
            outs.extend(states[i] for i in range(m))
        return outs

    def execute_partition(self, ctx, pidx):
        agg_t = self.metrics.metric(M.DISTINCT_AGG_TIME
                                    if self.plan.distinct_level
                                    else M.AGG_TIME)
        if self.shard_over:
            states = self._sharded_states(ctx)
            if states is not None:
                self.metrics.metric(M.NUM_OUTPUT_ROWS).add(
                    states[pidx].num_rows)
                self.metrics.metric(M.NUM_OUTPUT_BATCHES).add(1)
                yield states[pidx]
                return
        child_batches = self.children[0].execute_partition(ctx, pidx)
        nkeys = len(self.plan.group_exprs)

        if self.mode in ("partial", "complete"):
            ansi = self.conf.get(C.ANSI_ENABLED)
            from spark_rapids_tpu.runtime.retry import with_retry

            filter_apart = self._kern_apart and _FilterRun(
                self, self.pre_filter)

            def plain_attempt(b):
                # raise_errors inside the attempt so ANSI-mode syncs
                # (and any device OOM they surface) are seen by the
                # retry loop. Note: under async dispatch a physical
                # RESOURCE_EXHAUSTED can still surface at a LATER sync
                # point; the cooperative budget (SpillFramework.
                # reserve) is the primary defense, this translation is
                # best-effort.
                kern = self.kern
                if filter_apart and meets_flat_string(b):
                    kept, errs, _ = filter_apart(b, jnp.int32(pidx),
                                                 jnp.int64(0))
                    compiled.raise_errors(errs)
                    carry_host_stats(b.columns, kept.columns)
                    b, kern = kept, self._kern_apart
                if self.plan.distinct_level:
                    b, kern = self._distinct_inputs(b, kern, ansi), \
                        kern.over_inputs()
                out, errs = kern.update(b, ansi)
                compiled.raise_errors(errs)
                return out

            attempt = plain_attempt
            chain_live = chain_matches = False
            chain_in_rows = [None]  # update-phase input rows (device)
            in_batches = self.metrics.metric(M.NUM_INPUT_BATCHES)
            if self.pre_chain and self._chain_failed:
                # an earlier partition's composed trace failed: run the
                # unfused member chain in front of the plain update
                child_batches = self._unfused_pre_chain(
                    self.children[0]).execute_partition(ctx, pidx)
            elif self.pre_chain:
                chain_fn = fuse.fused(self._chain_key(ansi),
                                      self._build_chain_update(ansi))
                pid = jnp.int32(pidx)
                disp = self.metrics.metric(M.STAGE_DISPATCHES)
                member_rows = [m.metrics.metric(M.NUM_OUTPUT_ROWS)
                               for m in self.pre_chain_members]

                def chain_attempt(b):
                    # the absorbed chain + update phase is ONE composed
                    # trace, idempotent over its input (chain bodies are
                    # carry-free by the absorb gate), so retry/split-retry
                    # treat it exactly like a plain update
                    disp.add(1)
                    if TR.active() is not None:  # args gated when off
                        TR.instant("stageDispatch", cat="dispatch", args={
                            "stage_id": self.fused_stage_id,
                            "absorbed": True,
                            # chain members + the update phase, composed
                            # into this ONE dispatch (the report's
                            # fusion-wins denominator)
                            "members": len(self.pre_chain_members) + 1})
                    out, errs_list, rows = chain_fn(b, pid)
                    for e in errs_list:
                        compiled.raise_errors(e)
                    for mr, r in zip(member_rows, rows):
                        mr.add(LazyRowCount(r))
                    if rows:  # what the update phase actually saw
                        chain_in_rows[0] = rows[-1]
                    return out

                attempt = chain_attempt
                chain_live = True
                chain_matches = any(stage_matches_strings(m)
                                    for m in self.pre_chain_members)

            if (self.conf.get(C.AGG_FORCE_SINGLE_PASS) and nkeys > 0) \
                    or self.kern.has_custom:
                # One update pass over the concatenated input: the testing
                # knob (reference forceSinglePassPartialSortAgg), and the
                # REQUIRED path for custom segmented aggs (collect_*,
                # min_by/max_by, percentile) whose results cannot merge —
                # the planner already exchanged raw rows by key for them.
                batches = list(child_batches)
                child_batches = iter(
                    [K.concat_batches(batches)] if len(batches) > 1 else batches)

            skip_ratio = self.conf.get(C.SKIP_AGG_PASS_RATIO)
            skip_merge = False
            partials = []
            it = iter(child_batches)
            bi = -1
            while True:
                batch = next(it, None)
                if batch is None:
                    break
                bi += 1
                if chain_live and chain_matches and meets_flat_string(batch):
                    # the absorbed chain matches strings and this batch
                    # brings a flat column: the members run apart from
                    # here on (no fault, nothing noted)
                    chain_live, attempt = False, plain_attempt
                    it = self._chain_apart(ctx, pidx, batch, it)
                    bi -= 1
                    continue
                self._acquire(ctx)
                in_batches.add(1)
                n_before = len(partials)
                t0 = time.perf_counter_ns()
                try:
                    with self.span(agg_t):
                        # update is idempotent over its input batch:
                        # retried after a spill drain, or split in half,
                        # on OOM
                        for out in with_retry(attempt, batch):
                            if nkeys == 0:
                                out = ColumnarBatch(out.columns, 1)
                            partials.append(out)
                    if len(partials) > n_before:
                        self._mark_device(partials[-1], t0)
                except Exception as ex:
                    from spark_rapids_tpu.expr.core import SparkException
                    if not chain_live or isinstance(ex, SparkException):
                        # ANSI/analysis errors are deterministic runtime
                        # errors, never trace failures — replaying them
                        # through the unfused chain would double the work
                        # just to raise the same error
                        raise
                    # per-stage fallback (the stageFusion contract): the
                    # composed chain+update trace failed — drop this
                    # batch's partials (update is idempotent), route the
                    # batch and the rest of the input through the unfused
                    # member chain, and continue with the plain update
                    import logging
                    logging.getLogger("spark_rapids_tpu").warning(
                        "absorbed-chain trace failed for %s; falling back"
                        " to the unfused chain", self.name(),
                        exc_info=True)
                    del partials[n_before:]
                    self._chain_failed = True
                    from spark_rapids_tpu.runtime import obs as _obs
                    _obs.note_exec_fallback("absorbed_chain")
                    chain_live = False
                    attempt = plain_attempt
                    it = self._chain_apart(ctx, pidx, batch, it)
                    bi -= 1
                    continue
                if bi == 0 and skip_ratio < 1.0 and nkeys > 0 \
                        and self.mode == "partial":
                    # Reference skipAggPassReductionRatio: when the first
                    # batch's update barely reduced rows (groups/rows above
                    # the ratio), skip the within-partition merge pass and
                    # defer cross-batch merging to the post-exchange final
                    # agg. Sampled on the first batch only — row counts
                    # live on device and each fetch is a host sync. With an
                    # absorbed chain, the ratio is against the CHAIN's
                    # output (the rows the update phase actually saw), not
                    # the raw scan batch.
                    src_rows = (chain_in_rows[0]
                                if chain_live and chain_in_rows[0] is not None
                                else batch.num_rows)
                    in_rows = max(int(src_rows), 1)
                    skip_merge = int(partials[0].num_rows) > skip_ratio * in_rows
            if not partials:
                if nkeys == 0:
                    partials = [self._empty_state_batch()]
                else:
                    if self.mode == "complete":
                        return
                    return
        else:  # final: inputs are state batches
            skip_merge = False
            partials = list(child_batches)
            if not partials:
                if nkeys == 0:
                    partials = [self._empty_state_batch()]
                else:
                    return
        if partials:
            # rollup export (EXPLAIN ANALYZE / history / live registry):
            # lazy row counts — no sync unless something reads them
            out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
            out_batches = self.metrics.metric(M.NUM_OUTPUT_BATCHES)
            if skip_merge and len(partials) > 1:
                for p in partials:
                    p = K.compact_batch(p)
                    out_rows.add(p.num_rows)
                    out_batches.add(1)
                    yield p
                return
            self._acquire(ctx)
            t0 = time.perf_counter_ns()
            with self.span(agg_t):
                merged = self._merge(partials)
                # no compact at yield: exchanges, downstream aggs, and the
                # collect boundary consume masked batches natively
                # (zero-copy mask slices; session compacts on device right
                # before download), and every compact costs a count
                # sync (a host round trip)
                if self.mode != "partial":
                    merged = self._evaluate(merged)
            self._mark_device(merged, t0)
            if self.plan.distinct_level and self.mode != "partial":
                # the (keys, value) groups, compacted: the merge level
                # groups over them alone, and the count is the host's
                merged = K.compact_batch(merged)
                self.metrics.metric(M.DISTINCT_KEYS).add(merged.num_rows)
            out_rows.add(merged.num_rows)
            out_batches.add(1)
            yield merged

    # -- phase helpers -----------------------------------------------------

    def _distinct_inputs(self, batch: ColumnarBatch, kern, ansi: bool
                         ) -> ColumnarBatch:
        """The distinct level's input: the filter and the keys and inputs
        as one program, its live rows compacted (one count sync sizes
        them) so that the group-by sorts those rows and not the batch's
        capacity."""
        t0 = time.perf_counter_ns()
        masked, errs = kern.project_inputs(batch, ansi)
        compiled.raise_errors(errs)
        if isinstance(masked.num_rows, LazyRowCount):
            device_mark(self.metrics.metric(M.DISTINCT_AGG_DEVICE_TIME),
                        masked.num_rows.traced(), t0)
        small = K.compact_batch(masked)
        self.metrics.metric(M.DISTINCT_INPUT_ROWS).add(small.num_rows)
        return small

    def _mark_device(self, out: ColumnarBatch, since_ns: int) -> None:
        """aggDeviceTime (distinctAggDeviceTime on a distinct level): the
        device's time for the programs enqueued since `since_ns` whose
        last one computes `out`'s last state (or result) plane, read at
        the next read-back that exists; a plane that a program only hands
        through is ready at once and adds nothing."""
        data = out.columns[-1].data if out.columns else None
        if isinstance(data, jax.Array):
            device_mark(self.metrics.metric(
                M.DISTINCT_AGG_DEVICE_TIME if self.plan.distinct_level
                else M.AGG_DEVICE_TIME), data, since_ns)

    def _merge(self, partials: List[ColumnarBatch]) -> ColumnarBatch:
        if len(partials) == 1 and not getattr(partials[0], "coalesced",
                                              False):
            # A single partial already has unique keys — merging is
            # identity. NOT true of an exchange-coalesced batch: that is
            # a concat of several partials (duplicate keys across the
            # seams: tiny post-shuffle slices, or the whole of a bypassed
            # exchange's input, ShuffleExchangeExec._bypass), exactly
            # what the merge kernel below exists to fold.
            return partials[0]
        # partials a sharded update read back to the host (a global
        # aggregate's, through the collect) are laid together there
        batch = K.concat_host_batches(partials) \
            or K.concat_batches(partials)
        nkeys = len(self.plan.group_exprs)
        if nkeys == 0 and batch.num_rows <= 1:
            return batch
        out = self.kern.merge(batch)
        if nkeys == 0:
            out = ColumnarBatch(out.columns, 1)
        return out

    def _evaluate(self, state: ColumnarBatch) -> ColumnarBatch:
        nkeys = len(self.plan.group_exprs)
        fn = fuse.fused(self._sig("evaluate"), lambda: self.kern._evaluate_states)
        out = fn(state)
        n = state.num_rows if nkeys else 1
        return ColumnarBatch(out.columns, n, out.row_mask)

    def _empty_state_batch(self) -> ColumnarBatch:
        fields = self.state_fields()
        cols = []
        # zero-row update produces: count states = 0 (valid), collect
        # results = [] (valid), others null
        for f in fields:
            cap = round_capacity(1)
            if isinstance(f.dtype, T.ArrayType):
                if isinstance(f.dtype.element, T.StringType):
                    child = ColumnVector(
                        f.dtype.element,
                        {"offsets": jnp.zeros(9, jnp.int32),
                         "bytes": jnp.zeros(8, jnp.uint8)},
                        jnp.zeros(8, jnp.bool_))
                else:
                    child = ColumnVector(f.dtype.element,
                                         jnp.zeros(8, f.dtype.element.np_dtype),
                                         jnp.zeros(8, jnp.bool_))
                cols.append(ColumnVector(
                    f.dtype, {"offsets": jnp.zeros(cap + 1, jnp.int32),
                              "child": child},
                    jnp.arange(cap) < 1))
                continue
            if isinstance(f.dtype, T.StringType):
                cols.append(ColumnVector(
                    f.dtype, {"offsets": jnp.zeros(cap + 1, jnp.int32),
                              "bytes": jnp.zeros(8, jnp.uint8)},
                    jnp.zeros(cap, jnp.bool_)))
                continue
            is_count = f.name.endswith("__count")
            data = jnp.zeros(cap, f.dtype.np_dtype)
            valid = (jnp.arange(cap) < 1) if is_count else jnp.zeros(cap, jnp.bool_)
            cols.append(ColumnVector(f.dtype, data, valid))
        return ColumnarBatch(cols, 1)


def _resize_col(c: ColumnVector, cap: int) -> ColumnVector:
    if c.capacity == cap:
        return c
    idx = jnp.arange(cap, dtype=jnp.int32)
    idx = jnp.where(idx < c.capacity, idx, -1)
    return K.gather_column(c, idx, c.capacity)


def _resize_plane(vals, valid, dtype, cap: int) -> ColumnVector:
    n = vals.shape[0]
    if n == cap:
        pass
    elif n > cap:
        vals, valid = vals[:cap], valid[:cap]
    else:
        vals = jnp.concatenate([vals, jnp.zeros(cap - n, vals.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros(cap - n, jnp.bool_)])
    if vals.dtype != np.dtype(dtype.np_dtype):
        vals = vals.astype(dtype.np_dtype)
    return ColumnVector(dtype, vals, valid)


# ---------------------------------------------------------------------------
# Exchanges (stage barriers)
# ---------------------------------------------------------------------------

def _partitioning_mode(conf) -> str:
    """spark.rapids.shuffle.partitioning: 'compact' (counting-sort, the
    default) or 'masked' (legacy mask-sliced sub-batches)."""
    v = str(conf.get(C.SHUFFLE_PARTITIONING)).strip().lower()
    if v not in ("compact", "masked"):
        raise ValueError(
            "spark.rapids.shuffle.partitioning must be 'compact' or "
            f"'masked', got {v!r}")
    return v


def _tiny_rows(exchange) -> int:
    """spark.rapids.shuffle.coalesceTinyRows, or the measured cost pass's
    override of it; a coalesced batch holds at most 4x this."""
    override = getattr(exchange, "_tiny_override", None)
    return int(override) if override is not None \
        else int(exchange.conf.get(C.SHUFFLE_COALESCE_TINY_ROWS))


class ExchangeExec(TpuExec):
    """Base: materialize child partitions as concurrent tasks, re-partition,
    serve. Plays the role of Spark shuffle for the reference
    (RapidsShuffleInternalManagerBase MULTITHREADED mode runs parallel
    serialization through thread pools; here batches stay on device --
    the CACHE_ONLY/UCX 'stay on device' design, SURVEY §2.7).

    Two device partitioning strategies share the emit helpers below
    (spark.rapids.shuffle.partitioning): 'compact' counting-sorts each
    input batch by target partition in ONE fused dispatch and fetches the
    offsets vector ONCE, yielding contiguous right-sized sub-batches;
    'masked' emits n_out full-capacity selection-mask slices whose row
    counts each sync lazily. The partitionDispatches / partitionHostFetches
    metrics record exactly that asymmetry — partitioning-KERNEL launches
    and sizing round trips, not the compact path's per-slice assembly
    gathers (those are O(output rows)) — so tests can assert the O(1)
    contract instead of eyeballing profiles."""

    def __init__(self, plan, children, conf):
        super().__init__(plan, children, conf)
        self._lock = threading.Lock()
        self._out: Optional[List[List[ColumnarBatch]]] = None
        #: streaming tap: when set, every emitted (partition, sub_batch)
        #: is ALSO handed to this callable as it is produced — the
        #: serialized writer hooks it so serde/spill of batch i overlaps
        #: the device partitioning of batch i+1
        self._emit_sink = None
        #: measured cost pass override of coalesceTinyRows, snapshotted
        #: at convert time (the thread-local hints are gone by execute):
        #: history said this plan is dispatch-bound, so coalesce harder
        from spark_rapids_tpu.plan import cost as COST
        h = COST.current_hints()
        self._tiny_override: Optional[int] = (
            h.coalesce_tiny_rows if h is not None else None)

    @property
    def schema(self):
        return self.children[0].schema

    #: a streaming-capable _repartition consumes each child partition in
    #: ONE forward pass, so _materialize may hand it live iterators
    #: instead of materialized lists (ShuffleExchangeExec narrows this
    #: for the ICI mode, whose eligibility probe iterates twice)
    _streaming_ok = True

    def _materialize(self) -> List[List[ColumnarBatch]]:
        with self._lock:
            if self._out is None:
                child = self.children[0]
                streams = self._streamed_children(child)
                if streams is not None:
                    try:
                        self._out = self._repartition(streams)
                    finally:
                        for s in streams:
                            s.close()
                        self._finish_stream_tasks()
                    return self._out
                results: List[List[ColumnarBatch]] = [None] * child.num_partitions

                def run(p):
                    with TaskContext(partition_id=p) as tctx:
                        return list(child.execute_partition(tctx, p))

                if child.num_partitions == 1:
                    results[0] = run(0)
                else:
                    # child partitions run as tasks on the process-wide
                    # host pool (one bounded pool instead of a throwaway
                    # executor per exchange; nested exchanges degrade to
                    # inline execution rather than deadlocking); the
                    # writer-threads conf still caps THIS exchange's
                    # concurrent materializations (HBM admission)
                    from spark_rapids_tpu.runtime.host_pool import (
                        get_host_pool,
                    )
                    pool = get_host_pool(self.conf)
                    nthreads = self.conf.get(C.SHUFFLE_WRITER_THREADS)
                    for p, res in enumerate(
                            pool.map_ordered(run,
                                             range(child.num_partitions),
                                             max_concurrency=nthreads)):
                        results[p] = res
                self._out = self._repartition(results)
        return self._out

    def _streamed_children(self, child):
        """The compute->exchange-write pipeline boundary: each child
        partition becomes a bounded PipelinedIterator whose producer runs
        the partition's generator (decode, compute, upload) on the host
        pool WHILE this thread's repartition loop consumes earlier
        batches — the partitioning kernel, its offsets fetch, and the
        serialized writer's throttled serde all overlap upstream compute
        instead of waiting for full materialization. Child partitions
        still produce concurrently (every producer is armed up front,
        each with `depth` lookahead — a tighter memory bound than the
        historical materialize-everything). Returns None when streaming
        must not engage: pipelining off, a two-pass _repartition (ICI),
        a nested (pool-worker) caller, or more child partitions than
        device-semaphore permits. The permit gate is a deadlock fence: a
        producer past the permit count would park its pool worker in the
        semaphore wait queue, and enough parked producers would starve
        the pool of the workers the permit HOLDERS need to finish and
        release — the materialize-worker path (below) gives each
        partition a dedicated worker for its whole life, so it has no
        such cycle and keeps the wide-partition case."""
        from spark_rapids_tpu.runtime.host_pool import (
            HostTaskPool, get_host_pool,
        )
        from spark_rapids_tpu.runtime.pipeline import (
            PipelinedIterator, pipeline_conf,
        )
        depth = pipeline_conf(self.conf)
        nparts = self.children[0].num_partitions
        if depth <= 0 or not self._streaming_ok \
                or HostTaskPool._depth() != 0 \
                or nparts > self.conf.get(C.CONCURRENT_TPU_TASKS) \
                or nparts >= get_host_pool(self.conf).n_threads:
            return None

        def gen(p, tctx, fin):
            # the producer thread owns the task end-of-life exactly like
            # the materialize worker did (semaphore release, accumulator
            # rollup); `fin` makes completion exactly-once across this
            # finally and the close-path sweep in _finish_stream_tasks
            status = "failed"
            try:
                for b in self.children[0].execute_partition(tctx, p):
                    yield b
                status = "ok"
            except GeneratorExit:
                # early close (sibling partition failed, consumer bailed)
                # cancels this task — it did not itself fail
                status = "cancelled"
                raise
            except LC.QueryCancelledError:
                # the query's cancel token fired at a checkpoint inside
                # this producer: same rollup as the close path, and the
                # error still travels to the consumer
                status = "cancelled"
                raise
            finally:
                if not fin[0]:
                    fin[0] = True
                    tctx.complete(failed=(status == "failed"),
                                  cancelled=(status == "cancelled"))

        streams = []
        finals = []
        try:
            for p in range(self.children[0].num_partitions):
                tctx = TaskContext(partition_id=p)
                fin = [False]
                finals.append((tctx, fin))
                streams.append(PipelinedIterator(
                    gen(p, tctx, fin), depth, ctx=tctx, conf=self.conf,
                    label=f"{self.name()}@p{p}",
                    stall_metric=self.metrics.metric(M.PIPELINE_STALL_TIME),
                    producer_metric=self.metrics.metric(
                        M.PIPELINE_PRODUCER_TIME),
                    stalls_metric=self.metrics.metric(M.PIPELINE_STALLS)))
        except Exception:  # noqa: BLE001 - setup fallback: synchronous
            for s in streams:
                s.close()
            self._stream_finals = finals
            self._finish_stream_tasks()
            return None
        self._stream_finals = finals
        self.metrics.metric(M.PIPELINE_DEPTH).set(depth)
        return streams

    def _finish_stream_tasks(self) -> None:
        """Complete any streamed-child task whose generator never ran
        (close() on a not-yet-started generator skips its finally): the
        task did no work and did not fail, but its (empty) rollup and
        completion callbacks must still fire exactly once."""
        for tctx, fin in getattr(self, "_stream_finals", ()):
            if not fin[0]:
                fin[0] = True
                tctx.complete(failed=False)
        self._stream_finals = []

    def _repartition(self, child_results) -> List[List[ColumnarBatch]]:
        raise NotImplementedError

    def _partition_metrics(self):
        return (self.metrics.metric(M.PARTITION_DISPATCHES),
                self.metrics.metric(M.PARTITION_HOST_FETCHES),
                self.metrics.metric(M.NUM_OUTPUT_ROWS))

    def _repartition_passthrough(self, child_results):
        """n_out == 1: every row lands in the single output partition —
        emit the batches unchanged. No partition kernel, no data
        movement, no sizing fetch (either strategy would only have
        reshuffled rows onto themselves)."""
        rows_m = self.metrics.metric(M.NUM_OUTPUT_ROWS)
        flat = []
        for part in child_results:
            for b in part:
                rows_m.add(b.num_rows)
                flat.append(b)
                if self._emit_sink is not None:
                    self._emit_sink(0, b)
        return [flat]

    def _emit_compact(self, batch, fused_out, out) -> None:
        """Compact-path emission: `fused_out` is (sorted_batch, offsets)
        from ONE counting-sort dispatch; the single offsets fetch here is
        the entire host synchronization for partitioning this batch.
        Column bounds re-attach host-side (they are not pytree leaves and
        stay valid under any row subset); empty partitions emit nothing."""
        disp, fetch, rows_m = self._partition_metrics()
        sorted_b, off_dev = fused_out
        disp.add(1)
        LC.check_current()  # per-batch exchange checkpoint: the offsets
        FLT.site("exchange.fetch")  # sync is where a shuffle blocks
        with device_wait("exchange"):
            offsets = np.asarray(jax.device_get(off_dev))
            fetch.add(1)
        for p, sub in enumerate(
                RP.compact_slices(sorted_b, offsets, self.n_out)):
            if sub is None:
                continue
            carry_host_stats(batch.columns, sub.columns)
            rows_m.add(int(sub.num_rows))
            out[p].append(sub)
            if self._emit_sink is not None:
                self._emit_sink(p, sub)

    def _emit_masked(self, batch, subs, out) -> None:
        """Masked-path emission with the bookkeeping the compact path gets
        for free: each input batch costs n_out full-capacity sub-batch
        computations and n_out deferred count syncs (the LazyRowCounts
        materialize one by one downstream)."""
        disp, fetch, rows_m = self._partition_metrics()
        disp.add(self.n_out)
        fetch.add(self.n_out)
        for p, sub in enumerate(subs):
            carry_host_stats(batch.columns, sub.columns)
            rows_m.add(sub.num_rows)
            out[p].append(sub)
            if self._emit_sink is not None:
                self._emit_sink(p, sub)

    def _compact_stream(self, batches, dispatch, out, part_t) -> None:
        """Drive a compact partitioning loop with a one-deep deferred
        offsets fetch (pipeline-gated): dispatch batch i+1's counting
        sort and START its offsets D2H before consuming batch i's
        offsets, so the transfer rides under device compute instead of
        serializing against it. Emission order (and therefore every
        downstream result) is unchanged; with pipelining disabled this
        is exactly the historical dispatch-then-fetch loop."""
        from spark_rapids_tpu.runtime.pipeline import pipeline_conf, start_d2h
        if pipeline_conf(self.conf) <= 0:
            for batch in batches:
                with self.span(part_t):
                    self._emit_compact(batch, dispatch(batch), out)
            return
        pending = None
        for batch in batches:
            with self.span(part_t):
                fo = dispatch(batch)
            start_d2h(fo[1])
            if pending is not None:
                with self.span(part_t):
                    self._emit_compact(pending[0], pending[1], out)
            pending = (batch, fo)
        if pending is not None:
            with self.span(part_t):
                self._emit_compact(pending[0], pending[1], out)

    def execute_partition(self, ctx, pidx):
        out = self._materialize()
        # coalesce first, then split: the two repair opposite tails (dust
        # -> fewer dispatches, giants -> bounded dispatches) and a split
        # slice must never be re-merged back into the giant it came from
        yield from self._split_skewed(self._coalesce_tiny(out[pidx]), pidx)

    def _item_rows(self, item, pidx) -> Optional[int]:
        """Free (host-int) row count of one materialized item, or None
        when counting would sync — the skew detector's unit of account."""
        if isinstance(item, ColumnarBatch) and item.row_mask is None \
                and isinstance(item.num_rows, int):
            return item.num_rows
        return None

    def _skew_plan(self):
        """(threshold_rows, target_rows, totals) once per exchange, or
        None when no partition qualifies for splitting. Computed from
        the already-materialized output's host-int counts only — the
        decision never syncs (partitions with any lazy count are
        excluded and never split)."""
        with self._lock:
            sp = getattr(self, "_skew_decision", None)
            if sp is None:
                from spark_rapids_tpu.exec import adaptive as AQ
                totals: List[Optional[int]] = []
                for p, part in enumerate(self._out or []):
                    n: Optional[int] = 0
                    for item in part:
                        r = self._item_rows(item, p)
                        if r is None:
                            n = None
                            break
                        n += r
                    totals.append(n)
                t = AQ.skew_threshold(self.conf, totals)
                sp = self._skew_decision = (
                    False if t is None else (t[0], t[1], totals))
        return sp or None

    def _split_skewed(self, batches, pidx):
        """Skewed-partition split (spark.rapids.sql.adaptive.skewFactor;
        reference GpuSkewJoin / skewedPartitionFactor): a partition whose
        row total exceeds factor x median splits its oversized batches
        into ~median-row contiguous slices (bounded fan-out), so one hot
        key range stops serializing the whole downstream stage behind a
        single giant dispatch. In-order slices — every downstream result
        is byte-identical, sub-batches just rejoin under the existing
        batch semantics."""
        if getattr(self, "n_out", 1) <= 1:
            return batches
        from spark_rapids_tpu.exec import adaptive as AQ
        if not AQ.enabled(self.conf) \
                or float(self.conf.get(C.ADAPTIVE_SKEW_FACTOR)) <= 0:
            return batches
        sp = self._skew_plan()
        if sp is None:
            return batches
        threshold, target, totals = sp
        total = totals[pidx] if pidx < len(totals) else None
        if total is None or total <= threshold:
            return batches
        return self._split_stream(batches, pidx, total, threshold, target)

    def _split_stream(self, batches, pidx, total, threshold, target):
        from spark_rapids_tpu.exec import adaptive as AQ
        nsplits = 0
        for b in batches:
            n = self._item_rows(b, pidx)
            if n is None or n <= 2 * target:
                yield b
                continue
            # bounded fan-out: at most 8 sub-dispatches per batch, each
            # a contiguous in-order slice sharing the compact exchange's
            # capacity buckets (ops/repartition.py slice_rows)
            step = max(target, -(-n // 8))
            start = 0
            while start < n:
                ln = min(step, n - start)
                sub = RP.slice_rows(b, start, ln)
                carry_host_stats(b.columns, sub.columns)
                sub.coalesced = getattr(b, "coalesced", False)
                nsplits += 1
                yield sub
                start += ln
        if nsplits:
            AQ.record(AQ.SKEW_SPLIT, partition=pidx, rows=int(total),
                      median=int(target), threshold_rows=int(threshold),
                      splits=nsplits)

    def _coalesce_tiny(self, batches):
        """Post-shuffle tiny-partition coalescing (spark.rapids.shuffle.
        coalesceTinyRows): ragged post-shuffle slice sizes make nearly
        every sub-batch shape a fresh downstream trace AND a separate
        dispatch — the q72shfl shape zoo. Adjacent device sub-batches
        under the tiny threshold merge (bounded at 4x the threshold)
        before downstream dispatch. The decision is free: compact slices
        carry plain host-int row counts from the already-fetched offsets
        vector, so nothing here ever syncs a lazy count (batches whose
        count is still on device pass through untouched, as do masked
        batches and lazily-deserialized shuffle blobs). Merges count
        into shuffleCoalescedBatches — visible in EXPLAIN ANALYZE."""
        tiny = _tiny_rows(self)
        if tiny <= 0 or getattr(self, "n_out", 1) <= 1:
            yield from batches
            return
        budget = tiny * 4
        run: List[ColumnarBatch] = []
        run_rows = 0
        for b in batches:
            small = (isinstance(b, ColumnarBatch)
                     and b.row_mask is None
                     and isinstance(b.num_rows, int)
                     and 0 < b.num_rows < tiny)
            if small and run_rows + b.num_rows <= budget:
                run.append(b)
                run_rows += b.num_rows
                continue
            yield from self._flush_coalesce_run(run)
            if small:
                run, run_rows = [b], b.num_rows
            else:
                run, run_rows = [], 0
                yield b
        yield from self._flush_coalesce_run(run)

    def _flush_coalesce_run(self, run):
        if not run:
            return
        if len(run) == 1:
            yield run[0]
            return
        merged = K.concat_batches(run)
        # a coalesced batch is a CONCAT of exchange sub-batches: any
        # per-batch invariant the sources carried individually (a final
        # agg's "one partial has unique keys") no longer holds — the
        # flag tells _merge to run its merge kernel even for a single
        # input batch
        merged.coalesced = True
        self.metrics.metric(M.SHUFFLE_COALESCED_BATCHES).add(len(run))
        TR.instant("shuffleCoalesce", cat="exchange",
                   args={"merged": len(run),
                         "rows": int(merged.num_rows)}, level=TR.DEBUG)
        yield merged


class CollectExchangeExec(ExchangeExec):
    """N -> 1 concat exchange (single partitioning analog)."""

    @property
    def num_partitions(self):
        return 1

    def _repartition(self, child_results):
        flat = [b for part in child_results for b in part]
        return [flat]


class ShuffleExchangeExec(ExchangeExec):
    """Hash-partitioned exchange. Two modes (spark.rapids.shuffle.mode):

    MULTITHREADED (default, any device count): murmur3(keys) pmod n on
    device, then zero-copy mask slicing into per-target sub-batches
    (reference GpuShuffleExchangeExecBase + GpuHashPartitioningBase).

    ICI (requires >= n_out jax devices): one partition shard per device;
    the ENTIRE exchange is a single shard_map-ped XLA program whose
    lax.all_to_all moves rows over the interconnect — the engine-level
    realization of the reference's UCX transport replacement (SURVEY.md
    §2.7 "TPU-native equivalent"). Falls back to MULTITHREADED when the
    device count or column layout doesn't fit (flat strings / differing
    vocabs can't ride a fixed-width collective).

    Neither runs where there is nothing to exchange (`_bypass`): an
    exchange the planner built between the halves of one aggregate
    (`may_bypass`), whose whole input is already on the host with
    host-int row counts and within the tiny-coalescing budget (a sharded
    partial aggregate's read-back: a few groups a shard), lays those
    rows together with numpy as ONE batch of partition 0 and leaves the
    other partitions empty. Every row of a key is then in one partition,
    which is all the final aggregate asks. An exchange under a join never
    does this (both sides must be co-partitioned)."""

    def __init__(self, plan, children, conf, keys: List[Expression], n_out: int,
                 may_bypass: bool = False):
        super().__init__(plan, children, conf)
        self.keys = keys
        self.n_out = n_out
        #: set by the planner where the sole consumer is the final
        #: aggregate of the same plan node (plan/overrides.py)
        self.may_bypass = may_bypass
        #: rows the last run passed without exchanging them, or None
        self._bypassed_rows: Optional[int] = None

    def tree_string(self, indent: int = 0) -> str:
        head, nl, rest = super().tree_string(indent).partition("\n")
        if self._bypassed_rows is not None:
            head += f" [bypassed: {self._bypassed_rows} rows on the host]"
        return f"{head}{nl}{rest}"

    @property
    def num_partitions(self):
        return self.n_out

    @property
    def _ici_first(self):
        # the in-program all_to_all is the shuffle whenever the session
        # runs sharded (multichip) or asks for it outright (SHUFFLE_MODE)
        from spark_rapids_tpu.parallel.mesh import multichip_on
        return (self.conf.get(C.SHUFFLE_MODE).upper() == "ICI"
                or multichip_on(self.conf))

    @property
    def _streaming_ok(self):
        # the ICI eligibility probe and vocab alignment iterate the child
        # results twice — a live stream cannot be replayed
        return not self._ici_first

    def _item_rows(self, item, pidx):
        if isinstance(item, _LazyShuffleBlobs):
            # serialized partitions are sized by the writer-side tally —
            # decoding blobs just to count them would defeat the free-
            # decision contract
            store = getattr(self, "_store", None)
            n = store.partition_rows(pidx) if store is not None else 0
            return n if n > 0 else None
        return super()._item_rows(item, pidx)

    def _repartition(self, child_results):
        out = self._bypass(child_results)
        if out is not None:
            return out
        mode = self.conf.get(C.SHUFFLE_MODE).upper()
        if self._ici_first:
            with self.span(self.metrics.metric(M.PARTITION_TIME)):
                out = self._repartition_ici(child_results)
            if out is not None:
                return out
        if mode == "SERIALIZED":
            return self._repartition_serialized(child_results)
        return self._repartition_device(child_results)

    def _bypass(self, child_results):
        """The input as one batch of partition 0, the other partitions
        empty, where the planner allows it and nothing needs exchanging:
        every batch on the host with a host-int row count (no
        LazyRowCount: the decision adds no sync) and the rows in all
        within what a coalesced batch may hold (4x coalesceTinyRows; 0
        turns this off with the coalescing). None otherwise, and for a
        live stream of batches, which cannot be looked at twice: the
        caller then exchanges as it always did. The batch is marked
        `coalesced` (it is a concat of partials: the final aggregate must
        run its merge kernel on it) and skips the skew split, which would
        cut sixteen rows against three empty partitions."""
        tiny = _tiny_rows(self)
        if not self.may_bypass or tiny <= 0 \
                or not all(isinstance(p, list) for p in child_results):
            return None
        batches = [b for part in child_results for b in part]
        merged = K.concat_host_batches(batches, 4 * tiny)
        if merged is None:
            return None
        self._bypassed_rows = merged.num_rows
        self.metrics.metric(M.EXCHANGE_BYPASSED).add(1)
        self.metrics.metric(M.NUM_OUTPUT_ROWS).add(merged.num_rows)
        merged.coalesced = True
        out: List[List[ColumnarBatch]] = [[] for _ in range(self.n_out)]
        if merged.num_rows:
            out[0].append(merged)
        return out

    def _repartition_device(self, child_results):
        """In-memory device partitioning (the MULTITHREADED mode body and
        the SERIALIZED mode's device half)."""
        if self.n_out == 1:
            return self._repartition_passthrough(child_results)
        if _partitioning_mode(self.conf) == "masked":
            return self._repartition_masked(child_results)
        return self._repartition_compact(child_results)

    def _repartition_compact(self, child_results):
        """Counting-sort exchange: one fused XLA computation per input
        batch hashes the keys, pmods to partition ids, stable-sorts rows
        by pid and emits the permuted planes plus the n_out+1 offsets
        vector (ops/repartition.py). ONE host fetch of the offsets then
        yields contiguous sub-batches sized by actual row counts — the
        cudf hashPartitionAndClose contract, not an n_out-mask fanout."""
        part_t = self.metrics.metric(M.PARTITION_TIME)
        keys, n_out = self.keys, self.n_out

        def build():
            def fn(batch):
                live = batch.live_mask()
                ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                               batch.capacity, False, live=live)
                key_cols = [e.eval_tpu(ectx) for e in keys]
                h = K.partition_hash_batch(key_cols, batch.num_rows,
                                           live=live)
                pid = _pmod(h, n_out)
                return RP.counting_sort_by_pid(batch, pid, n_out)
            return fn

        fn = fuse.fused(("hash_exchange_compact",
                         tuple(e.fingerprint() for e in keys), n_out), build)
        out: List[List[ColumnarBatch]] = [[] for _ in range(n_out)]
        self._compact_stream((b for part in child_results for b in part),
                             fn, out, part_t)
        return out

    def _repartition_serialized(self, child_results):
        """Masked device partition, then parallel serialization through the
        kudo-analog wire format into a spillable host store (reference
        RapidsShuffleThreadedWriterBase:291-513 + ShuffleBufferCatalog).
        Device planes are released once serialized; blobs page to disk
        under spark.rapids.shuffle.hostSpillBudget. The returned partition
        lists deserialize lazily at read time."""
        from spark_rapids_tpu.shuffle import serde
        from spark_rapids_tpu.shuffle.store import ShuffleStore
        from spark_rapids_tpu.runtime.pipeline import pipeline_conf
        ser_t = self.metrics.metric(M.PARTITION_TIME)
        codec = self.conf.get(C.SHUFFLE_COMPRESSION)
        serde.codec_id(codec)  # validate up front
        store = ShuffleStore(self.n_out,
                             self.conf.get(C.SHUFFLE_HOST_BUDGET))
        nthreads = max(1, self.conf.get(C.SHUFFLE_WRITER_THREADS))

        def ser(item):
            # the compact partitioning path hands over already-contiguous
            # right-sized slices; serialize_batch compacts the masked
            # path's sub-batches itself. The row count rides along into
            # the store's per-partition tally (skew detection reads it
            # without decoding blobs).
            p, b = item
            n = rows_int(b.num_rows)
            if n == 0:
                return p, None, 0  # empty sub-batches never ship
            return p, FLT.site_bytes("shuffle.write",
                                     serde.serialize_batch(b, codec)), n

        if pipeline_conf(self.conf) > 0 and nthreads > 1:
            self._serialize_streaming(child_results, store, ser, nthreads,
                                      ser_t)
        else:
            parted = self._repartition_device(child_results)
            work = [(p, b) for p, part in enumerate(parted) for b in part]
            with self.span(ser_t):
                if len(work) > 1 and nthreads > 1:
                    from spark_rapids_tpu.runtime.host_pool import (
                        get_host_pool,
                    )
                    for p, blob, n in get_host_pool(self.conf).map_ordered(
                            ser, work, max_concurrency=nthreads):
                        if blob is not None:
                            store.add(p, blob, rows=n)
                else:
                    for item in work:
                        p, blob, n = ser(item)
                        if blob is not None:
                            store.add(p, blob, rows=n)
        self._store = store
        tot = store.totals()
        self.metrics.metric(M.SHUFFLE_BYTES_WRITTEN).add(
            tot["bytes_written"])
        self.metrics.metric(M.SHUFFLE_BYTES_SPILLED).add(
            tot["bytes_spilled"])
        rthreads = self.conf.get(C.SHUFFLE_READER_THREADS)
        return [[_LazyShuffleBlobs(store, p, rthreads, self.conf)]
                if store.partition_bytes(p)
                else [] for p in range(self.n_out)]

    def _serialize_streaming(self, child_results, store, ser,
                             nthreads: int, ser_t) -> None:
        """Async throttled serialized write (reference ThrottlingExecutor
        / RapidsShuffleThreadedWriterBase): the emit sink submits each
        sub-batch for serde the moment the device partitioning produces
        it, so serde/spill of batch i overlaps the partitioning kernel of
        batch i+1. TrafficController caps the host bytes in flight;
        completed blobs drain into the store IN SUBMISSION ORDER (the
        deque head gates on done()), so per-partition blob order — and
        every downstream result — is identical to the synchronous path."""
        from collections import deque

        from spark_rapids_tpu.io.async_io import (
            ThrottlingExecutor, TrafficController,
        )
        from spark_rapids_tpu.runtime.host_pool import get_host_pool
        ctrl = TrafficController(
            self.conf.get(C.ASYNC_WRITE_MAX_INFLIGHT),
            stall_warn_s=self.conf.get(C.ASYNC_WRITE_STALL_WARN_S) or None)
        # serde runs on the SHARED host pool (PR-2 boundedness invariant:
        # no per-writer throwaway executors); the TrafficController's
        # byte budget is the per-exchange admission bound
        ex = ThrottlingExecutor(nthreads, ctrl,
                                pool=get_host_pool(self.conf))
        futures = deque()

        def drain(block: bool) -> None:
            while futures and (block or futures[0].done()):
                p, blob, n = futures.popleft().result()
                if blob is not None:
                    store.add(p, blob, rows=n)

        def sink(p, b):
            futures.append(ex.submit(b.device_memory_size(), ser, (p, b)))
            drain(False)

        self._emit_sink = sink
        ok = False
        try:
            self._repartition_device(child_results)
            ok = True
        finally:
            self._emit_sink = None
            if ok:
                with self.span(ser_t):
                    drain(True)
                ex.shutdown()
            else:
                # partitioning raised: settle the in-flight serde work
                # without letting ITS errors mask the propagating one
                try:
                    drain(True)
                except Exception:  # noqa: BLE001
                    pass
                ex.shutdown(wait=False)

    def execute_partition(self, ctx, pidx):
        out = self._materialize()
        if self._bypassed_rows is not None:
            yield from out[pidx]
            return

        def decoded():
            for item in out[pidx]:
                if isinstance(item, _LazyShuffleBlobs):
                    yield from item.batches()
                else:
                    yield item

        # deserialized blobs coalesce exactly like device sub-batches:
        # the serialized path chops partitions even finer. Skew split
        # applies after (the store's writer-side row tally sizes lazy
        # partitions without decoding them).
        yield from self._split_skewed(self._coalesce_tiny(decoded()), pidx)

    def _ici_eligible(self, child_results):
        import jax as _jax
        # the shard math assumes exactly one cap-sized shard per device:
        # source partition count must equal the output count
        if len(child_results) != self.n_out or self.n_out < 2:
            return False
        if len(_jax.devices()) < self.n_out:
            return False
        for part in child_results:
            for b in part:
                for c in b.columns:
                    if c.is_string and not c.is_dict:
                        return False  # variable-length payloads
        # differing dict vocabs are ALIGNED by _align_vocabs, not rejected
        return True

    @staticmethod
    def _align_vocabs(batches):
        """Remap dict-string codes across shards onto ONE union vocab so
        string keys ride the fixed-width collective (VERDICT r3 #5: 'the
        TPU-native shuffle does not work for string keys'). Builds NEW
        batches — the inputs may alias cached/session batches whose
        identity-keyed caches assume immutability."""
        live = [b for b in batches if b is not None]
        if not live:
            return batches
        ncols = len(live[0].columns)
        new_cols = {i: list(b.columns) for i, b in enumerate(batches)
                    if b is not None}
        changed = False
        for ci in range(ncols):
            cols = [b.columns[ci] for b in live]
            if not cols[0].is_dict:
                continue
            aligned = K.align_dict_columns(cols)
            if aligned[0] is cols[0]:
                continue
            changed = True
            li = 0
            for i, b in enumerate(batches):
                if b is None:
                    continue
                new_cols[i][ci] = aligned[li]
                li += 1
        if not changed:
            return batches
        return [None if b is None
                else ColumnarBatch(new_cols[i], b.num_rows, b.row_mask)
                for i, b in enumerate(batches)]

    def _repartition_ici(self, child_results):
        """One shard per device, rows moved by lax.all_to_all inside a
        single shard_map program (parallel/exchange.py). Reached only
        where `_bypass` declined: the join exchanges, partial states
        whose counts are lazy or whose planes are on the device, and
        states of more rows than a coalesced batch holds (a group-by of
        many keys). None where the layout does not fit the collective;
        the caller then partitions on the device."""
        if not self._ici_eligible(child_results):
            return None
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from spark_rapids_tpu.parallel import exchange as X
        from spark_rapids_tpu.parallel.mesh import make_mesh
        from jax import shard_map
        import jax as _jax

        n = self.n_out
        # one compacted batch per source partition, padded to one capacity
        batches = []
        for part in child_results:
            b = K.compact_batch(K.concat_batches(part)) if part else None
            batches.append(b)
        live_parts = [b for b in batches if b is not None]
        if not live_parts:
            return [[] for _ in range(n)]
        batches = self._align_vocabs(batches)
        live_parts = [b for b in batches if b is not None]
        schema_cols = live_parts[0].columns
        cap = max(round_capacity(max(int(b.num_rows), 1)) for b in live_parts)
        mesh = make_mesh(n, axis_names=("part",))

        # build global [n*cap] planes sharded over the mesh. Assembled
        # in NUMPY: each jnp pad/concat here is an eager XLA program
        # (~200 of them per exchange), while numpy pad+concat is a
        # memcpy — the planes hit the device exactly once, at the
        # sharded device_put below.
        def pad_plane(arr, fill, dtype):
            dt = np.dtype(dtype)
            out = np.full(cap, fill, dt)
            a = np.asarray(arr)[:cap]
            out[: a.shape[0]] = a.astype(dt, copy=False)
            return out

        # every plane the packing reads, of every source, in ONE read:
        # (data or codes, validity) a column, then the live mask
        host = host_planes([None if b is None else (
            [(c.data["codes"] if c.is_dict else c.data, c.validity)
             for c in b.columns], b.live_mask()) for b in batches],
            "exchange")
        planes = {}
        per_col_meta = []
        for ci, c in enumerate(schema_cols):
            key = f"c{ci}"
            if c.is_dict:
                per_col_meta.append(("dict", c.dtype, c.data["dict_offsets"],
                                     c.data["dict_bytes"], c.dict_unique))
                dt = np.dtype(np.int32)
            else:
                dt = np.dtype(c.data.dtype)
                per_col_meta.append(("fixed", c.dtype, None, None, True))
            planes[key] = np.concatenate([
                pad_plane(h[0][ci][0], 0, dt) if h is not None
                else np.zeros(cap, dt) for h in host])
            vshards = []
            for b, h in zip(batches, host):
                if b is None:
                    vshards.append(np.zeros(cap, np.bool_))
                else:
                    v = h[0][ci][1]
                    if v is None:
                        v = np.arange(b.columns[ci].capacity) < \
                            int(traced_rows(b.num_rows))
                    vshards.append(pad_plane(v, False, np.bool_))
            planes[key + "_v"] = np.concatenate(vshards)
        live = np.concatenate([
            pad_plane(h[1], False, np.bool_) if h is not None
            else np.zeros(cap, np.bool_) for h in host])

        # target partition ids from the key hash, computed globally, plus
        # per-(source, destination) counts for the right-sizing pass.
        # FAST PATH (all fixed-width columns): ONE jitted program
        # evaluates the keys, hashes, and counts the per-(src,dst) lanes
        # over the packed planes — the per-source loop costs three eager
        # kernel launches per source. Grouping keys are row-local
        # expressions, so evaluating them on the concatenated planes is
        # exact; dict-encoded keys hash decoded values, so they keep the
        # per-source path.
        n_cols = len(per_col_meta)
        if all(meta[0] == "fixed" for meta in per_col_meta):
            dts = [meta[1] for meta in per_col_meta]

            def _build_hash():
                def f(data_planes, valid_planes, live):
                    cols = [ColumnVector(dt, d, v) for dt, d, v
                            in zip(dts, data_planes, valid_planes)]
                    total = live.shape[0]
                    ectx = EvalCtx(cols, total, total, False, live=live)
                    key_cols = [e.eval_tpu(ectx) for e in self.keys]
                    h = K.partition_hash_batch(key_cols, total, live=live)
                    pid = jnp.where(live, _pmod(h, n), 0).astype(jnp.int32)
                    # per-(src,dst) counts via the counting-sort kernel's
                    # bucket pass (ops/repartition.py) — one code path
                    # sizes both the compact slices and the ICI send lanes
                    counts = jax.vmap(
                        lambda p_, l_: RP.partition_counts(p_, l_, n)
                    )(pid.reshape(n, cap), live.reshape(n, cap))
                    return pid, counts
                return f

            hfn = fuse.fused(
                ("ici_hash", n, cap,
                 tuple(e.fingerprint() for e in self.keys),
                 tuple(str(planes[f"c{ci}"].dtype)
                       for ci in range(n_cols))),
                _build_hash)
            pid_all, counts_dev = hfn(
                [planes[f"c{ci}"] for ci in range(n_cols)],
                [planes[f"c{ci}_v"] for ci in range(n_cols)], live)
            target, counts_host = host_planes((pid_all, counts_dev),
                                              "exchange")
            counts_host = np.asarray(counts_host)
        else:
            keys = self.keys

            def _build_src_hash():
                def f(b):
                    live = b.live_mask()
                    ectx = EvalCtx(b.columns, traced_rows(b.num_rows),
                                   b.capacity, False, live=live)
                    key_cols = [e.eval_tpu(ectx) for e in keys]
                    h = K.partition_hash_batch(key_cols, b.num_rows,
                                               live=live)
                    pid = _pmod(h, n)
                    return pid, RP.partition_counts(pid, live, n)
                return f

            # keyed like the fast path above: run eagerly, the hash of a
            # string key's vocabulary is a while loop jax compiles anew
            # on every call (8 compiles a Q1 over four shards)
            sfn = fuse.fused(
                ("ici_hash_src", n,
                 tuple(e.fingerprint() for e in keys)), _build_src_hash)
            # every source's ids and lane counts in ONE read
            pid_counts = host_planes([None if b is None else sfn(b)
                                      for b in batches], "exchange")
            target = np.concatenate([
                np.zeros(cap, np.int32) if pc is None
                else pad_plane(pc[0], 0, np.int32) for pc in pid_counts])
            counts_host = np.stack([
                np.zeros(n, np.int32) if pc is None else np.asarray(pc[1])
                for pc in pid_counts])
        # ONE host fetch sizes the send lanes: C = max rows any source
        # sends any destination, rounded to a capacity bucket — the ICI
        # collective then moves ~rows/P per lane instead of the whole
        # local capacity (VERDICT r3 weak #5: capacity-naive buffers)
        send_cap = min(cap, round_capacity(max(int(counts_host.max()), 1)))

        spec = PS("part")
        sh = NamedSharding(mesh, spec)
        planes = {k: _jax.device_put(v, sh) for k, v in planes.items()}
        live = _jax.device_put(live, sh)
        target = _jax.device_put(target, sh)

        def shard_fn(planes, live, target):
            return X.all_to_all_exchange(planes, live, target, ("part",),
                                         send_cap=send_cap)

        # the KEYED compile layer, not _cc.jit: shard_fn is a fresh
        # closure every repartition, so raw jax.jit would retrace the
        # whole collective each collect. The key pins the shapes that
        # matter (mesh width, capacity buckets, plane dtypes) and the
        # compile-cache fingerprint adds the mesh component under
        # multichip — repeated exchanges replay the warm executable.
        key = ("ici_exchange", n, cap, send_cap,
               tuple((k, str(planes[k].dtype)) for k in sorted(planes)))
        fn = fuse.fused(key, lambda: shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=({k: spec for k in planes}, spec)))
        # the collective dispatch itself, timed with NO host sync inside
        # the span (async dispatch; the interval is issue cost plus any
        # backend blocking). NESTED inside the partitionTime span the
        # caller opened — rollups/attribution exclude it (metrics.
        # NESTED_TIME_METRICS) and the 'ici_exchange' attribution view
        # reports it separately.
        with self.span(self.metrics.metric(M.ICI_EXCHANGE_TIME)):
            out_planes, out_live = fn(planes, live, target)

        # slice the global result back into per-partition, PER-SENDER
        # batches (consumers like the aggregate merge rely on "one batch =
        # rows from one upstream partial" for their unique-key reasoning).
        # ONE host assembly first: the n*n slices below are eager ops, and
        # on the sharded collective output each would run the GSPMD
        # partitioner (20-40x a single-device slice). device_get gathers
        # the local shards without an XLA program; the emitted batches
        # keep the host numpy views — consumers feed them into jitted
        # kernels (which accept numpy) or host packers, and re-uploading
        # each of the n*n*planes slices measured ~0.15ms apiece.
        out_planes, out_live = host_planes((out_planes, out_live), "exchange")
        out: List[List[ColumnarBatch]] = []
        shard_rows = n * send_cap  # each device receives n*send_cap slots
        for p in range(n):
            subs = []
            for src in range(n):
                base = p * shard_rows + src * send_cap
                sl = slice(base, base + send_cap)
                cols = []
                for ci, (kind, dtype, doff, dby, uniq) in enumerate(per_col_meta):
                    data = out_planes[f"c{ci}"][sl]
                    valid = out_planes[f"c{ci}_v"][sl]
                    if kind == "dict":
                        cols.append(ColumnVector(
                            dtype, {"codes": data, "dict_offsets": doff,
                                    "dict_bytes": dby}, valid,
                            dict_unique=uniq))
                    else:
                        cols.append(ColumnVector(dtype, data, valid))
                mask = out_live[sl]
                subs.append(ColumnarBatch(
                    cols, int(mask.sum()), mask))
            out.append(subs)
        return out

    def _repartition_masked(self, child_results):
        part_t = self.metrics.metric(M.PARTITION_TIME)
        keys, n_out = self.keys, self.n_out

        def build():
            def fn(batch):
                live = batch.live_mask()
                ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                               batch.capacity, False, live=live)
                key_cols = [e.eval_tpu(ectx) for e in keys]
                h = K.partition_hash_batch(key_cols, batch.num_rows, live=live)
                pid = _pmod(h, n_out)
                subs = []
                for p in range(n_out):
                    m = live & (pid == p)
                    subs.append(ColumnarBatch(
                        batch.columns, LazyRowCount(jnp.sum(m.astype(jnp.int32))), m))
                return subs
            return fn

        fn = fuse.fused(("hash_exchange",
                         tuple(e.fingerprint() for e in keys), n_out), build)
        out: List[List[ColumnarBatch]] = [[] for _ in range(self.n_out)]
        for part in child_results:
            for batch in part:
                with self.span(part_t):
                    # mask-sliced sub-batches: the planes are SHARED across
                    # all n_out outputs (zero-copy partitioning); only the
                    # selection masks differ.
                    self._emit_masked(batch, fn(batch), out)
        return out


def _pmod(h, n):
    r = h % n
    return jnp.where(r < 0, r + n, r)


class _LazyShuffleBlobs:
    """A reduce partition's serialized blobs; deserializes at read time.
    Host-side decode (decompression + frame parsing) runs on the shuffle
    reader pool (spark.rapids.shuffle.multiThreaded.reader.threads);
    device upload stays ordered.

    Integrity recovery: each blob's wire CRC (and frame xxhash64) is
    verified during deserialization (spark.rapids.shuffle.
    verifyChecksums); a ShuffleCorruptionError triggers ONE transparent
    re-fetch of the same blob from the store — disk-resident blobs
    re-read their spill-file segment, so a transient read corruption
    heals — counted in the shuffleCorruptionRetries task accumulator
    before a second failure surfaces (and, under
    spark.rapids.fallback.cpu.enabled, degrades the query to CPU)."""

    def __init__(self, store, partition: int, reader_threads: int = 1,
                 conf=None):
        self.store = store
        self.partition = partition
        self.reader_threads = max(1, reader_threads)
        self.conf = conf
        self.verify = True if conf is None \
            else bool(conf.get(C.SHUFFLE_VERIFY_CHECKSUMS))
        self._task_ctx = None

    def _read(self, index: int) -> bytes:
        return FLT.site_bytes(
            "shuffle.read", self.store.read_blob(self.partition, index))

    def _decode(self, index: int):
        from spark_rapids_tpu.shuffle import serde
        try:
            return serde.deserialize_batch(self._read(index),
                                           verify=self.verify)
        except serde.ShuffleCorruptionError as e:
            # decode may run on a host-pool worker with no TaskContext
            # bound: the retry accounts to the CONSUMING task captured
            # in batches()
            ctx = TaskContext.peek() or self._task_ctx
            if ctx is not None:
                ctx.metric("shuffleCorruptionRetries").add(1)
            TR.instant("shuffleCorruptionRetry", cat="shuffle", args={
                "partition": self.partition, "blob": index,
                "error": str(e)[:120]})
            import logging
            logging.getLogger("spark_rapids_tpu").warning(
                "shuffle blob %d of partition %d failed verification "
                "(%s); re-fetching from the store once", index,
                self.partition, e)
            return serde.deserialize_batch(self._read(index),
                                           verify=self.verify)

    def batches(self):
        self._task_ctx = TaskContext.peek()
        n = self.store.num_blobs(self.partition)
        if self.reader_threads > 1 and n > 1:
            from spark_rapids_tpu.runtime.host_pool import get_host_pool
            yield from get_host_pool(self.conf).map_ordered(
                self._decode, range(n),
                max_concurrency=self.reader_threads)
            return
        for i in range(n):
            yield self._decode(i)


class RoundRobinExchangeExec(ExchangeExec):
    """Round-robin repartition (reference GpuRoundRobinPartitioning)."""

    def __init__(self, plan, children, conf, n_out: int):
        super().__init__(plan, children, conf)
        self.n_out = n_out

    @property
    def num_partitions(self):
        return self.n_out

    def _repartition(self, child_results):
        if self.n_out == 1:
            return self._repartition_passthrough(child_results)
        part_t = self.metrics.metric(M.PARTITION_TIME)
        n_out = self.n_out
        compact = _partitioning_mode(self.conf) == "compact"

        def build():
            def fn(batch):
                live = batch.live_mask()
                pid = jnp.cumsum(live.astype(jnp.int32)) % n_out
                if compact:
                    return RP.counting_sort_by_pid(batch, pid, n_out)
                subs = []
                for p in range(n_out):
                    m = live & (pid == p)
                    subs.append(ColumnarBatch(
                        batch.columns, LazyRowCount(jnp.sum(m.astype(jnp.int32))), m))
                return subs
            return fn

        fn = fuse.fused(("rr_exchange_compact" if compact
                         else "rr_exchange", n_out), build)
        out: List[List[ColumnarBatch]] = [[] for _ in range(self.n_out)]
        if compact:
            self._compact_stream(
                (b for part in child_results for b in part), fn, out,
                part_t)
            return out
        for part in child_results:
            for batch in part:
                with self.span(part_t):
                    self._emit_masked(batch, fn(batch), out)
        return out


class RangeExchangeExec(ExchangeExec):
    """Range repartition by sort keys (reference GpuRangePartitioner +
    SamplingUtils): sample transformed order keys, compute n-1 bounds on
    host, then assign each row its partition by branch-free lexicographic
    bound comparisons on device. Output partition p holds rows ordering
    before partition p+1's — a per-partition sort then yields a globally
    sorted result without collecting to one partition (the scalability
    cliff VERDICT flagged)."""

    def __init__(self, plan, children, conf, orders, n_out: int):
        super().__init__(plan, children, conf)
        self.orders = orders
        self.n_out = n_out

    @property
    def num_partitions(self):
        return self.n_out

    def _key_fn(self):
        orders = self.orders

        def build():
            def fn(batch):
                live = batch.live_mask()
                ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                               batch.capacity, False, live=live)
                planes = []
                for o in orders:
                    kc = o.expr.eval_tpu(ectx)
                    k, nulls = K.normalize_key(kc, batch.num_rows, live=live)
                    null_rank = jnp.uint8(0) if o.resolved_nulls_first() \
                        else jnp.uint8(1)
                    val_rank = jnp.uint8(1) - null_rank
                    planes.append(jnp.where(nulls, null_rank, val_rank))
                    planes.append(k if o.ascending else ~k)
                return tuple(planes), live
            return fn

        return fuse.fused(
            ("range_keys", tuple((o.expr.fingerprint(), o.ascending,
                                  o.resolved_nulls_first())
                                 for o in self.orders)), build)

    def _repartition(self, child_results):
        if self.n_out == 1:
            return self._repartition_passthrough(child_results)
        part_t = self.metrics.metric(M.PARTITION_TIME)
        n_out = self.n_out
        keyfn = self._key_fn()
        per_batch = []   # (batch, planes)
        samples = []     # host tuples
        budget = self.conf.get(C.CPU_RANGE_PARTITION_SAMPLE) * n_out
        with self.span(part_t):
            for part in child_results:
                for batch in part:
                    planes, live = keyfn(batch)
                    per_batch.append((batch, planes))
                    # tpulint: disable=TPU-L004 range bounds need the sample values on host before the slicing kernels can be BUILT — there is no later point to consume a deferred fetch
                    host = host_planes(list(planes) + [live], "exchange")
                    lv = host[-1]
                    idx = np.flatnonzero(lv)
                    if len(idx) > budget:
                        # ceil stride so samples span the WHOLE batch — a
                        # floor stride takes a prefix and biases bounds on
                        # pre-ordered input
                        idx = idx[:: -(-len(idx) // budget)][:budget]
                    for i in idx:
                        samples.append(tuple(int(p[i]) for p in host[:-1]))
            if not samples:
                return [[] for _ in range(n_out)]
            samples.sort()
            bounds = [samples[(len(samples) * (i + 1)) // n_out]
                      for i in range(n_out - 1)]
            # bounds ride in as TRACED plane-aligned arrays — baking their
            # values into the fuse key would permanently cache one compiled
            # executable per dataset
            bound_planes = None
            compact = _partitioning_mode(self.conf) == "compact"

            def build():
                def fn(batch, planes, bplanes):
                    live = batch.live_mask()
                    pid = jnp.zeros(batch.capacity, jnp.int32)
                    for bi in range(n_out - 1):
                        # lexicographic: bound < row
                        lt = jnp.zeros(batch.capacity, jnp.bool_)
                        eq = jnp.ones(batch.capacity, jnp.bool_)
                        for bp, plane in zip(bplanes, planes):
                            bv = bp[bi]
                            lt = lt | (eq & (plane > bv))
                            eq = eq & (plane == bv)
                        pid = pid + lt.astype(jnp.int32)
                    if compact:
                        return RP.counting_sort_by_pid(batch, pid, n_out)
                    subs = []
                    for p in range(n_out):
                        m = live & (pid == p)
                        subs.append(ColumnarBatch(
                            batch.columns,
                            LazyRowCount(jnp.sum(m.astype(jnp.int32))), m))
                    return subs
                return fn

            fn = fuse.fused(("range_exchange_compact" if compact
                             else "range_exchange", n_out,
                             tuple((o.expr.fingerprint(), o.ascending)
                                   for o in self.orders)), build)
            out: List[List[ColumnarBatch]] = [[] for _ in range(n_out)]
            for batch, planes in per_batch:
                if bound_planes is None:
                    bound_planes = tuple(
                        jnp.asarray(np.array([b[j] for b in bounds],
                                             dtype=planes[j].dtype))
                        for j in range(len(planes)))
                if compact:
                    self._emit_compact(
                        batch, fn(batch, planes, bound_planes), out)
                else:
                    self._emit_masked(
                        batch, fn(batch, planes, bound_planes), out)
        return out


# ---------------------------------------------------------------------------
# Joins
# ---------------------------------------------------------------------------

#: a mask-through inner join cuts its probe to the build keys' span first
#: when the probe batch has more rows of capacity than this and that span
#: is at most one part in _JOIN_COMPACT_RATIO of the probe key's (_selective)
_JOIN_COMPACT_ABOVE = 1 << 20
_JOIN_COMPACT_RATIO = 4


class _HashJoinBase(TpuExec):
    """Shared probe loop for the hash-join family (reference GpuHashJoin /
    JoinGatherer assembly). Skew handling: when the build side exceeds the
    sub-partition threshold, both sides mask-split by key hash into k
    buckets (zero-copy: shared planes, different selection masks) and join
    pairwise — reference GpuSubPartitionHashJoin.scala:32,156-180."""

    def _sub_parts(self, build_rows: int) -> int:
        thr = self.conf.get(C.JOIN_SUBPARTITION_ROWS)
        if build_rows <= thr:
            return 1
        return min(-(-build_rows // thr), 64)

    def __init__(self, plan, children, conf):
        super().__init__(plan, children, conf)
        #: width-normalized (lkeys, rkeys) for hashing; set by the planner
        #: on the shuffled path, derived lazily elsewhere
        self.part_keys = None
        self._split_lock = threading.Lock()
        self._split_cache = None
        #: caching the split only pays when partitions share ONE build (the
        #: broadcast path); shuffled joins have per-partition builds and a
        #: shared lock would serialize them
        self._cache_build_split = False
        self._dense_lock = threading.Lock()
        self._dense_cache = None  # (build identity, DenseBuildTable|None)

    def _dense_table_for(self, build, build_keys):
        """Direct-address build table for the mask-through probe, prepared
        once per build batch (one 4-scalar fetch). Shared across actions
        through the plan node when the build itself is, and across whole
        ACTIONS through the session broadcast cache entry (the reference's
        reused-broadcast semantics: the table is a pure function of the
        build batch + probe key types)."""
        plan_cache = getattr(self.plan, "_dense_table_cache", None)
        if plan_cache is not None and plan_cache[0] is build:
            return plan_cache[1]
        entry = getattr(self.plan, "_bcast_session_entry", None)
        tkey = tuple(type(e.data_type()).__name__
                     for e in self.plan.left_keys)
        if entry is not None and entry["build"] is build \
                and tkey in entry["dense"]:
            table = entry["dense"][tkey]
            self.plan._dense_table_cache = (build, table)
            return table
        with self._dense_lock:
            if self._dense_cache is None or self._dense_cache[0] is not build:
                table = None
                if int(build.num_rows) > 0:
                    table = J.prepare_dense_build(
                        build_keys, build.num_rows,
                        [e.data_type() for e in self.plan.left_keys])
                self._dense_cache = (build, table)
                self.plan._dense_table_cache = (build, table)
                if entry is not None and entry["build"] is build:
                    entry["dense"][tkey] = table
            return self._dense_cache[1]

    def _hash_keys(self, side: int):
        if self.part_keys is None:
            # Spark murmur3 is width-sensitive (int32 and int64 hash
            # differently): bucket hashing must use a common key type on
            # both sides or equal values split across buckets.
            lks, rks = [], []
            for lk, rk in zip(self.plan.left_keys, self.plan.right_keys):
                ct = T.common_type(lk.data_type(), rk.data_type())
                lks.append(lk if lk.data_type() == ct else Cast(lk, ct))
                rks.append(rk if rk.data_type() == ct else Cast(rk, ct))
            self.part_keys = (lks, rks)
        return self.part_keys[side]

    def _split_build(self, build, k):
        """Split/compact the build side into k key-hash buckets; cached
        only when the exec shares one build across partitions."""
        def compute():
            parts = []
            for bp in self._bucket_split(build, self._hash_keys(1), k):
                bpc = K.compact_batch(bp)
                parts.append(
                    (bpc, compiled.run_stage(self.plan.right_keys, bpc)))
            return parts

        if not self._cache_build_split:
            return compute()
        with self._split_lock:
            if self._split_cache is None or self._split_cache[0] is not build:
                self._split_cache = (build, compute())
            return self._split_cache[1]

    def _bucket_split(self, batch, keys, k, seed=107):
        """Mask-partition a batch into k hash buckets of its join keys
        (seed 107 — the reference's agg-repartition seed)."""
        key_cols = compiled.run_stage(keys, batch)
        live = batch.live_mask()
        h = K.partition_hash_batch(key_cols, batch.num_rows, seed=seed, live=live)
        b = _pmod(h, k)
        out = []
        for i in range(k):
            m = live & (b == i)
            out.append(ColumnarBatch(batch.columns,
                                     LazyRowCount(jnp.sum(m.astype(jnp.int32))), m))
        return out

    def _probe_stream(self, ctx, probe_iter, build, build_keys, join_t,
                      track_build_matches: bool):
        """Yields joined batches, counting their rows (joinOutputRows)."""
        rows = self.metrics.metric(M.JOIN_OUTPUT_ROWS)
        for out in self._probe_batches(ctx, probe_iter, build, build_keys,
                                       join_t, track_build_matches):
            rows.add(out.num_rows)
            yield out

    def _probe_batches(self, ctx, probe_iter, build, build_keys, join_t,
                       track_build_matches: bool):
        how = self.plan.how
        matched_build = (jnp.zeros(build.capacity, jnp.bool_)
                         if track_build_matches else None)
        if how in ("inner", "left", "left_semi", "left_anti"):
            # mask-through fast path: unique dense build keys mean each
            # probe row matches <= 1 build row, so the join emits the probe
            # planes UNTOUCHED plus build columns gathered at probe
            # positions — no pair expansion, no compaction, no per-batch
            # host sync (reference contrast: GpuHashJoin always assembles
            # gather maps; on this hardware the gathers + count syncs they
            # imply cost more than the whole probe).
            table = self._dense_table_for(build, build_keys)
            if table is not None and table.max_dup <= 1:
                for probe in probe_iter:
                    self._acquire(ctx)
                    t0 = time.perf_counter_ns()
                    with self.span(join_t):
                        if how == "inner" and self._selective(probe, table):
                            probe = self._in_key_range(probe, table)
                        out = self._probe_masked(probe, build, table)
                    # a left join hands the probe's mask through: the
                    # matches are its last build column's validity
                    mark = out.row_mask if how != "left" \
                        else out.columns[-1].validity
                    if mark is not None:
                        device_mark(
                            self.metrics.metric(M.JOIN_DEVICE_TIME),
                            mark, t0)
                    yield out
                return
        # sub-partitioning applies to inner/left/semi/anti; right/full track
        # a build-global matched mask that bucket-local indices would
        # corrupt, so they stay on the single-pass path
        k = self._sub_parts(int(build.num_rows)) \
            if how in ("inner", "left", "left_semi", "left_anti") else 1
        build_parts = self._split_build(build, k) if k > 1 else None
        for probe in probe_iter:
            self._acquire(ctx)
            with self.span(join_t):
                if build_parts is not None:
                    probe_parts = self._bucket_split(probe, self._hash_keys(0), k)
                    for pp, (bpc, bkeys) in zip(probe_parts, build_parts):
                        ppc = K.compact_batch(pp)
                        _, out = self._probe_one(ppc, bpc, bkeys, None)
                        if out is not None:
                            yield out
                    continue
                matched_build, out = self._probe_one(probe, build, build_keys,
                                                     matched_build)
                if out is not None:
                    yield out
        if track_build_matches:
            un_idx, n_un = J.unmatched_indices(matched_build,
                                               build.live_mask())
            if n_un:
                from spark_rapids_tpu.columnar.batch import empty_like_schema
                dummy = empty_like_schema(self.children[0].schema, capacity=8)
                pi = jnp.full(un_idx.shape, -1, jnp.int32)
                yield self._emit(dummy, build, pi, un_idx, n_un)

    def _selective(self, probe, table) -> bool:
        """Whether an inner join's build keys span so little of a large
        probe batch's key range that the probe is cut to that span and
        compacted first (_in_key_range), and the look-up and the gathers
        of the build's columns run over the survivors and not at the
        probe's capacity. Judged from what the host has, with no read-back
        of its own: the dense table's span against the probe key's column
        stats. A star join's filtered dimension (one year of a calendar)
        is the case; an unfiltered dimension spans its key and the probe
        passes through masked as before."""
        key = self.plan.left_keys[0]
        if probe.capacity <= _JOIN_COMPACT_ABOVE \
                or not isinstance(key, BoundRef):
            return False
        bounds = probe.columns[key.index].bounds
        if bounds is None:
            return False
        return table.span * _JOIN_COMPACT_RATIO <= bounds[1] - bounds[0] + 1

    def _in_key_range(self, probe, table) -> ColumnarBatch:
        """The probe rows whose key lies in the build keys' span,
        compacted (the count's read-back sizes the output): every row an
        inner join can match, and few others when the span is dense."""
        key = self.plan.left_keys[0]

        def build():
            def join_key_range(probe, bmin, span):
                live = probe.live_mask()
                ectx = EvalCtx(probe.columns, traced_rows(probe.num_rows),
                               probe.capacity, False, live=live)
                k = key.eval_tpu(ectx)
                v = k.data.astype(jnp.int64)
                ok = (v >= bmin) & (v < bmin + span)
                if k.validity is not None:
                    ok = ok & k.validity
                return K.mask_filter_batch(probe, ok)
            return join_key_range

        cut = fuse.fused(("join_key_range", key.fingerprint()), build)(
            probe, table.bmin, jnp.int64(table.span))
        carry_host_stats(probe.columns, cut.columns)
        return K.compact_batch(cut)

    def _probe_masked(self, probe, build, table) -> ColumnarBatch:
        """Unique-build-key join without pair materialization: output is a
        masked batch sharing the probe's planes. Handles inner/left/semi/
        anti, including join conditions (evaluated as a mask over the
        mask-through batch — valid because each probe row has at most one
        candidate)."""
        how = self.plan.how
        plan = self.plan
        left_keys, right_keys = plan.left_keys, plan.right_keys
        condition = plan.condition
        # key_map: build cols reconstructable from probe keys (static
        # decision from plan schemas)
        key_map = {}
        for ki, rk in enumerate(right_keys):
            lt = left_keys[ki].data_type()
            if isinstance(rk, BoundRef) and rk.index < len(build.columns):
                c = build.columns[rk.index]
                if lt == c.dtype and not c.is_string and not c.is_nested:
                    key_map[rk.index] = ki

        # the build columns the join gathers, packed where the host knows
        # them small (K.gather_plan): one gather a word, not two a column
        plan = K.gather_plan([c for ci, c in enumerate(build.columns)
                              if ci not in key_map])

        def build_fn():
            def fn(probe, build, slot_idx, bmin):
                plive = probe.live_mask()
                ectx = EvalCtx(probe.columns, traced_rows(probe.num_rows),
                               probe.capacity, False, live=plive)
                probe_keys = [e.eval_tpu(ectx) for e in left_keys]
                pk0 = probe_keys[0]
                p_in = plive if pk0.validity is None \
                    else (plive & pk0.validity)
                bidx = J.dense_lookup_planes(slot_idx, bmin,
                                             pk0.data.astype(jnp.int64),
                                             p_in)
                matched = bidx >= 0
                blive = build.live_mask() if build.row_mask is not None \
                    else None
                gathered = iter(K.gather_columns(
                    [c for ci, c in enumerate(build.columns)
                     if ci not in key_map], bidx, build.num_rows,
                    src_live=blive, plan=plan))
                bcols = []
                for ci, c in enumerate(build.columns):
                    ki = key_map.get(ci)
                    if ki is not None:
                        pk = probe_keys[ki]
                        v = (pk.validity & matched) \
                            if pk.validity is not None else matched
                        bcols.append(ColumnVector(c.dtype, pk.data, v))
                    else:
                        bcols.append(next(gathered))
                if condition is not None:
                    cctx = EvalCtx(list(probe.columns) + bcols,
                                   traced_rows(probe.num_rows),
                                   probe.capacity, False, live=plive)
                    pred = condition.eval_tpu(cctx)
                    cond_ok = pred.data.astype(jnp.bool_) \
                        & (pred.validity if pred.validity is not None
                           else jnp.ones(probe.capacity, jnp.bool_))
                    matched = matched & cond_ok
                if how == "left_semi":
                    return K.mask_filter_batch(probe, matched)
                if how == "left_anti":
                    return K.mask_filter_batch(probe, ~matched)
                if how == "inner":
                    live = plive & matched
                    return ColumnarBatch(
                        list(probe.columns) + bcols,
                        LazyRowCount(jnp.sum(live.astype(jnp.int32))), live)
                ob = [ColumnVector(c.dtype, c.data,
                                   (c.validity & matched)
                                   if c.validity is not None else matched,
                                   dict_unique=c.dict_unique)
                      for c in bcols]
                return ColumnarBatch(list(probe.columns) + ob,
                                     probe.num_rows, probe.row_mask)
            return fn

        key = ("dense_probe_masked", how,
               tuple(e.fingerprint() for e in left_keys),
               tuple(e.fingerprint() for e in right_keys),
               condition.fingerprint() if condition is not None else None,
               tuple(sorted(key_map.items())), plan)
        fn = fuse.fused(key, build_fn)
        out = fn(probe, build, table.slot_idx, table.bmin)
        if how == "left":
            # every probe row comes out once: the input's own row count
            # object, a host int where the probe's was
            out = ColumnarBatch(out.columns, probe.num_rows, out.row_mask)
        # probe planes pass through, build columns are gathered: both
        # keep their column stats (bounds, string widths)
        carry_host_stats(probe.columns, out.columns)
        if how in ("inner", "left"):
            carry_host_stats(build.columns,
                             out.columns[len(probe.columns):])
        return out

    def _probe_one(self, probe, build, build_keys, matched_build):
        how = self.plan.how
        probe_keys = compiled.run_stage(self.plan.left_keys, probe)
        live = probe.live_mask() if probe.row_mask is not None else None
        pi, bi, nmatch = J.join_pairs(build_keys, build.num_rows,
                                      probe_keys, probe.num_rows,
                                      probe_live=live)
        pi, bi, nmatch = self._apply_condition(probe, build, pi, bi, nmatch)
        if how in ("left_semi", "left_anti"):
            mask = J.probe_matched_mask(pi, probe.capacity)
            if how == "left_anti":
                mask = ~mask
            return matched_build, K.mask_filter_batch(probe, mask)
        if how in ("left", "full"):
            mask = J.probe_matched_mask(pi, probe.capacity)
            un_idx, n_un = J.unmatched_indices(mask, probe.live_mask())
            if n_un:
                tot = nmatch + n_un
                cap = round_capacity(max(tot, 1))
                pi = _concat_idx(pi, nmatch, un_idx, n_un, cap)
                bi = _concat_idx(bi, nmatch,
                                 jnp.full(un_idx.shape, -1, jnp.int32),
                                 n_un, cap)
                nmatch = tot
        if matched_build is not None:
            matched_build = matched_build | J.probe_matched_mask(
                bi, build.capacity)
        return matched_build, self._emit(probe, build, pi, bi, nmatch)

    def _apply_condition(self, probe, build, pi, bi, nmatch):
        if self.plan.condition is None or nmatch == 0:
            return pi, bi, nmatch
        pair_batch = _pair_batch(probe, build, pi, bi, nmatch)
        [pred] = compiled.run_stage([self.plan.condition], pair_batch)
        keep = pred.data.astype(jnp.bool_) & pred.validity_or_default(nmatch)
        keep = keep & (jnp.arange(pi.shape[0]) < nmatch)
        idx, cnt = K.filter_indices(keep, pi.shape[0])
        sel = jnp.clip(idx, 0, pi.shape[0] - 1)
        return (jnp.where(idx >= 0, pi[sel], -1),
                jnp.where(idx >= 0, bi[sel], -1), cnt)

    def _emit(self, probe, build, pi, bi, n):
        return _pair_batch(probe, build, pi, bi, n)


class BroadcastHashJoinExec(_HashJoinBase):
    """Build side fully materialized (broadcast analog), probe side streamed
    per partition (reference GpuBroadcastHashJoinExecBase). Build side =
    RIGHT child. right/full outer joins are planned through a collect
    exchange so this exec sees a single probe partition."""

    def __init__(self, plan, children, conf):
        super().__init__(plan, children, conf)
        self._build_lock = threading.Lock()
        self._build: Optional[ColumnarBatch] = None
        self._build_keys = None
        self._cache_build_split = True  # one shared build for all partitions

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def _cacheable_build_plan(self) -> bool:
        """The build result may be cached ACROSS actions (reused broadcast,
        the ReusedExchange analog) when the build subtree is a pure view
        over an immutable cached relation."""
        def ok(n):
            if isinstance(n, (P.CachedRelation,)):
                return True
            if isinstance(n, (P.Filter, P.Project, P.Limit)):
                return all(ok(c) for c in n.children)
            return False
        return ok(self.plan.children[1])

    def _reuse_anchor(self):
        """(CachedRelation, structural fingerprint) for the cross-action
        broadcast cache, or (None, None). Only build subtrees reading
        EXACTLY ONE cached relation participate: the reused entry lives ON
        that relation (so it is dropped with the cache, never pins HBM
        past it, and object identity cannot be confused by recycled ids —
        the reference's exchange-reuse map scopes lifetime the same way)."""
        rels = []

        def walk(n):
            if isinstance(n, P.CachedRelation):
                rels.append(n)
                return "cached"
            parts = tuple(walk(c) for c in n.children)
            if isinstance(n, P.Filter):
                return ("filter", n.condition.fingerprint(), parts)
            if isinstance(n, P.Project):
                return ("project",
                        tuple(e.fingerprint() for e in n.exprs), parts)
            if isinstance(n, P.Limit):
                return ("limit", n.n, parts)
            # _cacheable_build_plan() admits only the node kinds above;
            # anything else poisons the key so no reuse can happen
            rels.append(None)
            rels.append(None)
            return ("uncacheable",)

        fp = walk(self.plan.children[1])
        if len(rels) != 1 or rels[0] is None:
            return None, None
        return rels[0], (fp, tuple(e.fingerprint()
                                   for e in self.plan.right_keys))

    def _build_side(self) -> ColumnarBatch:
        with self._build_lock:
            if self._build is None:
                cached = getattr(self.plan, "_bcast_cache", None)
                if cached is not None and self._cacheable_build_plan():
                    self._build, self._build_keys = cached
                    return self._build
                anchor = skey = None
                if self._cacheable_build_plan():
                    anchor, skey = self._reuse_anchor()
                if anchor is not None:
                    store = getattr(anchor, "_bcast_reuse", {})
                    entry = store.get(skey)
                    # entry is valid only for the materialization it was
                    # built from (identity checked against LIVE state: a
                    # re-cache replaces the list and invalidates)
                    if entry is not None \
                            and entry["mat"] is not anchor.materialized:
                        del store[skey]  # stale: stop pinning old batches
                        entry = None
                    from spark_rapids_tpu.exec import adaptive as AQ
                    src = "anchor"
                    if entry is None:
                        # second chance: the digest-keyed cross-query
                        # cache (exec/adaptive.py) — a DIFFERENT plan
                        # tree joining the same cached relation through
                        # the same build shape reuses the materialized
                        # broadcast; the hit re-warms the anchor store
                        entry = AQ.build_cache_get(
                            self.conf, self.plan.children[1], skey, anchor)
                        src = "digest"
                        if entry is not None:
                            if len(store) >= 8:
                                store.pop(next(iter(store)))
                            store[skey] = entry
                            if getattr(anchor, "_bcast_reuse",
                                       None) is None:
                                anchor._bcast_reuse = store
                    if entry is not None:
                        self._build = entry["build"]
                        self._build_keys = entry["keys"]
                        self.plan._bcast_cache = (self._build,
                                                  self._build_keys)
                        self.plan._bcast_session_entry = entry
                        if AQ.enabled(self.conf):
                            AQ.record(
                                AQ.BUILD_REUSE, source=src,
                                dispatches_saved=int(
                                    entry.get("build_batches", 0)) or 1)
                        return self._build
                build_t = self.metrics.metric(M.BUILD_TIME)
                right = self.children[1]
                batches = []
                with self.span(build_t):
                    for p in range(right.num_partitions):
                        with TaskContext(partition_id=p) as tctx:
                            batches.extend(right.execute_partition(tctx, p))
                    if batches:
                        self._build = K.compact_batch(K.concat_batches(batches))
                    else:
                        from spark_rapids_tpu.columnar.batch import empty_like_schema
                        self._build = empty_like_schema(right.schema)
                    self._build_keys = compiled.run_stage(
                        self.plan.right_keys, self._build)
                if anchor is not None and anchor.materialized is not None:
                    entry = {"build": self._build, "keys": self._build_keys,
                             "dense": {}, "mat": anchor.materialized,
                             "build_batches": len(batches)}
                    store = getattr(anchor, "_bcast_reuse", None)
                    if store is None:
                        store = anchor._bcast_reuse = {}
                    if len(store) >= 8:
                        store.pop(next(iter(store)))
                    store[skey] = entry
                    self.plan._bcast_session_entry = entry
                    self.plan._bcast_cache = (self._build, self._build_keys)
                    from spark_rapids_tpu.exec import adaptive as AQ
                    AQ.build_cache_put(self.conf, self.plan.children[1],
                                       skey, anchor, entry)
        return self._build

    def execute_partition(self, ctx, pidx):
        join_t = self.metrics.metric(M.JOIN_TIME)
        build = self._build_side()
        track = self.plan.how in ("right", "full")
        probe_iter = self.children[0].execute_partition(ctx, pidx)
        yield from self._probe_stream(ctx, probe_iter, build,
                                      self._build_keys, join_t, track)


class AdaptiveJoinExec(TpuExec):
    """Runtime join-strategy pick (the AQE role; reference
    GpuCustomShuffleReaderExec + per-stage re-planning): when the planner
    cannot estimate the build side, materialize it ONCE at execution time
    and route on the MEASURED row count — broadcast when it fits, hash
    exchange both sides otherwise. The materialized build feeds whichever
    strategy wins (no recompute: the exchange path consumes it through an
    in-memory source, matching AQE's reuse of materialized stages)."""

    def __init__(self, plan, children, conf, part_keys):
        super().__init__(plan, children, conf)
        self.part_keys = part_keys
        self._lock = threading.Lock()
        self._chosen: Optional[TpuExec] = None

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def _choose(self) -> TpuExec:
        with self._lock:
            if self._chosen is None:
                left, right = self.children
                threshold = self.conf.get(C.BROADCAST_JOIN_ROW_THRESHOLD)
                # stream the build side only UP TO the threshold: measuring
                # by materializing everything would hold the whole side in
                # HBM exactly when it is too big to broadcast
                batches, rows, overflow = [], 0, False
                for p in range(right.num_partitions):
                    with TaskContext(partition_id=p) as tctx:
                        for b in right.execute_partition(tctx, p):
                            batches.append(b)
                            rows += rows_int(b.num_rows)
                            if rows > threshold:
                                overflow = True
                                break
                    if overflow:
                        break
                if not overflow:
                    right_src = _MaterializedExec(self.plan.children[1],
                                                  batches, self.conf)
                    self._chosen = BroadcastHashJoinExec(
                        self.plan, [left, right_src], self.conf)
                    from spark_rapids_tpu.exec import adaptive as AQ
                    AQ.record(AQ.BROADCAST_CONVERSION, source="row_probe",
                              build_rows=rows, threshold_rows=threshold,
                              # both sides' exchanges (partition kernel +
                              # offsets fetch per input batch) never run
                              dispatches_saved=2 * max(len(batches), 1))
                else:
                    del batches  # release; the exchange re-executes right
                    lkeys, rkeys = self.part_keys
                    n_out = left.num_partitions
                    lex = ShuffleExchangeExec(self.plan, [left], self.conf,
                                              lkeys, n_out)
                    rex = ShuffleExchangeExec(self.plan, [right], self.conf,
                                              rkeys, n_out)
                    self._chosen = ShuffledHashJoinExec(
                        self.plan, [lex, rex], self.conf,
                        part_keys=self.part_keys)
        return self._chosen

    def execute_partition(self, ctx, pidx):
        yield from self._choose().execute_partition(ctx, pidx)


class _MaterializedExec(TpuExec):
    """Already-materialized device batches as a single-partition exec (the
    reused-stage input of the adaptive path)."""

    def __init__(self, plan, batches, conf):
        super().__init__(plan, [], conf)
        self._batches = list(batches)

    @property
    def schema(self):
        return self.plan.schema

    @property
    def num_partitions(self):
        return 1

    def execute_partition(self, ctx, pidx):
        yield from self._batches


class BroadcastNestedLoopJoinExec(TpuExec):
    """Non-equi joins (reference GpuBroadcastNestedLoopJoinExecBase): the
    build (right) side broadcasts whole; the join condition evaluates over
    TILED row pairs — left batch x one build tile per fused dispatch, with
    the pair batch emitted directly as a selection-masked output (inner)
    and per-side matched masks accumulated by scatter-or for outer/semi/
    anti completions. All shapes static per (left capacity, tile rows)."""

    MAX_PAIRS = 1 << 20

    def __init__(self, plan, children, conf):
        super().__init__(plan, children, conf)
        self._build_lock = threading.Lock()
        self._build: Optional[ColumnarBatch] = None

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def _build_side(self) -> ColumnarBatch:
        with self._build_lock:
            if self._build is None:
                build_t = self.metrics.metric(M.BUILD_TIME)
                right = self.children[1]
                batches = []
                with self.span(build_t):
                    for p in range(right.num_partitions):
                        with TaskContext(partition_id=p) as tctx:
                            batches.extend(right.execute_partition(tctx, p))
                    if batches:
                        self._build = K.compact_batch(K.concat_batches(batches))
                    else:
                        from spark_rapids_tpu.columnar.batch import empty_like_schema
                        self._build = empty_like_schema(right.schema)
        return self._build

    def _tile_fn(self, tile_rows: int, how: str, ansi: bool):
        cond = self.plan.condition

        def build():
            def fn(left: ColumnarBatch, build: ColumnarBatch, tile0,
                   lmatched, bmatched):
                lcap = left.capacity
                pairs = lcap * tile_rows
                p = jnp.arange(pairs, dtype=jnp.int32)
                lidx = p // tile_rows
                bidx = tile0 + (p % tile_rows)
                bcap = build.capacity
                b_in = bidx < traced_rows(build.num_rows)
                bsafe = jnp.clip(bidx, 0, bcap - 1)
                lcols = [K.gather_column(c, lidx, left.num_rows,
                                         src_live=left.live_mask())
                         for c in left.columns]
                bcols = [K.gather_column(c, bsafe, build.num_rows)
                         for c in build.columns]
                live_pair = left.live_mask()[lidx] & b_in
                ectx = EvalCtx(lcols + bcols, jnp.sum(live_pair.astype(jnp.int32)),
                               pairs, ansi, live=live_pair)
                if cond is not None:
                    pred = cond.eval_tpu(ectx)
                    pvalid = (pred.validity if pred.validity is not None
                              else ectx.row_mask)
                    match = live_pair & pred.data.astype(jnp.bool_) & pvalid
                else:
                    match = live_pair
                lmatched = lmatched.at[lidx].max(match)
                bmatched = bmatched.at[bsafe].max(match & b_in)
                out = None
                if how in ("inner", "left", "right", "full"):
                    out = ColumnarBatch(
                        lcols + bcols,
                        LazyRowCount(jnp.sum(match.astype(jnp.int32))), match)
                return out, lmatched, bmatched, dict(ectx.errors)
            return fn

        return fuse.fused(
            ("bnlj_tile", tile_rows, how, ansi,
             cond.fingerprint() if cond is not None else None), build)

    def execute_partition(self, ctx, pidx):
        join_t = self.metrics.metric(M.JOIN_TIME)
        how = self.plan.how
        ansi = self.conf.get(C.ANSI_ENABLED)
        build = self._build_side()
        n_build = int(build.num_rows)
        bcap = max(build.capacity, 1)
        bmatched_total = jnp.zeros(bcap, jnp.bool_)
        null_right_by_cap = {}

        for left in self.children[0].execute_partition(ctx, pidx):
            self._acquire(ctx)
            lcap = max(left.capacity, 1)
            tile_rows = max(1, min(bcap, self.MAX_PAIRS // lcap))
            fn = self._tile_fn(tile_rows, how, ansi)
            lmatched = jnp.zeros(lcap, jnp.bool_)
            with self.span(join_t):
                for t0 in range(0, max(n_build, 1), tile_rows):
                    if n_build == 0:
                        break
                    out, lmatched, bmatched_total, errs = fn(
                        left, build, jnp.int32(t0), lmatched, bmatched_total)
                    compiled.raise_errors(errs)
                    if out is not None and how != "left_semi":
                        yield out
                if how in ("left", "full"):
                    null_right = null_right_by_cap.get(lcap)
                    if null_right is None:
                        # per left-capacity: columns of one output batch
                        # must share a capacity
                        null_right = [
                            K.gather_column(c, jnp.full(lcap, -1, jnp.int32),
                                            build.num_rows)
                            for c in build.columns]
                        null_right_by_cap[lcap] = null_right
                    m = left.live_mask() & ~lmatched
                    yield ColumnarBatch(
                        list(left.columns) + null_right,
                        LazyRowCount(jnp.sum(m.astype(jnp.int32))), m)
                elif how == "left_semi":
                    m = left.live_mask() & lmatched
                    yield ColumnarBatch(
                        list(left.columns),
                        LazyRowCount(jnp.sum(m.astype(jnp.int32))), m)
                elif how == "left_anti":
                    m = left.live_mask() & ~lmatched
                    yield ColumnarBatch(
                        list(left.columns),
                        LazyRowCount(jnp.sum(m.astype(jnp.int32))), m)

        if how in ("right", "full") and n_build > 0:
            # single probe partition guaranteed by the planner
            null_left = [
                _null_gather(f.dtype, bcap)
                for f in self.plan.children[0].schema.fields]
            m = build.live_mask() & ~bmatched_total
            yield ColumnarBatch(
                null_left + list(build.columns),
                LazyRowCount(jnp.sum(m.astype(jnp.int32))), m)


def _null_gather(dtype, cap: int):
    """All-null column of `dtype` at capacity `cap`."""
    no = jnp.zeros(cap, jnp.bool_)
    if isinstance(dtype, T.StringType):
        return ColumnVector(dtype, {"offsets": jnp.zeros(cap + 1, jnp.int32),
                                    "bytes": jnp.zeros(8, jnp.uint8)}, no)
    return ColumnVector(dtype, jnp.zeros(cap, dtype.np_dtype), no)


class ShuffledHashJoinExec(_HashJoinBase):
    """Both sides hash-exchanged on the join keys; each partition builds
    from its slice of the right side and probes its slice of the left
    (reference GpuShuffledHashJoinExec:125). Unlike the broadcast path,
    right/full outer joins work per partition with NO collect: the
    exchange guarantees equal keys co-locate."""

    def __init__(self, plan, children, conf, part_keys=None):
        super().__init__(plan, children, conf)
        self.part_keys = part_keys

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def execute_partition(self, ctx, pidx):
        join_t = self.metrics.metric(M.JOIN_TIME)
        build_t = self.metrics.metric(M.BUILD_TIME)
        with self.span(build_t):
            batches = list(self.children[1].execute_partition(ctx, pidx))
            if batches:
                build = K.compact_batch(K.concat_batches(batches))
            else:
                from spark_rapids_tpu.columnar.batch import empty_like_schema
                build = empty_like_schema(self.children[1].schema)
            build_keys = compiled.run_stage(self.plan.right_keys, build)
        track = self.plan.how in ("right", "full")
        probe_iter = self.children[0].execute_partition(ctx, pidx)
        yield from self._probe_stream(ctx, probe_iter, build, build_keys,
                                      join_t, track)


def _pair_batch(left: ColumnarBatch, right: ColumnarBatch, li, ri, n: int
                ) -> ColumnarBatch:
    # masked sides join uncompacted: gathers must use the LIVE mask, not
    # arange<num_rows (live rows sit at arbitrary positions)
    llive = left.live_mask() if left.row_mask is not None else None
    rlive = right.live_mask() if right.row_mask is not None else None
    cols = [K.gather_column(c, li, left.num_rows, src_live=llive)
            for c in left.columns]
    cols += [K.gather_column(c, ri, right.num_rows, src_live=rlive)
             for c in right.columns]
    return ColumnarBatch(cols, n)


def _concat_idx(a, na: int, b, nb: int, cap: int):
    r = jnp.arange(cap, dtype=jnp.int32)
    from_a = r < na
    from_b = (r >= na) & (r < na + nb)
    av = a[jnp.clip(r, 0, a.shape[0] - 1)]
    bv = b[jnp.clip(r - na, 0, b.shape[0] - 1)]
    return jnp.where(from_a, av, jnp.where(from_b, bv, -1))


class CartesianProductExec(TpuExec):
    """Chunked cross join (reference GpuCartesianProductExec)."""

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def execute_partition(self, ctx, pidx):
        right = self.children[1]
        rbatches = []
        for p in range(right.num_partitions):
            with TaskContext(partition_id=p) as tctx:
                rbatches.extend(right.execute_partition(tctx, p))
        build = K.compact_batch(K.concat_batches(rbatches)) if rbatches else None
        for probe in self.children[0].execute_partition(ctx, pidx):
            self._acquire(ctx)
            if probe.row_mask is not None:
                probe = K.compact_batch(probe)
            if build is None or build.num_rows == 0 or probe.num_rows == 0:
                continue
            n = probe.num_rows * build.num_rows
            cap = round_capacity(n)
            r = jnp.arange(cap, dtype=jnp.int32)
            li = jnp.where(r < n, r // build.num_rows, -1)
            ri = jnp.where(r < n, r % build.num_rows, -1)
            out = _pair_batch(probe, build, li, ri, n)
            if self.plan.condition is not None:
                [pred] = compiled.run_stage([self.plan.condition], out)
                mask = pred.data.astype(jnp.bool_) & pred.validity_or_default(n)
                out = K.filter_batch(out, mask)
            yield out


# ---------------------------------------------------------------------------
# CPU fallback
# ---------------------------------------------------------------------------

class CpuFallbackExec(TpuExec):
    """Runs one plan node on the CPU backend, bridging device<->host at the
    boundaries (reference: unconverted nodes stay as CPU Spark operators
    with GpuColumnarToRow/RowToColumnar transitions inserted). Adjacent CPU
    fallbacks chain host-side without bouncing through the device."""

    @property
    def num_partitions(self):
        return 1

    def _child_cols(self, child: TpuExec):
        if isinstance(child, CpuFallbackExec):
            return child.cpu_result()
        tables = []
        for p in range(child.num_partitions):
            with TaskContext(partition_id=p) as tctx:
                for batch in child.execute_partition(tctx, p):
                    tables.append(to_arrow(batch, child.schema.names, "host"))
        if not tables:
            import pyarrow as pa
            fields = [pa.field(f.name, T.to_arrow(f.dtype))
                      for f in child.schema.fields]
            tables = [pa.Table.from_arrays(
                [pa.array([], type=f.type) for f in fields],
                schema=pa.schema(fields))]
        import pyarrow as pa
        return CPU.table_to_cols(pa.concat_tables(tables))

    def cpu_result(self):
        ansi = self.conf.get(C.ANSI_ENABLED)
        child_cols = [self._child_cols(c) for c in self.children]
        return CPU.apply_node(self.plan, child_cols, ansi)

    def execute_partition(self, ctx, pidx):
        cols = self.cpu_result()
        table = CPU.cols_to_table(cols, self.plan.schema.names)
        self._acquire(ctx)
        yield from_arrow(table)
