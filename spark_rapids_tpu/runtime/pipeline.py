"""Pipelined batch execution: overlap host decode/serde/upload with device
compute across exec boundaries.

Reference parity: the reference gets most of its throughput not from
kernels alone but from OVERLAP — MultiFileReaderThreadPool prefetches and
decodes the next chunk while the device computes, and the async write
path (ThrottlingExecutor/TrafficController) keeps serialization off the
compute critical path. PR 1/2 drove this engine down to ~1 dispatch per
batch per stage, but the `execute_partition` generator chains were still
fully synchronous: every batch's pyarrow decode, pad/H2D upload and
shuffle serde sat serially BETWEEN device dispatches. This module is the
classic input-pipeline answer — bounded-lookahead producer/consumer
pipelining at planner-chosen exec boundaries.

Design (the four interactions the header warned about):

* Producers run on the shared bounded host pool (runtime/host_pool.py),
  but as PULL-TRIGGERED REFILL tasks, not partition-lifetime threads: a
  refill produces until the bounded queue is full, stashes at most one
  overflow item, and returns its worker to the pool. The consumer
  re-arms the refill after every take. A producer therefore never
  blocks a pool worker on a full queue, and a fleet of concurrent
  pipelines cannot starve the pool the way partition-lifetime producer
  threads would.
* TaskContext is thread-local: each refill binds the consumer task's
  context for its duration (and restores the worker's previous binding)
  so semaphore re-entrancy, retry accounting and trace-track attribution
  all see the owning task from producer threads.
* The device semaphore is acquired by the CONSUMER before the first
  refill is armed (the boundary sits above a scan whose first upload
  would acquire anyway). The task already holds its permit when
  producer-side uploads run, so a producer never parks a pool worker in
  the semaphore wait queue — the pool stays live for the permit-holders
  whose prefetch work it must run.
* Early exit (LIMIT closing its upstream) cancels the pipeline: close()
  stops re-arming, waits for the in-flight refill to return its worker,
  and closes the source generator from a thread that is provably not
  executing it. Producer exceptions (including retry-OOM that exhausted
  its retries) travel through the queue and re-raise at the consumer.

Per-stage fallback: PipelineExec runs the child synchronously whenever
depth<=0, the submission would land at host-pool depth 2 (inline — no
overlap possible, and a bounded queue with no concurrent consumer would
deadlock), or pipeline setup raises.

`start_d2h` is the deferred-scalar-fetch half of the design: call sites
that need a per-batch device scalar on the host (compact-shuffle offsets,
LIMIT/TopN carries) start the D2H copy right after the dispatch that
produces it and consume the value only when the NEXT batch has been
dispatched, so the transfer rides under device compute instead of
serializing against it.
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Iterator, Optional

log = logging.getLogger("spark_rapids_tpu")

#: queue sentinel: the producer exhausted its source
_DONE = object()
#: hand sentinel: no stashed overflow item
_EMPTY = object()

#: consumers currently blocked waiting on a producer refill — the live
#: "pipeline stall state" gauge the resource sampler reads
#: (runtime/obs/sampler.py). Guarded by its own tiny lock: the counter
#: moves only on the SLOW path (the consumer is about to block on an
#: empty queue), never per batch.
_STALLED = 0
_STALL_LOCK = threading.Lock()


def stalled_consumers() -> int:
    """Pipeline consumers blocked on a producer right now (racy read by
    design — it feeds a sampler gauge)."""
    return _STALLED


def _stall_enter() -> None:
    global _STALLED
    with _STALL_LOCK:
        _STALLED += 1


def _stall_exit() -> None:
    global _STALLED
    with _STALL_LOCK:
        _STALLED = max(0, _STALLED - 1)


def start_d2h(dev) -> None:
    """Begin an async device->host copy of `dev` (a jax array) without
    waiting for it. A later int()/np.asarray() of the same array then
    finds the transfer finished (or in flight) instead of starting it
    cold. Best effort: backends without copy_to_host_async (or non-array
    inputs) are a no-op — the later blocking fetch still works."""
    fn = getattr(dev, "copy_to_host_async", None)
    if fn is not None:
        try:
            fn()
        except Exception:  # noqa: BLE001 - prefetch only, never required
            pass


class _ProducerError:
    """Queue envelope for an exception raised on the producer side."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class PipelinedIterator:
    """Bounded-lookahead bridge: items of `source` are produced on the
    host pool up to `depth` ahead of the consumer.

    Iterate it exactly once (it is its own iterator) and close() it when
    done — PipelineExec does both; direct users should too. Thread
    model: ONE consumer thread iterates; refill tasks never run
    concurrently with each other (single-flight, guarded by _lock)."""

    def __init__(self, source: Iterator, depth: int, ctx=None,
                 conf=None, label: str = "pipeline",
                 stall_metric=None, producer_metric=None):
        from spark_rapids_tpu.runtime.host_pool import get_host_pool
        self._source = source
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._ctx = ctx
        self._label = label
        self._stall = stall_metric
        self._prod = producer_metric
        from spark_rapids_tpu.analysis import sanitizer as _san
        from spark_rapids_tpu.runtime.obs import live as _live
        self._pool = get_host_pool(conf)
        # the consumer's bound query id: refills re-bind it (with the
        # TaskContext) so producer-side spans/instants/ring entries
        # attribute to the owning query even from pool workers that the
        # submit-time wrapper cannot cover (the refill re-arms ITSELF
        # from inside _refill_loop's exit paths via the consumer)
        self._query_id = _live.current_query_id()
        # the consumer's serving request context rides the same seam:
        # producer-side spans land in the request's reqtrace ring even
        # when a consumer-armed refill runs on a fresh pool worker
        self._req = _live.current_request()
        self._lock = _san.lock("pipeline.iterator")
        self._cancel = False
        self._refill_running = False
        self._finished = False      # terminal item produced (DONE/error)
        self._hand = _EMPTY         # overflow item a full queue bounced
        self._future = None         # in-flight refill, for close()
        self._closed = False
        self._ensure_refill()

    # -- producer side -----------------------------------------------------

    def _ensure_refill(self) -> None:
        with self._lock:
            if (self._refill_running or self._cancel
                    or (self._finished and self._hand is _EMPTY)):
                return
            self._refill_running = True
            self._future = self._pool.submit(self._refill)

    def _refill(self) -> None:
        """Produce until the bounded queue is full (stashing at most one
        bounced item), then return the pool worker. Runs under the
        consumer task's TaskContext so upstream semaphore/retry/trace
        state attributes to the owning task.

        Invariant: _refill_running flips False under the SAME lock hold
        that decides to exit — a consumer that takes the lock afterwards
        either sees an armed refill or may safely arm one. Clearing the
        flag in a finally instead would leave a window where the
        consumer drains the queue against a stale True and blocks with
        nobody left to re-arm."""
        from spark_rapids_tpu.runtime.obs import live as _live
        from spark_rapids_tpu.runtime.task import TaskContext
        prev = TaskContext.peek()
        prev_qid = _live.bind(self._query_id)
        prev_req = _live.bind_request(self._req)
        if self._ctx is not None:
            TaskContext.set_current(self._ctx)
        try:
            try:
                self._refill_loop()
            except BaseException as e:  # noqa: BLE001 - _refill_loop only
                # raises on instrumentation bugs; the consumer must still
                # be unblocked with a terminal item
                with self._lock:
                    self._refill_running = False
                    if not self._finished:
                        self._finished = True
                        try:
                            self._q.put_nowait(_ProducerError(e))
                        except queue.Full:
                            self._hand = _ProducerError(e)
        finally:
            _live.bind_request(prev_req)
            _live.bind(prev_qid)
            if self._ctx is not None:
                if prev is not None:
                    TaskContext.set_current(prev)
                else:
                    TaskContext.clear()

    def _refill_loop(self) -> None:
        from spark_rapids_tpu.runtime import faults as _faults
        from spark_rapids_tpu.runtime import lifecycle as _lc
        from spark_rapids_tpu.runtime import trace
        while True:
            with self._lock:
                if self._cancel:
                    self._refill_running = False
                    return
                if self._hand is not _EMPTY:
                    try:
                        self._q.put_nowait(self._hand)
                        self._hand = _EMPTY
                    except queue.Full:
                        # consumer re-arms after its next take
                        self._refill_running = False
                        return
                if self._finished:
                    self._refill_running = False
                    return
            t0 = time.perf_counter_ns()
            try:
                # cooperative checkpoint: a cancelled query's refill
                # raises here and the error travels the producer-error
                # envelope to the consumer, which unwinds normally
                _lc.check_current()
                # producer-death injection: a fault here travels the same
                # envelope as a real upstream decode failure
                _faults.site("pipeline.producer")
                item = next(self._source)
            except StopIteration:
                item = _DONE
            except BaseException as e:  # noqa: BLE001 - travels to the
                item = _ProducerError(e)  # consumer and re-raises there
            dt = time.perf_counter_ns() - t0
            if self._prod is not None and not isinstance(
                    item, _ProducerError) and item is not _DONE:
                self._prod.add(dt)
            if trace.active() is not None:
                trace.emit_span("pipelineProduce", t0, dt, cat="pipeline",
                                args={"label": self._label},
                                level=trace.DEBUG)
            with self._lock:
                if item is _DONE or isinstance(item, _ProducerError):
                    self._finished = True
                if self._cancel:
                    self._refill_running = False
                    return
                try:
                    self._q.put_nowait(item)
                except queue.Full:
                    self._hand = item
                    self._refill_running = False
                    return

    # -- consumer side -----------------------------------------------------

    def __iter__(self):
        while True:
            self._ensure_refill()
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                t0 = time.perf_counter_ns()
                _stall_enter()
                try:
                    item = self._q.get()
                finally:
                    _stall_exit()
                dt = time.perf_counter_ns() - t0
                if self._stall is not None:
                    self._stall.add(dt)
            if item is _DONE:
                return
            if isinstance(item, _ProducerError):
                raise item.exc
            yield item

    def close(self) -> None:
        """Cancel the pipeline: stop re-arming, wait out the in-flight
        refill, then close the source generator (safe — nothing is
        executing it once the refill returned). Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._cancel = True
            fut = self._future
        if fut is not None:
            try:
                fut.result(timeout=300)
            except Exception:  # noqa: BLE001 - refill never raises; a
                # timeout means a wedged upstream decode, log and move on
                log.warning("pipeline %s: refill did not finish on close",
                            self._label, exc_info=True)
        try:
            self._source.close()
        except BaseException:  # noqa: BLE001 - upstream cleanup only
            pass
        # drop buffered batches promptly (device memory)
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._hand = _EMPTY


# ---------------------------------------------------------------------------
# The exec node + planner pass
# ---------------------------------------------------------------------------

_PIPELINE_CLS = None


def make_pipeline_exec():
    """PipelineExec is defined against the live TpuExec base lazily (the
    stage_fusion pattern) so this module imports without pulling the
    operator library."""
    from spark_rapids_tpu.exec import tpu_nodes as X
    from spark_rapids_tpu.runtime import metrics as M
    from spark_rapids_tpu.runtime.host_pool import HostTaskPool
    from spark_rapids_tpu.runtime.lifecycle import QueryCancelledError

    class PipelineExec(X.TpuExec):
        """Pipeline boundary: runs its child's generator on the host pool
        with bounded lookahead so the child's host work (decode, pad,
        upload) overlaps the parent's device compute. Transparent to the
        data: yields the child's batches unchanged."""

        def __init__(self, plan, children, conf, depth: int):
            super().__init__(plan, children, conf)
            self.depth = int(depth)

        @property
        def schema(self):
            return self.children[0].schema

        @property
        def num_partitions(self):
            return self.children[0].num_partitions

        def name(self) -> str:
            return f"PipelineExec(depth={self.depth})"

        def tree_string(self, indent: int = 0) -> str:
            pad = "  " * indent
            return "\n".join([f"{pad}{self.name()}",
                              self.children[0].tree_string(indent + 1)])

        def execute_partition(self, ctx, pidx):
            depth_m = self.metrics.metric(M.PIPELINE_DEPTH)
            out_batches = self.metrics.metric(M.NUM_OUTPUT_BATCHES)
            # depth-2 pool submissions run inline: an "async" producer on
            # the consumer's own thread gives zero overlap and a bounded
            # queue nobody drains — run synchronously instead
            if self.depth <= 0 or HostTaskPool._depth() >= 2:
                depth_m.set(0)
                for b in self.children[0].execute_partition(ctx, pidx):
                    out_batches.add(1)
                    yield b
                return
            src = self.children[0].execute_partition(ctx, pidx)
            try:
                # consumer-side acquire BEFORE the producer is armed: the
                # task holds its permit when producer uploads run, so a
                # producer never parks a pool worker on the semaphore
                self._acquire(ctx)
                pit = PipelinedIterator(
                    src, self.depth, ctx=ctx, conf=self.conf,
                    label=f"{type(self.children[0]).__name__}@p{pidx}",
                    stall_metric=self.metrics.metric(M.PIPELINE_STALL_TIME),
                    producer_metric=self.metrics.metric(
                        M.PIPELINE_PRODUCER_TIME))
            except QueryCancelledError:
                # a cancelled query's unwind is not a setup failure:
                # running the stage synchronously would resurrect the
                # killed work
                raise
            except Exception:  # noqa: BLE001 - per-stage fallback: a
                # pipeline setup failure must degrade to the synchronous
                # path, never fail the query
                from spark_rapids_tpu.runtime import obs as _obs
                _obs.note_exec_fallback("pipeline")
                log.warning("pipeline setup failed for %s; running "
                            "synchronously", self.name(), exc_info=True)
                depth_m.set(0)
                for b in src:
                    out_batches.add(1)
                    yield b
                return
            depth_m.set(self.depth)
            try:
                for b in pit:
                    out_batches.add(1)
                    yield b
            finally:
                pit.close()

    return PipelineExec


def pipeline_exec_cls():
    global _PIPELINE_CLS
    if _PIPELINE_CLS is None:
        _PIPELINE_CLS = make_pipeline_exec()
    return _PIPELINE_CLS


def pipeline_conf(conf) -> int:
    """Effective lookahead depth from the conf pair (0 = disabled)."""
    from spark_rapids_tpu import config as C
    if not conf.get(C.PIPELINE_ENABLED):
        return 0
    return max(0, int(conf.get(C.PIPELINE_DEPTH)))


def insert_pipelines(exec_root, conf):
    """Planner pass (applied by plan/overrides.convert_plan after stage
    fusion): wrap every non-root host-producing scan in a PipelineExec so
    the scan->compute edge becomes a pipeline boundary. Scans feeding an
    exchange get the same treatment — the exchange's partition kernel is
    the consumer there (the compute->exchange-write half of the overlap
    is the exchange's own throttled async writer and deferred offsets
    fetch, tpu_nodes.py)."""
    depth = pipeline_conf(conf)
    if depth <= 0:
        return exec_root
    from spark_rapids_tpu.exec import tpu_nodes as X
    scan_types = (X.ParquetScanExec, X.EncodedParquetSourceExec,
                  X.TextScanExec, X.InMemoryScanExec,
                  X.ShuffleFileScanExec)
    cls = pipeline_exec_cls()

    def rewrite(node, parent):
        node.children = [rewrite(c, node) for c in node.children]
        if parent is not None and isinstance(node, scan_types):
            return cls(node.plan, [node], conf, depth)
        return node

    return rewrite(exec_root, None)
