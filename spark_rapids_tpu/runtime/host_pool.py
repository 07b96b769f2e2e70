"""Process-wide bounded host task pool.

Reference parity: MultiFileReaderThreadPool (GpuMultiFileReader.scala) —
ONE executor-wide pool shared by every multi-file reader, sized once,
instead of a pool per scan. This engine previously built a throwaway
ThreadPoolExecutor per prefetch call and per exchange materialization;
every one paid thread start-up latency and, worse, the aggregate thread
count was unbounded (an exchange over an exchange over N parquet scans
could spawn writer*reader*scan threads). All host-side task parallelism
(scan prefetch, exchange child materialization, serialized-shuffle codec
work, shuffle-blob decode) now shares this bounded pool.

Deadlock discipline: pool workers may themselves reach code that submits
to the pool (an exchange task runs a scan whose prefetcher submits row-
group loads — the engine's dominant query shape). A single bounded pool
whose workers block on queued work deadlocks, so the pool is TWO tiers
of equal size: top-level submissions run on tier 0, submissions from a
tier-0 worker run on tier 1 (scan prefetch under an exchange keeps its
decode/upload overlap), and submissions from a tier-1 worker run inline.
Tier-1 workers never wait on tier-1 work, so no cycle can starve — the
same layering the reference gets from keeping file reads off the shuffle
threads, with both tiers' sizes still bounded.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Iterable, Iterator, List, Optional

from spark_rapids_tpu.analysis import sanitizer as _san

_PREFIX0 = "rapids-host-pool-t0"
_PREFIX1 = "rapids-host-pool-t1"
_PREFIX_TASK = "rapids-task"
_LOCK = _san.lock("hostPool.registry")
_POOL: "Optional[HostTaskPool]" = None


# ---------------------------------------------------------------------------
# serving QoS tier (spark.rapids.serving.requestNice)
# ---------------------------------------------------------------------------
#
# A background-tier request runs its host work at raised OS niceness so
# latency-tier requests win CPU contention. The tier is thread-local and
# propagates to wave threads and pool workers the same way the session
# conf fingerprint and query-id binding do: captured at submit time,
# applied (and restored) around the task on the worker.

_QOS = threading.local()
_NICE_RESTORABLE: Optional[bool] = None


def qos_nice() -> int:
    """This thread's background-tier niceness (0 = latency tier)."""
    return getattr(_QOS, "nice", 0)


def run_at_nice(nice: int, fn: Callable, *args):
    """Run fn on the current thread at the given niceness (thread-local
    tier set for nested submissions), restoring both afterwards."""
    if nice <= 0:
        return fn(*args)
    prev = getattr(_QOS, "nice", 0)
    _QOS.nice = nice
    restore = _raise_nice(nice)
    try:
        return fn(*args)
    finally:
        _QOS.nice = prev
        if restore is not None:
            restore()


def _nice_restorable() -> bool:
    """One-time probe: can this process LOWER a thread's niceness back
    down (CAP_SYS_NICE / RLIMIT_NICE)? If not, never raise it on any
    thread — a shared pool worker stuck at 19 would slow every query
    that lands on it afterwards. QoS degrades to a no-op."""
    global _NICE_RESTORABLE
    if _NICE_RESTORABLE is None:
        import os
        ok = False
        if hasattr(os, "setpriority"):
            try:
                tid = threading.get_native_id()
                before = os.getpriority(os.PRIO_PROCESS, tid)
                if before < 19:
                    os.setpriority(os.PRIO_PROCESS, tid, before + 1)
                    os.setpriority(os.PRIO_PROCESS, tid, before)
                    ok = True
            except OSError:
                ok = False
        _NICE_RESTORABLE = ok
    return _NICE_RESTORABLE


def _raise_nice(nice: int):
    """Raise the current thread's niceness; returns a restore callable,
    or None when nothing was changed (already that nice, or the probe
    says restoring would fail)."""
    import os
    if not _nice_restorable():
        return None
    try:
        tid = threading.get_native_id()
        before = os.getpriority(os.PRIO_PROCESS, tid)
        if before >= nice:
            return None
        os.setpriority(os.PRIO_PROCESS, tid, min(int(nice), 19))
    except OSError:
        return None

    def restore():
        try:
            os.setpriority(os.PRIO_PROCESS, tid, before)
        except OSError:
            pass
    return restore


def run_task_wave(fn, items, max_concurrency: int = 16) -> list:
    """Run one action's top-level partition tasks (the Spark task-set
    role) and return [fn(item)] in input order.

    This is the ONE sanctioned place the engine fans partition tasks out
    to threads (TPU-L002 funnels every other call site here or to the
    shared pool). The wave owns a throwaway executor ON PURPOSE, unlike
    everything else in this module: task threads block for whole-task
    lifetimes (semaphore waits, nested actions — broadcast
    materialization collects from inside a task), so waves sharing one
    bounded executor could deadlock nested waves behind blocked outer
    tasks. Wave threads carry the `rapids-task` prefix, which `_depth()`
    maps to 0 — their submissions land on tier 0 exactly like the old
    per-call pools' did.

    Wave threads inherit the SUBMITTER's thread-bound session conf and
    attribution-suppression state: the compile cache's conf fingerprint
    and the warmup-replay suppression are thread-local, and a wave
    thread deciding them from process defaults would key one query's
    executables under two fingerprints (or leak a warmup replay's
    compile seconds into a user query's attribution)."""
    items = list(items)
    if len(items) <= 1:
        return [fn(i) for i in items]
    from spark_rapids_tpu import config as _cfg
    from spark_rapids_tpu.runtime import lifecycle as _lc
    from spark_rapids_tpu.runtime.obs import attribution as _attr
    from spark_rapids_tpu.runtime.obs import live as _live
    conf = getattr(_cfg._local, "conf", None)
    suppress = _attr.thread_suppressed()
    # the submitter's bound query id rides to the wave threads the same
    # way the conf fingerprint does: a task constructed on a wave thread
    # must attribute to the query that fanned it out
    qid = _live.current_query_id()
    # ... and so does the serving request context (distributed tracing):
    # spans a wave thread emits must land in the request's ring
    rctx = _live.current_request()
    nice = qos_nice()

    def bound(item):
        if conf is not None:
            _cfg.set_session_conf(conf)
        if suppress:
            _attr.set_thread_suppressed(True)
        if qid is not None:
            _live.bind(qid)
        if rctx is not None:
            _live.bind_request(rctx)
        try:
            # wave-start cooperative checkpoint: partitions of an
            # already-cancelled query unwind before doing any work
            _lc.check_current()
            if nice:
                return run_at_nice(nice, fn, item)
            return fn(item)
        finally:
            if rctx is not None:
                _live.bind_request(None)
            if qid is not None:
                _live.bind(None)

    with ThreadPoolExecutor(max_workers=min(len(items), max_concurrency),
                            thread_name_prefix=_PREFIX_TASK) as tp:
        return list(tp.map(bound, items))


def spawn_service_thread(target, name: str, daemon: bool = True
                         ) -> threading.Thread:
    """Sanctioned creation point for long-lived or abandonable SERVICE
    threads (the obs HTTP server's serve_forever, the healthz device
    probe). These must never ride a bounded pool worker: serve_forever
    never returns, and a wedged device probe must be abandonable without
    poisoning a pool slot. Returns the started thread."""
    t = threading.Thread(target=target, name=name, daemon=daemon)
    t.start()
    return t


class HostTaskPool:
    """Bounded shared two-tier pool with inline fallback at depth 2."""

    def __init__(self, n_threads: int):
        self.n_threads = max(1, int(n_threads))
        self._tier0 = ThreadPoolExecutor(max_workers=self.n_threads,
                                         thread_name_prefix=_PREFIX0)
        self._tier1 = ThreadPoolExecutor(max_workers=self.n_threads,
                                         thread_name_prefix=_PREFIX1)

    @staticmethod
    def _depth() -> int:
        name = threading.current_thread().name
        if name.startswith(_PREFIX1):
            return 2
        if name.startswith(_PREFIX0):
            return 1
        return 0

    def submit(self, fn: Callable, *args) -> Future:
        depth = self._depth()
        # cross-thread query correlation (OUTERMOST wrapper): pool workers
        # are shared
        # across queries, so every submission captures the SUBMITTER's
        # bound query id and re-binds it (with restore) around the work
        # — exchange materialization, scan prefetch, serde, async
        # writes and blob decode all attribute to the right in-flight
        # query. One thread-local read per submit; unbound submitters
        # skip the wrapper entirely.
        from spark_rapids_tpu.runtime.obs import live as _live
        qid = _live.current_query_id()
        if qid is not None:
            inner_fn = fn

            def fn(*a):  # noqa: F811 - bound wrapper replaces fn
                return _live.run_bound(qid, inner_fn, *a)
        # the submitter's serving request context rides the same seam
        # (distributed tracing): prefetch/serde/decode spans run on a
        # shared worker still land in the request's ring
        rctx = _live.current_request()
        if rctx is not None:
            req_fn = fn

            def fn(*a):  # noqa: F811 - request-bound wrapper replaces fn
                return _live.run_request_bound(rctx, req_fn, *a)
        # the submitter's QoS tier rides along the same way: background
        # requests keep their raised niceness on whichever worker runs
        # the task (restored after, so shared workers aren't poisoned)
        nice = qos_nice()
        if nice:
            tier_fn = fn

            def fn(*a):  # noqa: F811 - QoS wrapper replaces fn
                return run_at_nice(nice, tier_fn, *a)
        if depth == 0:
            return self._tier0.submit(fn, *args)
        if depth == 1:
            return self._tier1.submit(fn, *args)
        f: Future = Future()
        try:
            f.set_result(fn(*args))
        except BaseException as e:  # noqa: BLE001 - future carries it
            f.set_exception(e)
        return f

    def map_ordered(self, fn: Callable, items: Iterable,
                    max_concurrency: Optional[int] = None) -> Iterator:
        """Results of fn(item) in input order (pool.map analog that keeps
        the tiered-submission discipline). `max_concurrency` caps this
        CALLER's in-flight tasks below the tier size — the per-site knobs
        (shuffle writer/reader threads) still bound how much work one
        exchange admits, even though the threads are shared."""
        from collections import deque
        limit = self.n_threads if max_concurrency is None \
            else max(1, min(int(max_concurrency), self.n_threads))
        pending: "deque[Future]" = deque()
        it = iter(items)
        for item in it:
            pending.append(self.submit(fn, item))
            if len(pending) >= limit:
                break
        while pending:
            f = pending.popleft()
            try:
                pending.append(self.submit(fn, next(it)))
            except StopIteration:
                pass
            yield f.result()

    def queue_depths(self) -> dict:
        """Tasks queued (submitted, not yet picked up) per tier — the
        live backlog gauge /metrics exposes. Racy reads by design."""
        return {"tier0": self._tier0._work_queue.qsize(),
                "tier1": self._tier1._work_queue.qsize()}

    def shutdown(self) -> None:
        self._tier0.shutdown(wait=True)
        self._tier1.shutdown(wait=True)


def _pool_size(conf) -> int:
    """The tier size honors every conf that used to size its own pool:
    multiThreadedRead (scans) and the shuffle writer/reader threads."""
    from spark_rapids_tpu import config as C
    c = conf if conf is not None else C.conf()
    return max(c.get(C.MULTIFILE_READER_THREADS),
               c.get(C.SHUFFLE_WRITER_THREADS),
               c.get(C.SHUFFLE_READER_THREADS))


def get_host_pool(conf=None) -> HostTaskPool:
    """The process-wide pool, created on first use (the first caller's
    conf wins, exactly like the reference's getOrCreateThreadPool)."""
    global _POOL
    with _LOCK:
        if _POOL is None:
            _POOL = HostTaskPool(_pool_size(conf))
        return _POOL


def current_pool() -> "Optional[HostTaskPool]":
    """The pool if one exists, WITHOUT creating it (the live queue-depth
    gauges must not size a pool from a scrape thread's conf)."""
    return _POOL


def reset_host_pool() -> None:
    """Test hook: drop the shared pool so the next user re-sizes it."""
    global _POOL
    with _LOCK:
        pool, _POOL = _POOL, None
    if pool is not None:
        pool.shutdown()
