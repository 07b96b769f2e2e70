"""Device admission semaphore (reference GpuSemaphore.scala /
PrioritySemaphore.scala).

Limits the number of tasks concurrently touching the device to
`spark.rapids.sql.concurrentTpuTasks`. Priority follows the reference's
design: tasks already holding device data (re-acquisition) outrank fresh
tasks, reducing memory pressure; ties break by task id (older first).

Wakeups are DIRECT HANDOFF, not polling: a release (or an enqueue while
permits are free) grants permits to eligible head waiters under the lock
and signals exactly those waiters' events — a waiter blocks on its event
with no timeout, so the measured semaphoreWaitTime is real contention,
never a 50 ms poll quantum (the reference PrioritySemaphore's
condition-signal discipline).

Interruptible acquire (runtime/lifecycle.py): a queued waiter's event is
registered with the acquiring query's cancel token, so cancel() doubles
as the wakeup. A waiter that leaves abnormally — cancelled, or killed by
an exception on the wait path (the `semaphore.wait` fault site injects
exactly this) — removes its heap entry and re-runs the handoff, so its
reserved (or reservable) permits can never strand. Before this rework a
waiter dying while queued left its entry at the heap head forever,
blocking `_grant_head_locked` for every later waiter.
"""
from __future__ import annotations

import heapq
import threading
import time
from typing import Dict, Optional

from spark_rapids_tpu.analysis import sanitizer as _san
from spark_rapids_tpu.runtime import faults as _faults
from spark_rapids_tpu.runtime import trace


class PrioritySemaphore:
    def __init__(self, permits: int):
        self._permits = permits
        self._available = permits
        self._lock = _san.lock("semaphore.priority")
        self._waiters = []  # heap of [-priority, seq, n, event, granted]
        self._seq = 0

    def _grant_head_locked(self) -> None:
        """Direct handoff (caller holds the lock): pop head waiters while
        their permits fit, reserving the permits FOR them before setting
        their event — the woken thread never re-contends."""
        while self._waiters and self._available >= self._waiters[0][2]:
            entry = heapq.heappop(self._waiters)
            self._available -= entry[2]
            entry[4] = True  # reserved: an abandoning waiter must refund
            entry[3].set()

    def _abandon_locked_entry(self, entry) -> None:
        """A waiter is leaving abnormally (cancelled, or its wait path
        raised): refund permits already reserved for it, or remove its
        still-queued heap entry, then re-run the handoff — an abandoned
        head entry must never block later waiters."""
        with self._lock:
            if entry[4]:
                self._available += entry[2]
            else:
                try:
                    self._waiters.remove(entry)
                    heapq.heapify(self._waiters)
                except ValueError:
                    pass
            self._grant_head_locked()

    def acquire(self, n: int = 1, priority: int = 0,
                wait_metric=None, cancel_token=None) -> None:
        """Block until n permits are reserved for this caller. When
        `cancel_token` (runtime/lifecycle.CancelToken) is passed, the
        waiter event doubles as the cancel wakeup and a fired token
        raises QueryCancelledError with the entry cleaned up."""
        t0 = time.perf_counter_ns()
        with self._lock:
            if self._available >= n and not self._waiters:
                self._available -= n
                return
            ev = threading.Event()
            self._seq += 1
            entry = [-priority, self._seq, n, ev, False]
            heapq.heappush(self._waiters, entry)
            # a higher-priority arrival may jump an ineligible queue, and
            # permits freed while nobody dispatched must not strand: try
            # the handoff immediately (possibly granting ourselves)
            self._grant_head_locked()
        if cancel_token is not None:
            cancel_token.add_waiter(ev)
        try:
            # delay/wedge/ioerror a contended acquire; an injected error
            # here exercises the abandoned-entry cleanup below
            _faults.site("semaphore.wait")
            ev.wait()  # set once our permits are reserved, or on cancel
            if cancel_token is not None and cancel_token.cancelled:
                from spark_rapids_tpu.runtime.lifecycle import (
                    QueryCancelledError,
                )
                raise QueryCancelledError(cancel_token.query_id,
                                          cancel_token.reason)
        except BaseException:
            self._abandon_locked_entry(entry)
            raise
        finally:
            if cancel_token is not None:
                cancel_token.remove_waiter(ev)
        if wait_metric is not None:
            wait_metric.add(time.perf_counter_ns() - t0)

    def release(self, n: int = 1) -> None:
        with self._lock:
            self._available += n
            self._grant_head_locked()

    @property
    def available(self) -> int:
        return self._available

    @property
    def waiting(self) -> int:
        """Parked waiters (healthz saturation signal; racy read is fine)."""
        return len(self._waiters)


class TpuSemaphore:
    """Task-aware wrapper: re-entrant per task, auto-released on task end
    (reference GpuSemaphore.acquireIfNecessary / completion hook)."""

    def __init__(self, permits: int):
        self.permits = permits
        self._sem = PrioritySemaphore(permits)
        #: task_id -> perf_counter_ns at acquisition (truthy while held;
        #: the timestamp feeds the semaphoreHoldTime task accumulator)
        self._held: Dict[int, int] = {}
        self._lock = _san.lock("semaphore.held")

    def acquire_if_necessary(self, task_ctx) -> None:
        tid = task_ctx.task_id
        with self._lock:
            if self._held.get(tid):
                return
        prio = 1 if task_ctx.holds_device_data else 0
        traced = trace.active() is not None
        t0 = time.perf_counter_ns() if traced else 0
        # the acquiring query's cancel token (if any) rides into the
        # waiter so a cancelled query parked on the semaphore wakes and
        # unwinds instead of holding its queue position forever
        from spark_rapids_tpu.runtime import lifecycle as _lc
        self._sem.acquire(1, priority=prio,
                          wait_metric=task_ctx.metric("semaphoreWaitTime"),
                          cancel_token=_lc.current_token())
        if traced:  # args gated: no dict/clock work when tracing is off
            trace.instant("semaphoreAcquire", cat="semaphore", args={
                "task_id": tid, "priority": prio,
                "wait_ns": time.perf_counter_ns() - t0})
        with self._lock:
            self._held[tid] = time.perf_counter_ns()
        task_ctx.on_completion(lambda: self.release(task_ctx))

    def release(self, task_ctx) -> None:
        tid = task_ctx.task_id
        with self._lock:
            t_acq = self._held.pop(tid, 0)
            if not t_acq:
                return
        # hold-time accumulator (permit occupancy — the saturation-side
        # complement of semaphoreWaitTime; folded into the live registry
        # at task completion)
        task_ctx.metric("semaphoreHoldTime").add(
            time.perf_counter_ns() - t_acq)
        self._sem.release(1)

    @property
    def available(self) -> int:
        return self._sem.available

    @property
    def waiting(self) -> int:
        return self._sem.waiting


_global: Optional[TpuSemaphore] = None
_glock = _san.lock("semaphore.global")


def get_semaphore(conf=None) -> TpuSemaphore:
    global _global
    with _glock:
        if _global is None:
            from spark_rapids_tpu import config as C
            c = conf
            if c is None:
                from spark_rapids_tpu.config import conf as get_conf
                c = get_conf()
            _global = TpuSemaphore(c.get(C.CONCURRENT_TPU_TASKS))
        return _global


def peek_semaphore() -> Optional[TpuSemaphore]:
    """The process semaphore WITHOUT creating one (healthz / the live
    gauges must not mint a semaphore sized by whatever conf happens to
    be active on the scrape thread)."""
    return _global


def reset_semaphore() -> None:
    global _global
    with _glock:
        _global = None
