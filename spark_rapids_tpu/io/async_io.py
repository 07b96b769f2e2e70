"""Async write throttling.

Reference parity: io/async/{ThrottlingExecutor,TrafficController}.scala —
writes run on a background pool, but an executor-wide controller caps the
bytes in flight so a burst of tasks cannot exhaust host memory buffering
output files (TrafficController initialized in Plugin.scala:558).
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Optional

#: bounded wait slice while blocked on admission: each wakeup re-checks
#: the caller's query cancel token (runtime/lifecycle.py), so a
#: cancelled query's writer unwinds instead of waiting out other
#: queries' releases
_CANCEL_SLICE_S = 0.25


class TrafficController:
    """Blocks producers while more than max_in_flight_bytes of writes are
    buffered/unfinished.

    `stall_warn_s` (None disables) arms a diagnostic: a producer that has
    waited that long without admission fires ONE warning — log line,
    `asyncWriteStalled` trace instant, `rapids_async_write_stalls_total`
    obs counter — then keeps waiting. A writer that never completes
    (wedged filesystem, lost release) previously blocked acquire()
    forever with no signal anywhere; admission semantics are unchanged."""

    def __init__(self, max_in_flight_bytes: int,
                 stall_warn_s: Optional[float] = None):
        self.limit = max_in_flight_bytes
        self.stall_warn_s = stall_warn_s
        from spark_rapids_tpu.analysis import sanitizer as _san
        self._inflight = 0
        self._cv = _san.condition("asyncWrite.controller")

    def _warn_stalled(self, waited_s: float, nbytes: int,
                      inflight: int) -> None:
        """Called WITHOUT self._cv held (`inflight` is the caller's
        snapshot): the diagnostic does logging/trace/obs I/O, and a
        blocked log handler must never hold up writers' release()."""
        import logging

        from spark_rapids_tpu.runtime import obs, trace
        logging.getLogger("spark_rapids_tpu").warning(
            "async write throttle stalled: waited %.1fs for %d bytes "
            "(%d in flight, limit %d) — a writer may be wedged",
            waited_s, nbytes, inflight, self.limit)
        trace.instant("asyncWriteStalled", cat="io", args={
            "waited_s": round(waited_s, 3), "bytes": nbytes,
            "in_flight": inflight, "limit": self.limit},
            level=trace.ESSENTIAL)
        st = obs.state()
        if st is not None:
            try:
                st.registry.counter(
                    "rapids_async_write_stalls_total",
                    "Async-write throttle waits that exceeded the stall "
                    "warning threshold").inc()
            except Exception:  # noqa: BLE001 - diagnostics never fail IO
                pass

    def acquire(self, nbytes: int) -> None:
        import time

        t0 = time.perf_counter_ns()
        warned = False
        with self._cv:
            while self._inflight > 0 and self._inflight + nbytes > self.limit:
                if self.stall_warn_s is not None and not warned:
                    waited = (time.perf_counter_ns() - t0) / 1e9
                    if waited >= self.stall_warn_s:
                        warned = True
                        inflight = self._inflight
                        # warn with the lock DROPPED: release() must
                        # stay reachable while the diagnostic does I/O
                        self._cv.release()
                        try:
                            self._warn_stalled(waited, nbytes, inflight)
                        finally:
                            self._cv.acquire()
                        continue  # re-check admission: it may have freed
                    # timed wait ONLY until the warning threshold — once
                    # fired (or when disabled), the wait drops to the
                    # bounded cancellation slice below
                    self._cv.wait(timeout=min(
                        self.stall_warn_s - waited, _CANCEL_SLICE_S))
                else:
                    # cancellation-aware bounded slices (TPU-L012): a
                    # cancelled query's writer parked on admission that
                    # OTHER queries' releases control must wake and
                    # unwind, not wait out their drain. Only the blocked
                    # path pays the wakeups; steady state never enters
                    # this loop.
                    self._cv.wait(timeout=_CANCEL_SLICE_S)
                from spark_rapids_tpu.runtime import lifecycle as _lc
                _lc.check_current()
            self._inflight += nbytes

    def release(self, nbytes: int) -> None:
        with self._cv:
            self._inflight -= nbytes
            self._cv.notify_all()

    @property
    def in_flight(self) -> int:
        with self._cv:
            return self._inflight


class ThrottlingExecutor:
    """Thread pool + TrafficController: submit(task_bytes, fn) blocks until
    the controller admits the bytes; completion releases them.

    Pass `pool` (anything with submit(fn) -> Future, e.g. the process-wide
    host pool) to run tasks on a SHARED executor instead of owning one —
    shutdown() then leaves it alive. Per-writer throwaway executors are
    exactly what runtime/host_pool.py exists to prevent. `max_threads`
    bounds THIS writer's concurrency either way: an owned pool sizes its
    workers by it; on a shared pool submit() blocks on a slot semaphore
    (same admission semantics as the byte controller), so the writer
    cannot fan out wider than its conf across the pool's workers."""

    def __init__(self, max_threads: int, controller: TrafficController,
                 pool=None):
        self._owned = pool is None
        # tpulint: disable=TPU-L002 standalone-writer fallback only: the engine always passes pool= (the shared host pool); an owned executor here serves direct ThrottlingExecutor users (tests, tools) with shutdown() semantics the shared pool must not have
        self.pool = ThreadPoolExecutor(max_workers=max_threads) \
            if pool is None else pool
        self.controller = controller
        self._slots = None if pool is None \
            else threading.BoundedSemaphore(max_threads)

    def submit(self, nbytes: int, fn: Callable, *args) -> Future:
        self.controller.acquire(nbytes)
        if self._slots is not None:
            self._slots.acquire()

        def run():
            try:
                return fn(*args)
            finally:
                if self._slots is not None:
                    self._slots.release()
                self.controller.release(nbytes)

        return self.pool.submit(run)

    def shutdown(self, wait: bool = True) -> None:
        if self._owned:
            self.pool.shutdown(wait=wait)
