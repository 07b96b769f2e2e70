"""Task context (the Spark TaskContext analog the exec layer sees).

Reference parity: ScalableTaskCompletion (cheap completion callbacks),
GpuTaskMetrics per-task accumulators, and the per-task thread association
RmmSpark keeps for the retry framework.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

from spark_rapids_tpu.analysis import sanitizer as _san
from spark_rapids_tpu.runtime.metrics import GpuMetric


class TaskContext:
    _counter = 0
    _counter_lock = _san.lock("task.counter")
    _local = threading.local()

    def __init__(self, partition_id: int = 0, stage_id: int = 0):
        import time
        with TaskContext._counter_lock:
            TaskContext._counter += 1
            self.task_id = TaskContext._counter
        self.partition_id = partition_id
        self.stage_id = stage_id
        # cross-thread query correlation: the constructing thread's
        # bound query id (runtime/obs/live.py) — task waves bind it
        # before constructing contexts, so every task knows which
        # in-flight query it works for (None outside any query)
        from spark_rapids_tpu.runtime.obs import live as _live
        self.query_id = _live.current_query_id()
        self.holds_device_data = False
        #: where this task's source uploads its batches; None is the
        #: default device. A cache placed over a mesh sets it to the
        #: partition's own device (CachedScanExec._materialize)
        self.device = None
        self.start_ns = time.perf_counter_ns()
        self._metrics: Dict[str, GpuMetric] = {}
        self._completion: List[Callable[[], None]] = []
        self._failed = False
        self._cancelled = False

    def metric(self, name: str) -> GpuMetric:
        if name not in self._metrics:
            self._metrics[name] = GpuMetric(name)
        return self._metrics[name]

    def on_completion(self, fn: Callable[[], None]) -> None:
        self._completion.append(fn)

    def complete(self, failed: bool = False,
                 cancelled: bool = False) -> None:
        """Run completion callbacks and roll accumulators up. `cancelled`
        marks a task unwound by its query's cancel token (or an early
        sibling close): it did not fail, but it must not count as a
        clean completion either — obs folds it into
        rapids_tasks_cancelled_total."""
        self._failed = failed
        self._cancelled = cancelled
        for fn in reversed(self._completion):
            try:
                fn()
            except Exception:  # noqa: BLE001 - remaining callbacks
                # (semaphore release!) must still run; but a silently
                # swallowed failure hid real bugs — surface it
                import logging
                logging.getLogger("spark_rapids_tpu").warning(
                    "task %d completion callback failed", self.task_id,
                    exc_info=True)
        self._completion.clear()
        # roll the task accumulators into the active query trace's event
        # log AFTER the completion callbacks (the semaphore release hook
        # runs first, so its final wait total is included), then fold
        # them into the live observability registry and the per-query
        # attribution aggregate — ONE write batch per task, the only
        # obs cost on the execution path
        from spark_rapids_tpu.runtime import obs, trace
        from spark_rapids_tpu.runtime.obs import attribution
        trace.on_task_complete(self)
        obs.on_task_complete(self)
        attribution.fold_task(self._metrics)

    # -- thread association ------------------------------------------------
    @staticmethod
    def peek() -> "Optional[TaskContext]":
        """The thread's bound context WITHOUT creating one (trace track
        resolution must not mint phantom tasks on driver/pool threads)."""
        return getattr(TaskContext._local, "ctx", None)

    @staticmethod
    def get() -> "TaskContext":
        ctx = getattr(TaskContext._local, "ctx", None)
        if ctx is None:
            ctx = TaskContext()
            TaskContext._local.ctx = ctx
        return ctx

    @staticmethod
    def set_current(ctx: "TaskContext") -> None:
        TaskContext._local.ctx = ctx

    @staticmethod
    def clear() -> None:
        if hasattr(TaskContext._local, "ctx"):
            del TaskContext._local.ctx

    def __enter__(self):
        TaskContext.set_current(self)
        return self

    def __exit__(self, et, ev, tb):
        cancelled = False
        if et is not None:
            from spark_rapids_tpu.runtime.lifecycle import (
                QueryCancelledError,
            )
            cancelled = issubclass(et, QueryCancelledError)
        self.complete(failed=et is not None and not cancelled,
                      cancelled=cancelled)
        TaskContext.clear()
        return False
