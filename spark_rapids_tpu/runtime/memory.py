"""Device & memory runtime: HBM budget, three-tier spill, spillable batches.

Reference parity: SURVEY.md §2.3 —
- spill/SpillFramework.scala (device -> host -> disk stores with handles,
  spill-on-alloc-failure cascade, per-handle disk files),
- SpillableColumnarBatch.scala (the currency operators hold between steps),
- GpuDeviceManager.scala (pool sizing / budget),
- DeviceMemoryEventHandler.scala (alloc-failed -> drain spill stores).

TPU-first divergences:
- XLA owns the physical HBM allocator and exposes no alloc-failed
  callback, so the budget is COOPERATIVE: operators register their
  held batches; `reserve()` is called before materializing a large batch
  and synchronously drains the spill stores (device->host->disk) until
  the reservation fits. A real XLA RESOURCE_EXHAUSTED is also translated
  into a drain + TpuRetryOOM (runtime/retry.py) as a second line of
  defense.
- Spilling a batch is `jax.device_get` of its planes (host numpy tier)
  and `np.save` per plane for the disk tier; rematerialization is a
  single `jax.device_put` per plane. No pinned-buffer machinery: PJRT
  stages transfers itself.
"""
from __future__ import annotations

import os
import tempfile
import threading
import uuid
from typing import Dict, List, Optional

import numpy as np

import jax

from spark_rapids_tpu.analysis import sanitizer as _san
from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar.batch import ColumnarBatch

DEVICE, HOST, DISK = "device", "host", "disk"


def _record_spill(kind: str, nbytes: int, dur_ns: int,
                  handle_id: str) -> None:
    """Spill observability: the spilling TASK's accumulators (GpuTaskMetrics
    spillToHostTimeNs analog — the spill runs on the thread whose
    reservation forced it) plus a trace instant event."""
    from spark_rapids_tpu.runtime import trace
    from spark_rapids_tpu.runtime.task import TaskContext
    ctx = TaskContext.peek()
    if ctx is not None:
        ctx.metric(kind + "Bytes").add(nbytes)
        ctx.metric(kind + "Time").add(dur_ns)
    trace.instant(kind, cat="memory", args={
        "bytes": nbytes, "dur_ns": dur_ns, "handle": handle_id[:8]})


def _committed_device(batch: ColumnarBatch):
    """The one device `batch`'s planes are committed to, else None."""
    for leaf in jax.tree_util.tree_leaves(batch.columns):
        if isinstance(leaf, jax.Array):
            devs = leaf.devices()
            return next(iter(devs)) if leaf.committed and len(devs) == 1 \
                else None
    return None


class SpillableHandle:
    """One registered batch. State machine: device -> host -> disk,
    rematerialized back to device on demand (`get`). Priority: larger
    batches spill first (reference SpillFramework spills biggest-first to
    minimize handle churn)."""

    def __init__(self, framework: "SpillFramework", batch: ColumnarBatch):
        self.fw = framework
        self.handle_id = uuid.uuid4().hex
        self.size = batch.device_memory_size()
        # per-query ledger key (spark.rapids.query.deviceBudgetBytes):
        # the registering thread's bound query id, so quota enforcement
        # can pick victims from — and charge — the owning query only
        from spark_rapids_tpu.runtime.obs import live as _live
        self.query_id = _live.current_query_id()
        self._lock = _san.lock("memory.handle")
        self._tier = DEVICE
        self._device: Optional[ColumnarBatch] = batch
        #: the chip a shard of a mesh-placed cache is committed to: it
        #: comes back there, not to the default device
        self._home = _committed_device(batch)
        self._host = None  # leaves (host numpy)
        self._disk_paths: Optional[List[str]] = None
        self._treedef = None
        self._closed = False
        self._pinned = False  # mid-rematerialization: not a spill victim

    @property
    def tier(self) -> str:
        return self._tier

    def spillable(self) -> bool:
        return self._tier == DEVICE and not self._closed and not self._pinned

    # -- transitions -------------------------------------------------------

    def spill_to_host(self) -> int:
        """device -> host. Returns bytes freed from the device tier."""
        import time as _time
        t0 = _time.perf_counter_ns()
        with self._lock:
            if self._tier != DEVICE or self._closed or self._pinned:
                return 0
            leaves, treedef = jax.tree_util.tree_flatten(self._device)
            self._host = jax.device_get(leaves)
            self._treedef = treedef
            self._device = None
            self._tier = HOST
        _record_spill("spillToHost", self.size,
                      _time.perf_counter_ns() - t0, self.handle_id)
        return self.size

    def spill_to_disk(self) -> int:
        """host -> disk. Returns bytes freed from the host tier."""
        import time as _time
        from spark_rapids_tpu.runtime import faults as _faults
        # fault site OUTSIDE the handle lock: an injected disk error (or
        # wedge-sleep) must behave like np.save failing, not extend the
        # critical section
        _faults.site("spill.disk")
        t0 = _time.perf_counter_ns()
        # tpulint: disable=TPU-L001 np.save must be atomic with the HOST->DISK tier transition; the lock is per-handle and a handle spills at most once per tier, so no hot path ever waits on this write
        with self._lock:
            if self._tier != HOST or self._closed or self._pinned:
                return 0
            paths = []
            for i, leaf in enumerate(self._host):
                path = os.path.join(self.fw.spill_dir,
                                    f"{self.handle_id}_{i}.npy")
                np.save(path, np.asarray(leaf), allow_pickle=False)
                paths.append(path)
            self._disk_paths = paths
            self._host = None
            self._tier = DISK
        _record_spill("spillToDisk", self.size,
                      _time.perf_counter_ns() - t0, self.handle_id)
        return self.size

    def get(self) -> ColumnarBatch:
        """Rematerialize on device. NEVER calls into the framework while
        holding the handle lock (reserve may pick other handles — possibly
        themselves rematerializing — as victims; holding the lock across
        that is an ABBA deadlock). The handle is pinned for the duration so
        concurrent spills skip it."""
        # tpulint: disable=TPU-L001 np.load/unlink must be atomic with the DISK->HOST tier transition (a concurrent spill observing DISK mid-load would double-free the paths); per-handle lock, rematerialization path only
        with self._lock:
            if self._closed:
                raise ValueError("handle closed")
            if self._tier == DEVICE:
                return self._device
            self._pinned = True
            if self._tier == DISK:
                self._host = [np.load(p) for p in self._disk_paths]
                for p in self._disk_paths:
                    try:
                        os.unlink(p)
                    except OSError:
                        pass
                self._disk_paths = None
                self._tier = HOST
        try:
            # best-effort: an over-budget handle was admitted once and must
            # remain rematerializable (drain everything else, then load)
            self.fw.reserve(self.size, exclude=self, best_effort=True)
            with self._lock:
                if self._tier == HOST:
                    leaves = [jax.device_put(x, self._home)
                              if isinstance(x, np.ndarray)
                              else x for x in self._host]
                    batch = jax.tree_util.tree_unflatten(self._treedef, leaves)
                    self._device = ColumnarBatch(
                        batch.columns, int(batch.num_rows), batch.row_mask)
                    self._host = None
                    self._tier = DEVICE
                return self._device
        finally:
            with self._lock:
                self._pinned = False

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            paths, self._disk_paths = self._disk_paths, None
            self._device = None
            self._host = None
        # disk cleanup OUTSIDE the handle lock (TPU-L001): once _closed
        # is set no transition can race, and unlink latency must not
        # block spill-victim scans probing this handle
        for p in paths or ():
            try:
                os.unlink(p)
            except OSError:
                pass
        self.fw.unregister(self)


class SpillFramework:
    """Cooperative HBM budget + the spill cascade."""

    def __init__(self, device_budget_bytes: int, host_budget_bytes: int,
                 spill_dir: Optional[str] = None):
        self.device_budget = device_budget_bytes
        self.host_budget = host_budget_bytes
        self.spill_dir = spill_dir or tempfile.mkdtemp(prefix="srt_spill_")
        self._lock = _san.lock("memory.framework")
        self._handles: Dict[str, SpillableHandle] = {}
        self.metrics = {"spill_to_host_bytes": 0, "spill_to_disk_bytes": 0,
                        "spill_count": 0, "oom_drains": 0}
        #: leak audit (reference RapidsBufferCatalog leak tracking /
        #: -Dai.rapids.refcount.debug): when enabled, registrations
        #: record their creation stack so unreleased handles are
        #: attributable, and leak_report() names them
        self.leak_audit = False
        self._origins: Dict[str, str] = {}

    # -- registration ------------------------------------------------------

    def register(self, batch: ColumnarBatch) -> SpillableHandle:
        """Register a device-resident batch. Enforces the budget by
        spilling OTHER handles; a single batch larger than the whole
        budget is admitted anyway (it already exists on device — the
        cooperative budget cannot un-allocate it) after draining."""
        h = SpillableHandle(self, batch)
        from spark_rapids_tpu.runtime.retry import TpuRetryOOM
        # per-query quota FIRST, and its breach propagates (unlike the
        # global budget below): the over-quota query self-spills, and
        # when nothing of its own is left to spill the typed quota OOM
        # feeds ITS retry/split cascade instead of evicting neighbors
        self._enforce_query_budget(h.size)
        try:
            self.reserve(h.size)
        except TpuRetryOOM:
            self.drain_all()
        with self._lock:
            self._handles[h.handle_id] = h
            if self.leak_audit:
                import traceback
                self._origins[h.handle_id] = "".join(
                    traceback.format_stack(limit=8)[:-1])
        from spark_rapids_tpu.runtime import trace
        if trace.active() is not None:
            from spark_rapids_tpu.runtime.task import TaskContext
            ctx = TaskContext.peek()
            if ctx is not None:
                # high-water mark of device bytes registered while this
                # task ran (GpuTaskMetrics maxDeviceMemoryBytes analog).
                # Gated: device_bytes_held() sums live handles under the
                # framework lock — only worth paying when a trace is live
                ctx.metric("maxDeviceBytesHeld").set_max(
                    self.device_bytes_held())
        return h

    def unregister(self, h: SpillableHandle) -> None:
        with self._lock:
            self._handles.pop(h.handle_id, None)
            self._origins.pop(h.handle_id, None)

    # -- leak detection ----------------------------------------------------

    def leak_report(self, expected_live: int = 0) -> list:
        """Unreleased handles beyond `expected_live` (cached relations
        legitimately stay registered for their lifetime). Returns
        [(handle_id, bytes, origin_stack_or_None)]; callers (tests,
        session close, the aux-subsystem audit) decide whether to raise.
        The reference's RapidsBufferCatalog performs the same end-of-life
        sweep with refcount debug stacks."""
        with self._lock:
            if len(self._handles) <= expected_live:
                return []
            # dict order = registration order: the OLDEST registrations
            # are the legitimately persistent ones (cached relations
            # register before per-query handles)
            items = list(self._handles.items())[expected_live:]
            return [(hid, h.size, self._origins.get(hid))
                    for hid, h in items]

    def assert_no_leaks(self, expected_live: int = 0) -> None:
        leaks = self.leak_report(expected_live)
        if leaks:
            lines = [f"  {hid}: {size}B" + (f"\n{org}" if org else "")
                     for hid, size, org in leaks]
            raise AssertionError(
                f"{len(leaks)} spillable handle(s) not released:\n"
                + "\n".join(lines))

    # -- accounting --------------------------------------------------------

    def device_bytes_held(self, query_id=None) -> int:
        """Registered device-tier bytes — process-wide, or one query's
        ledger slice when `query_id` is passed (the per-query quota
        read)."""
        with self._lock:
            return sum(h.size for h in self._handles.values()
                       if h.tier == DEVICE
                       and (query_id is None or h.query_id == query_id))

    def host_bytes_held(self) -> int:
        with self._lock:
            return sum(h.size for h in self._handles.values()
                       if h.tier == HOST)

    def _enforce_query_budget(self, nbytes: int,
                              exclude: Optional[SpillableHandle] = None
                              ) -> None:
        """Per-query device quota (spark.rapids.query.deviceBudgetBytes,
        carried on the query's cancel token): when the CURRENT query's
        ledger plus this reservation exceeds its own budget, spill the
        query's OWN device handles (largest first). When nothing of its
        own remains spillable, raise the typed TpuQueryQuotaOOM — the
        retry framework then drains only this query's handles and
        splits/replays ITS work, leaving neighbor queries' batches
        resident (the isolation primitive concurrent serving needs)."""
        from spark_rapids_tpu.runtime import lifecycle as _lc
        tok = _lc.current_token()
        if tok is None or tok.device_budget <= 0:
            return
        budget, qid = tok.device_budget, tok.query_id
        from spark_rapids_tpu.runtime.retry import TpuQueryQuotaOOM
        while self.device_bytes_held(query_id=qid) + nbytes > budget:
            victim = self._pick_victim(exclude, query_id=qid)
            if victim is None:
                raise TpuQueryQuotaOOM(
                    f"query {qid} holds "
                    f"{self.device_bytes_held(query_id=qid)}B of device "
                    f"batches and needs {nbytes}B more, over its "
                    f"deviceBudgetBytes={budget} quota with nothing of "
                    f"its own left to spill", query_id=qid)
            freed = victim.spill_to_host()
            if freed:
                self.metrics["spill_to_host_bytes"] += freed
                self.metrics["spill_count"] += 1
                self._enforce_host_budget()

    def drain_query(self, query_id) -> int:
        """Spill every device handle the given query holds (the quota
        twin of drain_all: the retry framework calls this on a
        TpuQueryQuotaOOM so an over-quota query frees only its OWN
        memory before re-attempting)."""
        freed = 0
        while True:
            victim = self._pick_victim(None, query_id=query_id)
            if victim is None:
                return freed
            got = victim.spill_to_host()
            freed += got
            if got:
                self.metrics["spill_to_host_bytes"] += got
                self.metrics["spill_count"] += 1
                self._enforce_host_budget()

    def reserve(self, nbytes: int, exclude: Optional[SpillableHandle] = None,
                best_effort: bool = False) -> None:
        """Make room for an nbytes device materialization, spilling
        registered device handles (largest first) as needed. Raises
        TpuRetryOOM when even a full drain cannot fit the reservation —
        the retry framework then splits the work. best_effort=True drains
        what it can and returns instead of raising (used to rematerialize
        handles that were admitted over-budget). The per-query quota is
        enforced by register() (its breach must PROPAGATE, unlike the
        global-budget swallow there), not here."""
        from spark_rapids_tpu.runtime.retry import TpuRetryOOM
        if nbytes > self.device_budget:
            if best_effort:
                self.drain_all()
                return
            raise TpuRetryOOM(
                f"reservation {nbytes}B exceeds device budget "
                f"{self.device_budget}B")
        while self.device_bytes_held() + nbytes > self.device_budget:
            victim = self._pick_victim(exclude)
            if victim is None:
                if best_effort:
                    return
                raise TpuRetryOOM(
                    f"cannot reserve {nbytes}B: "
                    f"{self.device_bytes_held()}B held, nothing spillable")
            freed = victim.spill_to_host()
            if freed:
                self.metrics["spill_to_host_bytes"] += freed
                self.metrics["spill_count"] += 1
                self._enforce_host_budget()
            elif best_effort:
                return

    def _pick_victim(self, exclude,
                     query_id=None) -> Optional[SpillableHandle]:
        with self._lock:
            cands = [h for h in self._handles.values()
                     if h.spillable() and h is not exclude
                     and (query_id is None or h.query_id == query_id)]
        if not cands:
            return None
        return max(cands, key=lambda h: h.size)

    def _enforce_host_budget(self) -> None:
        while self.host_bytes_held() > self.host_budget:
            with self._lock:
                cands = [h for h in self._handles.values() if h.tier == HOST]
            if not cands:
                return
            victim = max(cands, key=lambda h: h.size)
            freed = victim.spill_to_disk()
            if freed:
                self.metrics["spill_to_disk_bytes"] += freed
            else:
                return

    def drain_all(self) -> int:
        """Emergency drain (the DeviceMemoryEventHandler analog, called
        when XLA itself reports RESOURCE_EXHAUSTED)."""
        self.metrics["oom_drains"] += 1
        freed = 0
        while True:
            victim = self._pick_victim(None)
            if victim is None:
                return freed
            got = victim.spill_to_host()
            freed += got
            if got:
                self._enforce_host_budget()


class SpillableColumnarBatch:
    """Operator currency: hold this between pipeline steps instead of a raw
    batch so OTHER tasks' reservations can evict it (reference
    SpillableColumnarBatch.scala)."""

    def __init__(self, batch: ColumnarBatch, fw: Optional["SpillFramework"] = None):
        self.fw = fw or get_spill_framework()
        self.handle = self.fw.register(batch)

    def get_batch(self) -> ColumnarBatch:
        return self.handle.get()

    @property
    def size(self) -> int:
        return self.handle.size

    def close(self) -> None:
        self.handle.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


_GLOBAL: Optional[SpillFramework] = None
_GLOBAL_LOCK = _san.lock("memory.global")


def get_spill_framework(conf=None) -> SpillFramework:
    """Process-wide framework. When a conf is passed (each session collect
    does), the budgets are re-synced so a later session's settings are not
    silently ignored."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        existing = _GLOBAL
    if conf is None and existing is not None:
        return existing
    if conf is None:
        from spark_rapids_tpu.config import conf as _active
        conf = _active()
    budget = _device_budget_from(conf)
    # directory creation OUTSIDE the global lock (TPU-L001): the spill
    # dir is only touched by disk spills, long after this returns
    sd = conf.get(C.SPILL_DIR)
    if sd:
        os.makedirs(sd, exist_ok=True)
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = SpillFramework(
                budget,
                conf.get(C.HOST_SPILL_LIMIT),
                spill_dir=sd or None)
        else:
            _GLOBAL.device_budget = budget
            _GLOBAL.host_budget = conf.get(C.HOST_SPILL_LIMIT)
        return _GLOBAL


def _device_budget_from(conf) -> int:
    """HBM budget = min(budgetBytes, allocFraction x detected chip HBM),
    times the chips of the session's mesh. The fraction keeps headroom
    for XLA scratch on chips whose HBM the runtime can report;
    budgetBytes remains the explicit ceiling a chip."""
    budget = conf.get(C.DEVICE_MEMORY_BUDGET)
    frac = conf.get(C.DEVICE_MEMORY_FRACTION)
    import jax
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    total = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if total:
        budget = min(budget, int(total * frac))
        # the ledger is process-wide: a mesh of n chips holds n budgets
        from spark_rapids_tpu.parallel.mesh import placement_devices
        budget *= max(1, len(placement_devices(conf)))
    elif dev.platform == "tpu":
        # the CPU simulator reports no HBM; a chip that cannot say how
        # much it has must not silently run under the constant
        raise RuntimeError(
            f"{dev.device_kind}: memory_stats() reports no HBM limit "
            f"({sorted(stats)}); cannot size the device memory budget")
    return budget


def peek_spill_framework() -> Optional[SpillFramework]:
    """The process framework WITHOUT creating (or re-syncing) one — the
    /healthz spill-pressure read and the live gauges must observe, never
    instantiate with a scrape thread's conf."""
    return _GLOBAL


def reset_spill_framework() -> None:
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = None
