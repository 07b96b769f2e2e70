"""Per-query wall-time attribution into named phase buckets.

Where did the wall-clock of ONE query go? The engine already measures
everything it does — per-exec GpuMetric timers, per-task accumulators
(semaphore wait, retry block, spill time), and the fuse-cache compile
cost — but nothing folded those measurements back against the query's
wall time. This module does exactly that fold: at query end the
session's metric snapshot plus the per-query direct-record aggregate
decompose into the ``BUCKETS`` roster below, normalized so the buckets
ALWAYS sum to the measured wall time (the <1% reconciliation bar of
tests/test_flight.py is exact by construction; what the test actually
guards is the accounting plumbing).

Consumers: ``df.explain(mode="analyze")`` prints the breakdown,
history records carry it (rendered as a bar by tools/history_server.py),
``tools/nds_probe.py`` adds per-query attribution columns to the
scorecard, ``/metrics`` exports ``rapids_query_seconds_bucket{phase=…}``
and the SLO detector's ``/healthz`` summary quotes the top buckets.

Concurrency semantics: per-task times are SUMMED across concurrent
tasks, so the measured total can exceed wall time (16 tasks each waiting
1s on the semaphore during a 2s query measure 16s of wait). When that
happens every bucket is scaled by wall/measured — the reported numbers
are then *critical-path shares*, with the raw sum preserved in
``measured_seconds`` and the ratio in ``concurrency_factor``. When the
total is under wall, the remainder lands in ``other`` (driver-side
planning, result assembly, untimed glue).

The roster is enforced the way fault sites (TPU-L008) and metric names
(TPU-L007) are: tpulint TPU-L009 pins every ``attribution.record("…")``
literal to ``BUCKETS`` and requires every bucket in the generated
docs/metrics.md.

Process-wide current-query aggregate (the tracer-singleton pattern, same
known limit: two top-level queries collected concurrently share the
aggregate, so their direct-recorded buckets can interleave).
"""
from __future__ import annotations

from typing import Dict, List, Optional

from spark_rapids_tpu.analysis import sanitizer as _san

#: The attribution-bucket roster: every ``attribution.record("...")``
#: literal in the engine must name one of these (tpulint TPU-L009), and
#: every bucket appears in generated docs/metrics.md.
BUCKETS: Dict[str, str] = {
    "compile": "XLA compilation: the first execution of a newly built "
               "fused/stage computation (fuse-cache miss; includes that "
               "first batch's compute — compile dominates it 10x+)",
    "device_compute": "host wall time inside exec operator spans (every "
                      "exec *Time metric not classified into another "
                      "bucket): the enqueue time of asynchronous "
                      "dispatches plus whatever device wait a host sync "
                      "inside the span holds; NOT the device's busy time "
                      "(on the chip it read 18 ms a Parquet Q6 query "
                      "whose device worked 3.1 s, and 6.7 s a Parquet Q3 "
                      "query whose device worked 5.7 s; PERF.md, PR 26)",
    "host_decode": "host-side scan decode and H2D/D2H transfer time "
                   "(tpuDecodeTime, copyToDeviceTime, copyFromDeviceTime)",
    "shuffle": "exchange work: partitioning kernels plus every *Time "
               "metric on an Exchange/Shuffle exec (serde, store writes)",
    "semaphore_wait": "tasks blocked acquiring the device semaphore "
                      "(semaphoreWaitTime task accumulator)",
    "pipeline_stall": "pipeline consumers blocked on a producer refill "
                      "(pipelineStallTime)",
    "retry_backoff": "retry-OOM store drain + exponential backoff between "
                     "attempts (retryBlockTime task accumulator)",
    "spill": "spill time device->host and host->disk (spillToHostTime, "
             "spillToDiskTime task accumulators)",
    "other": "unattributed wall-time remainder: admission, planning, "
             "host time of query.execute outside every exec span, result "
             "assembly (zero when concurrency-scaled); the document's "
             "other_phases splits it by the phase account's clocks",
}

#: *Time metrics that are overlapped upstream work or nested inside
#: another metric's span, never critical path on their own (mirrors
#: metrics.WAIT_TIME_METRICS/NESTED_TIME_METRICS reasoning: producer
#: time is the upstream's own decode/upload, already counted on the
#: upstream node; iciExchangeTime runs inside partitionTime's span and
#: is reported separately as the 'ici_exchange' view)
_EXCLUDED_METRICS = frozenset(("pipelineProducerTime", "iciExchangeTime"))

#: metric-name -> bucket for the per-exec snapshot half; a *Time metric
#: absent here buckets as device_compute (or shuffle on an exchange exec)
METRIC_BUCKETS: Dict[str, str] = {
    "tpuDecodeTime": "host_decode",
    "copyToDeviceTime": "host_decode",
    "copyFromDeviceTime": "host_decode",
    "partitionTime": "shuffle",
    "pipelineStallTime": "pipeline_stall",
    "semaphoreWaitTime": "semaphore_wait",
    "retryBlockTime": "retry_backoff",
    "spillToHostTime": "spill",
    "spillToDiskTime": "spill",
}

#: per-task accumulators folded into the aggregate at task completion
#: (these never appear in exec snapshots — no double counting)
TASK_BUCKETS: Dict[str, str] = {
    "semaphoreWaitTime": "semaphore_wait",
    "retryBlockTime": "retry_backoff",
    "spillToHostTime": "spill",
    "spillToDiskTime": "spill",
}

#: exec-class substrings whose unclassified *Time metrics bucket as
#: shuffle instead of device_compute
_SHUFFLE_CLASSES = ("Exchange", "Shuffle")

# the classification tables may only target roster buckets
assert set(METRIC_BUCKETS.values()) <= set(BUCKETS)
assert set(TASK_BUCKETS.values()) <= set(BUCKETS)

_LOCK = _san.lock("obs.attribution")
#: the ACTIVE query's direct-record aggregate (bucket -> ns); None when
#: no top-level action is running — record() is then one global read
_AGG: Optional[Dict[str, int]] = None

import threading as _threading  # noqa: E402 (module-local alias)

#: per-thread suppression: the AOT warmup replays set this (and the
#: task-wave factory propagates it to their task threads) so a replay's
#: compile/task records cannot land in a CONCURRENT user query's
#: aggregate — the one module-global _AGG cannot tell callers apart
_SUPPRESS = _threading.local()


def thread_suppressed() -> bool:
    return bool(getattr(_SUPPRESS, "on", False))


def set_thread_suppressed(on: bool) -> None:
    _SUPPRESS.on = bool(on)


def suppress_scope():
    """Context manager suppressing record()/fold_task() on the CURRENT
    thread (task waves submitted within inherit it)."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        prev = thread_suppressed()
        _SUPPRESS.on = True
        try:
            yield
        finally:
            _SUPPRESS.on = prev

    return _cm()


# ---------------------------------------------------------------------------
# per-query aggregate lifecycle (driven by TpuSession.collect)
# ---------------------------------------------------------------------------

def on_query_start() -> None:
    """Open a fresh aggregate for a top-level action."""
    global _AGG
    with _LOCK:
        _AGG = {}


def finish() -> Dict[str, int]:
    """Close and return the aggregate (bucket -> ns)."""
    global _AGG
    with _LOCK:
        agg, _AGG = (_AGG if _AGG is not None else {}), None
        return agg


def reset_for_tests() -> None:
    global _AGG
    with _LOCK:
        _AGG = None


def record(bucket: str, ns: int) -> None:
    """Direct-record ns into the active query's bucket (fuse-cache
    compile timing). No active query: one module-global read."""
    if _AGG is None:
        return
    if thread_suppressed():
        return  # warmup-replay work: not this user query's time
    with _LOCK:
        agg = _AGG
        if agg is not None:
            agg[bucket] = agg.get(bucket, 0) + int(ns)


def fold_task(metrics: Dict[str, object]) -> None:
    """Fold one finished task's accumulators into the active aggregate
    (called from TaskContext.complete — one fold per task, never per
    batch; no active query: one module-global read)."""
    if _AGG is None or thread_suppressed():
        return
    for name, bucket in TASK_BUCKETS.items():
        m = metrics.get(name)
        if m is None:
            continue
        try:
            v = int(m.value)
        except Exception:  # noqa: BLE001 - an unresolvable lazy count
            continue
        if v:
            record(bucket, v)


# ---------------------------------------------------------------------------
# the fold
# ---------------------------------------------------------------------------

def classify_exec_times(snaps: Optional[Dict[str, dict]]
                        ) -> Dict[str, Dict[str, int]]:
    """Per-exec-CLASS bucket decomposition of a last_metrics()-shaped
    snapshot: {exec_class: {bucket: ns}} under exactly the rules
    attribute() folds into its query totals. This is the snapshot half
    of attribute() factored out so the kernel cost auditor's roofline
    join (analysis/kernel_audit.py) reads per-class device seconds from
    the SAME classification — its device_compute reconciles with the
    attribution bucket by construction, not by a parallel copy of the
    rules."""
    per_cls: Dict[str, Dict[str, int]] = {}
    for exec_key, snap in (snaps or {}).items():
        cls = exec_key.split("#", 1)[0]
        shuffle_cls = any(s in cls for s in _SHUFFLE_CLASSES)
        dst = per_cls.setdefault(cls, {})
        for mname, v in snap.items():
            if not mname.endswith("Time") or mname in _EXCLUDED_METRICS:
                continue
            try:
                v = int(v)
            except Exception:  # noqa: BLE001 - non-numeric snapshot entry
                continue
            if v <= 0:
                continue
            b = METRIC_BUCKETS.get(mname)
            if b is None:
                b = "shuffle" if shuffle_cls else "device_compute"
            dst[b] = dst.get(b, 0) + v
    return per_cls


#: the compile-correction cascade order: a compile-laden first dispatch
#: also ran under its exec's span, so its wall sits in one of these
#: buckets too — subtraction walks them in THIS order. attribute() and
#: the kernel auditor's roofline join (analysis/kernel_audit.py) both
#: call subtract_compile, so the 'reconciles by construction' guarantee
#: rests on one cascade, not two hand-synchronized copies.
_COMPILE_CASCADE = ("device_compute", "shuffle", "host_decode")


def subtract_compile(totals: Dict[str, int], compile_ns: int) -> None:
    """Subtract a query's direct-recorded compile ns from the buckets
    its first dispatches double-counted into, in cascade order,
    mutating `totals` in place. Buckets absent from `totals` are
    skipped (the roofline join passes only its device groups)."""
    rem = int(compile_ns)
    if rem <= 0:
        return
    for b in _COMPILE_CASCADE:
        if b not in totals:
            continue
        shift = min(rem, totals[b])
        totals[b] -= shift
        rem -= shift
        if not rem:
            break


def attribute(snaps: Optional[Dict[str, dict]], duration_ns: int,
              extra: Optional[Dict[str, int]] = None,
              phases: Optional[Dict[str, int]] = None) -> Optional[dict]:
    """Decompose one query's wall time into the bucket roster.

    `snaps` is a last_metrics()-shaped {exec_key: {metric: value}}
    snapshot (the epilogue passes the phase account's peeked one);
    `extra` the direct-record aggregate from finish(); `phases` the
    phase account's clocks in ns (runtime/obs/phases.py), which split
    the `other` bucket. Returns the attribution document (buckets in
    seconds, fractions of wall, measured total and concurrency factor)
    or None for a zero-duration query."""
    wall_ns = int(duration_ns)
    if wall_ns <= 0:
        return None
    totals = {b: 0 for b in BUCKETS}
    for per_bucket in classify_exec_times(snaps).values():
        for b, v in per_bucket.items():
            totals[b] += v
    # views: named sub-intervals of a bucket, reported beside it rather
    # than as buckets of their own (they nest inside an already-counted
    # metric, so adding them to totals would double-count). ici_exchange
    # is the in-program all_to_all dispatch inside the shuffle bucket's
    # partitionTime. Raw measured ns, like measured_seconds — never
    # concurrency-scaled.
    ici_ns = 0
    for snap in (snaps or {}).values():
        try:
            ici_ns += int(snap.get("iciExchangeTime", 0))
        except Exception:  # noqa: BLE001 - non-numeric snapshot entry
            pass
    views = {"ici_exchange": round(ici_ns / 1e9, 9)} if ici_ns > 0 else {}
    for b, v in (extra or {}).items():
        if b in totals:
            totals[b] += int(v)
    # compile correction: the compile-laden first dispatch also ran
    # under its exec's span, so its ns sit in the span's bucket too —
    # device_compute usually, but a fresh EXCHANGE kernel's first call
    # times into 'shuffle' and a scan upload kernel's into
    # 'host_decode'. Cascade the subtraction so compile stays disjoint
    # from all three instead of double-counting (which would inflate
    # measured_seconds past wall and fake a concurrency factor).
    subtract_compile(totals, totals["compile"])
    measured = sum(totals.values())
    if measured > wall_ns:
        # concurrent tasks: summed time exceeds wall — report
        # critical-path SHARES (scaled to wall), keep the raw total
        factor = measured / wall_ns
        scaled = {b: int(v * wall_ns / measured)
                  for b, v in totals.items()}
        scaled["other"] += wall_ns - sum(scaled.values())  # rounding
        totals = scaled
    else:
        factor = 1.0
        totals["other"] += wall_ns - measured
    doc = {
        # 9 decimals = full ns resolution: a 6-decimal round would zero
        # genuine sub-microsecond buckets and break the exact-sum
        # invariant the reconciliation tests assert
        "wall_seconds": round(wall_ns / 1e9, 9),
        "buckets": {b: round(totals[b] / 1e9, 9) for b in BUCKETS},
        "fractions": {b: round(totals[b] / wall_ns, 4) for b in BUCKETS},
        "measured_seconds": round(measured / 1e9, 9),
        "concurrency_factor": round(factor, 3),
    }
    if views:
        # keyed only when present so default-path documents (and every
        # golden artifact derived from them) stay byte-identical
        doc["views"] = views
    if phases:
        # `other` by the phases that no exec timer covers: admission and
        # planning as the account clocked them, the rest being host time
        # inside query.execute outside every exec span plus the glue
        # between the spans (zero throughout when concurrency-scaled)
        rem, split = totals["other"], {}
        for p in ("admit", "plan"):
            split[p] = min(rem, int(phases.get(p, 0)))
            rem -= split[p]
        split["execute"] = rem
        doc["other_phases"] = {p: round(v / 1e9, 9)
                               for p, v in split.items()}
    return doc


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def render_text(doc: Optional[dict], width: int = 24) -> List[str]:
    """Text breakdown for explain(mode="analyze"): one line per nonzero
    bucket, largest first, with a proportional bar."""
    if not doc:
        return []
    head = (f"-- time attribution (wall {doc['wall_seconds']:.3f}s"
            + (f", concurrency {doc['concurrency_factor']:.1f}x"
               if doc.get("concurrency_factor", 1.0) > 1.0 else "")
            + ") --")
    lines = [head]
    buckets = doc.get("buckets", {})
    fracs = doc.get("fractions", {})
    for b in sorted(buckets, key=lambda k: -buckets[k]):
        s = buckets[b]
        if s <= 0:
            continue
        frac = fracs.get(b, 0.0)
        bar = "#" * max(1, int(frac * width))
        lines.append(f"  {b:<15} {s:>9.3f}s {frac * 100:>5.1f}%  {bar}")
    for name, s in sorted(doc.get("views", {}).items()):
        lines.append(f"  view:{name:<10} {s:>9.3f}s  (measured, nested "
                     f"in shuffle)")
    return lines


def summary(doc: Optional[dict], top: int = 3) -> Optional[dict]:
    """Compact /healthz form: wall + the top-N nonzero buckets."""
    if not doc:
        return None
    buckets = doc.get("buckets", {})
    ranked = sorted(((b, s) for b, s in buckets.items() if s > 0),
                    key=lambda kv: -kv[1])[:top]
    return {"wall_seconds": doc.get("wall_seconds"),
            "top_buckets": {b: s for b, s in ranked}}
