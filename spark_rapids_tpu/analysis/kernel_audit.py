"""Kernel cost auditor: per-dispatch FLOPs/bytes accounting at trace time.

The engine's hand-estimated bandwidth sat far below the HBM roofline and
nothing in the system could say WHY: the trace/attribution layer (PR 9)
decomposes wall time, but no surface
knew how many bytes or FLOPs a dispatch actually moves, whether a
kernel is bandwidth-, compute- or overhead-bound, or how many bytes the
shape-bucket ladder (PR 10) wastes as padding. This module is the
device-cost half: the reference dedicates a whole subsystem to per-op
device metrics (NvtxWithMetrics / ProfilerOnExecutor / per-exec
GpuMetrics); a TPU engine gets the same numbers from XLA's own cost
model instead of CUPTI.

How it hooks (and why at TRACE time)
------------------------------------
``runtime/compile_cache.py`` — the one sanctioned compile choke point —
wraps every traced Python body through :func:`wrap_traced` (keyed fused
entries) / :func:`wrap_kernel` (module-level ``compile_cache.jit``
kernels). jax executes the Python body ONLY while tracing: once per
(entry, argument-shape signature), including the re-traces a new shape
bucket triggers under an existing entry. The wrapper therefore fires
exactly once per distinct computation the device will ever run, records
the input aval signature, and queues a deferred resolution; steady-state
dispatches never execute Python, so the steady-state cost of the hook is
STRUCTURALLY zero — not "measured small", absent.

An earlier attempt audited in the first-call window instead and was
abandoned as nondeterministic two ways: an entry whose cache key spans
several argument shapes was audited at whichever shape a task thread
dispatched first (per-entry flops varied up to 2x per run), and the
golden generator's budgets pass leaked session state that shifted which
query first-traced an entry. Trace-time hooking with per-shape dedup is
the fix: accounting is SHAPE-COMPLETE (every shape that ever dispatches
is audited at its own trace), so per-query sums do not depend on thread
scheduling or on which process first warmed an entry.

Resolution is deferred off the dispatch path: the wrapper stores the
argument avals as ShapeDtypeStructs plus the jitted function, and
:func:`resolve_pending` (query epilogue / report tools) replays
``jfn.lower(avals).compile().cost_analysis()`` to pull XLA's flops and
bytes-accessed, plus input/output plane bytes from the avals and the
bucket-ladder padding exposure of the row capacity.

Per-query accounting
--------------------
``compile_cache.get`` is called once per dispatch (fuse/run_stage route
every batch through it), so when the audit is armed it notes the
resolved entry key into the active query's dispatch tally — one dict
increment on an already-Python path; with the audit off the hook is a
single module-global None check (the fuse._DISPATCH_HOOK pattern). The
query summary then joins (entry -> dispatch count) with the global
(entry, shape) -> cost table: a multi-shape entry is apportioned at the
mean of its audited shape costs (exact per-dispatch shape capture would
cost per-dispatch pytree walks; the approximation is deterministic
because the shape SET is). Module-level kernels dispatch beneath jax's
own signature cache where no per-call choke point exists; they are
credited once per audited shape to the query that traced them.

The roofline join (:func:`roofline`) combines the query's audited
bytes/FLOPs with ``attribution.classify_exec_times`` — the SAME
classification attribute() folds into its buckets, so the reported
device seconds reconcile with the attribution ``device_compute`` bucket
by construction — into per-group achieved GB/s and FLOP/s, % of the
configured rooflines, a memory/compute/dispatch-overhead boundedness
verdict, and the padding-waste exposure. Surfaced in
``explain(mode="analyze")``, history records, ``rapids_roofline_*``
gauges, the live console, and ``tools/roofline_report.py``.

Golden signatures: ``tools/gen_dispatch_budgets.py`` pins a per-query
cost signature for every NDS probe plan (regeneration must replay
exactly: fresh session, ``gen_tables(0.002, seed=7)``, cleared compile
cache, sorted query order); ``tools/audit_smoke.py`` and the tier-1
2-query cold prefix diff against them so a kernel that silently starts
moving 2x the bytes fails CI even when wall time hides it.

``KERNEL_PRIMITIVES`` below is the roster of kernel-emitting modules
(tpulint TPU-L013, the L007-L012 roster pattern): every module with a
``compile_cache.jit`` or ``pallas_call`` site must register here, so the
audit's coverage statement — "every compiled computation routes through
an audited entry point" — is enforced, not assumed.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from spark_rapids_tpu.analysis import sanitizer as _san

#: Kernel-emitting modules (package-relative paths): every module
#: containing a ``compile_cache.jit`` decoration/call or a raw
#: ``pallas_call`` site must be registered here — tpulint TPU-L013
#: AST-extracts this roster and fails on unrostered kernel emitters and
#: on stale entries, the way TPU-L008 pins fault sites. The golden
#: cost-signature artifact embeds the roster so coverage drift shows up
#: in review.
KERNEL_PRIMITIVES: Dict[str, str] = {
    "ops/kernels.py": "gather/compact/concat/sort primitives and the "
                      "device batch helpers (compile_cache.jit sites)",
    "ops/join.py": "dense-table hash-join build/probe kernels",
    "ops/repartition.py": "single-dispatch counting-sort shuffle "
                          "partitioning kernel",
    "ops/pallas_decode.py": "pallas parquet-decode bit-slice kernel "
                            "(dictionary/RLE unpack; run tables spread "
                            "over rows by a prefix sum) — sanctioned "
                            "pallas module",
    "ops/pallas_kernels.py": "hand-tiled pallas kernels (murmur3, "
                             "sort tiles) — sanctioned pallas module",
    "ops/pallas_segsum.py": "pallas segmented-sum kernel — sanctioned "
                            "pallas module",
    "parallel/distributed.py": "ICI mesh shard-step kernels "
                               "(compile_cache.jit sites)",
    # exec/tpu_nodes.py left the roster in round 19: the ICI exchange
    # shard program now compiles through the KEYED fuse layer
    # ("ici_exchange"/"ici_hash" families), so the exec layer has no
    # direct compile_cache.jit site — every dispatch routes through the
    # keyed fuse/run_stage entries.
}

#: audit exec-classes whose device time lands in the attribution
#: 'shuffle' bucket (exchange partitioning kernels and the module-level
#: repartition kernel — its exec-class embeds the module path, which
#: contains 'repartition'); everything else is 'device_compute'
_SHUFFLE_FAMILY_MARKERS = ("exchange", "partition", "shuffle")

#: findings list hard cap (a pathological run must not grow unbounded)
_MAX_FINDINGS = 200

_LOCK = _san.lock("analysis.kernel_audit")

#: armed flag: read once per get() miss and once per traced body — the
#: disabled path costs compile_cache one module-global None check
_ENABLED = False
_PEAK_GBPS = 819.0
_PEAK_GFLOPS = 197000.0
_OVERHEAD_FACTOR = 10.0

#: (exec_class, key, conf-fingerprint) -> {shape_sig: record-dict}.
#: Process-global, persisting across queries like the warm-trace cache
#: it mirrors: a record exists for every (entry, shape) traced while the
#: audit was armed.
_RECORDS: Dict[Tuple, Dict[Tuple, dict]] = {}

#: deferred resolutions: (entry_key, shape_sig, jfn_box, args, kwargs)
#: where args/kwargs carry ShapeDtypeStructs in place of array leaves
_PENDING: List[Tuple] = []

#: the ACTIVE query's dispatch tally (entry_key -> count); None when no
#: top-level action is running (the attribution._AGG singleton pattern,
#: same known concurrent-queries limit)
_AGG: Optional[Dict[Tuple, int]] = None

#: the ACTIVE query's per-wave shard row tallies: (n_shards, rows) where
#: rows is the UNRESOLVED [n_shards] device vector of live output rows
#: per shard (exec/sharded.py notes one entry per SPMD wave — no sync on
#: the dispatch path; finish_query fetches them in one bulk device_get)
_SHARD_NOTES: List[Tuple[int, object]] = []

#: audit anomalies (unresolvable cost analysis, steady-state dispatches
#: of entries traced before the audit armed): the golden generator
#: aborts on any of these
_FINDINGS: List[str] = []

_STATS = {"audited_shapes": 0, "resolved": 0, "resolve_failures": 0}

#: set while resolve_pending() lowers: a body re-trace fired by the
#: lowering itself must not queue a new pending entry
_TLS = threading.local()

#: jitted module-level kernels (compile_cache.jit) whose traces live in
#: jax's per-function signature cache, NOT the keyed warm-trace cache:
#: clear_for_cold_audit must drop exactly these so an in-process cold
#: replay re-fires their audit hooks — a process-wide jax.clear_caches
#: would also evict every jnp-internal jit and slow the surrounding
#: test suite by minutes. WEAK references: some compile_cache.jit
#: sites run per call (the ICI exchange shard jit, the distributed
#: step builders), and a strong registry would pin every such
#: PjitFunction + compiled executable for process lifetime. Dead refs
#: are pruned on registration.
_KERNEL_JFNS: List = []  # of weakref.ref


def enabled() -> bool:
    return _ENABLED


def device_is_v5e() -> bool:
    """Whether the default device is a TPU v5e, the one device whose
    peaks this repo carries (819 GB/s HBM, 197 TFLOP/s bf16 — the
    spark.rapids.obs.audit.peak* defaults). A SHARE of those peaks is
    reported only there; anywhere else it is None, never a v5e roofline
    of another device's seconds."""
    import jax
    kind = jax.devices()[0].device_kind.lower()
    return "v5 lite" in kind or "v5e" in kind


def configure(conf) -> None:
    """Apply the session conf (called from prepare_execution, the
    faults.from_conf slot): arm/disarm the audit and publish the
    roofline peaks. Arming installs this module as compile_cache's
    auditor; disarming uninstalls it so the disabled per-dispatch cost
    is one None check."""
    global _ENABLED, _PEAK_GBPS, _PEAK_GFLOPS, _OVERHEAD_FACTOR
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.runtime import compile_cache as _cc
    _PEAK_GBPS = float(conf.get(C.OBS_AUDIT_PEAK_GBPS))
    _PEAK_GFLOPS = float(conf.get(C.OBS_AUDIT_PEAK_GFLOPS))
    _OVERHEAD_FACTOR = float(conf.get(C.OBS_AUDIT_OVERHEAD_FACTOR))
    on = bool(conf.get(C.OBS_AUDIT_ENABLED))
    if on == _ENABLED:
        return
    _ENABLED = on
    _cc.set_auditor(_MODULE if on else None)


def set_enabled(on: bool) -> None:
    """Direct arm/disarm (tests and tools; configure() is the conf
    path)."""
    global _ENABLED
    from spark_rapids_tpu.runtime import compile_cache as _cc
    _ENABLED = bool(on)
    _cc.set_auditor(_MODULE if _ENABLED else None)


def reset_for_tests(drop_records: bool = False) -> None:
    """Disarm and clear per-query state. Records are KEPT by default:
    they mirror the process-wide warm-trace cache — dropping them while
    the cache stays warm would make every later audited query report
    phantom unaudited-entry findings. ``drop_records=True`` pairs with
    ``compile_cache.clear()`` (see clear_for_cold_audit)."""
    global _AGG, _ENABLED
    set_enabled(False)
    with _LOCK:
        _AGG = None
        del _FINDINGS[:]
        del _PENDING[:]
        del _SHARD_NOTES[:]
        if drop_records:
            _RECORDS.clear()
            for k in _STATS:
                _STATS[k] = 0


def clear_for_cold_audit() -> None:
    """Drop the warm-trace cache, the audited module kernels' own jit
    signature caches, AND the audit record table together so the next
    audited run is accounting-complete from a cold start (the
    golden-generator / audit-smoke / cold-prefix-test preamble).
    Module-level ``compile_cache.jit`` kernels need their own cache
    drop: their traces live in jax's per-function signature cache, not
    the keyed warm-trace cache — without dropping them, a kernel traced
    earlier in the process never re-fires the audit hook and its cost
    silently vanishes from an in-process "cold" replay (fresh processes
    — the golden recipe — would disagree). The drop is per REGISTERED
    kernel function, deliberately not the process-wide
    jax.clear_caches: evicting every jnp-internal jit leaves the whole
    surrounding process re-tracing basics (measured: minutes over a
    test suite, enough to blow the tier-1 timeout)."""
    from spark_rapids_tpu.runtime import compile_cache as _cc
    _cc.clear()
    with _LOCK:
        kernels = [r() for r in _KERNEL_JFNS]
    for jfn in kernels:
        if jfn is None:
            continue  # a per-call jit site's fn already collected
        try:
            jfn.clear_cache()
        except Exception:  # noqa: BLE001 - a kernel without a
            pass  # clearable cache just stays warm (and unaudited)
    with _LOCK:
        _RECORDS.clear()
        del _PENDING[:]
        del _FINDINGS[:]


def findings() -> List[str]:
    with _LOCK:
        return list(_FINDINGS)


def stats() -> Dict[str, int]:
    with _LOCK:
        out = dict(_STATS)
        out["entries"] = len(_RECORDS)
        out["shapes"] = sum(len(v) for v in _RECORDS.values())
        out["pending"] = len(_PENDING)
        out["findings"] = len(_FINDINGS)
    return out


def _finding(msg: str) -> None:
    with _LOCK:
        if len(_FINDINGS) < _MAX_FINDINGS:
            _FINDINGS.append(msg)


# ---------------------------------------------------------------------------
# the trace-time hook (installed into compile_cache)
# ---------------------------------------------------------------------------

def _leaf_sig(leaf) -> Tuple:
    aval = getattr(leaf, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        return (tuple(aval.shape), str(aval.dtype))
    # a non-array leaf: a static argument (static_argnums/argnames)
    # rides the trace CONCRETELY, and jax compiles one executable per
    # static VALUE — the signature must carry the value or two static
    # variants (num_partitions=4 vs 8) dedupe into one audit record
    # and the second variant's cost silently vanishes
    if isinstance(leaf, (int, bool, float, str, bytes, type(None))):
        return ((), type(leaf).__name__, repr(leaf))
    return ((), type(leaf).__name__)


def _leaf_bytes(leaf) -> int:
    aval = getattr(leaf, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    n = 1
    for d in aval.shape:
        n *= int(d)
    try:
        return n * int(aval.dtype.itemsize)
    except Exception:  # noqa: BLE001 - an extended dtype without a
        return n  # host itemsize still counts its element count


def _leading_dim(leaf) -> int:
    aval = getattr(leaf, "aval", None)
    if aval is not None and getattr(aval, "shape", ()):
        return int(aval.shape[0])
    return 0


def _sds_of(leaf):
    import jax
    aval = getattr(leaf, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype)
    return leaf  # static leaves replay as themselves


def _observe_trace(entry_key: Tuple, jfn_box: dict, args, kwargs) -> bool:
    """The trace-time body of both wrappers: dedupe by shape signature,
    record input plane bytes + row capacity, queue the deferred
    resolution. Runs ONLY while jax traces (or re-traces) the entry.
    Returns True when the shape is NEW to the audit (False for a
    re-trace of an audited shape, e.g. after jax dropped its caches)."""
    import jax
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    sig = tuple(_leaf_sig(x) for x in leaves)
    with _LOCK:
        shapes = _RECORDS.setdefault(entry_key, {})
        if sig in shapes:
            return False
        rec = {
            "in_bytes": sum(_leaf_bytes(x) for x in leaves),
            "row_capacity": max([_leading_dim(x) for x in leaves] or [0]),
            "flops": None, "bytes_accessed": None, "out_bytes": None,
            "error": None,
        }
        shapes[sig] = rec
        _STATS["audited_shapes"] += 1
        if getattr(_TLS, "resolving", 0):
            return True  # a lowering replay re-traced the body: the
            # record exists for dedup, but resolution is already in flight
        sds = jax.tree_util.tree_map(_sds_of, (args, kwargs))
        _PENDING.append((entry_key, sig, jfn_box, sds[0], sds[1]))
    return True


def wrap_traced(exec_class: str, key: Tuple, fp: Tuple,
                body: Callable) -> Tuple[Callable, Callable]:
    """Wrap a keyed fused entry's traced Python body. Returns
    (wrapped_body, bind_jfn): compile_cache jits the wrapped body and
    binds the resulting jitted function for the deferred lowering."""
    entry_key = (exec_class, key, fp)
    jfn_box: dict = {}

    def traced(*args, **kwargs):
        if _ENABLED:
            try:
                _observe_trace(entry_key, jfn_box, args, kwargs)
            except Exception as e:  # noqa: BLE001 - the audit must
                # never fail a trace
                _finding(f"trace observation failed for {exec_class}: "
                         f"{type(e).__name__}: {e}")
        return body(*args, **kwargs)

    def bind(jfn):
        jfn_box["jfn"] = jfn

    return traced, bind


def wrap_kernel(fn: Callable) -> Tuple[Callable, Callable]:
    """Wrap a module-level ``compile_cache.jit`` kernel's Python body.
    Wrapping happens unconditionally at decoration (import time, before
    any conf exists); the armed check runs at TRACE time, so steady
    dispatches cost exactly what a raw jax.jit call costs. functools.
    wraps carries the original signature through for static_argnames."""
    import functools
    mod = (getattr(fn, "__module__", "") or "").rsplit(
        "spark_rapids_tpu.", 1)[-1]
    # the family name must be process-independent: never fall back to
    # repr(fn), whose 0x-address would make golden signatures differ
    # per process
    name = (getattr(fn, "__qualname__", None)
            or getattr(fn, "__name__", None) or type(fn).__name__)
    entry_key = (f"kernel:{mod}.{name}", (), ())
    jfn_box: dict = {}

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        if _ENABLED:
            try:
                # one observation per audited SHAPE: a re-trace of a
                # shape already on record (jax evicted or dropped its
                # trace cache) is not credited again
                if _observe_trace(entry_key, jfn_box, args, kwargs):
                    _note_kernel_trace(entry_key)
            except Exception as e:  # noqa: BLE001 - the audit must
                # never fail a trace
                _finding(f"trace observation failed for "
                         f"{entry_key[0]}: {type(e).__name__}: {e}")
        return fn(*args, **kwargs)

    def bind(jfn):
        import weakref
        jfn_box["jfn"] = jfn
        with _LOCK:
            _KERNEL_JFNS[:] = [r for r in _KERNEL_JFNS
                               if r() is not None]
            _KERNEL_JFNS.append(weakref.ref(jfn))

    return traced, bind


def note(entry_key: Tuple) -> None:
    """One dispatch of a keyed entry (called by compile_cache.get on
    every hit/miss while the audit is armed): tally it into the active
    query. No active query, or a warmup-replay thread: drop."""
    if _AGG is None:
        return
    from spark_rapids_tpu.runtime.obs import attribution as _attr
    if _attr.thread_suppressed():
        return  # AOT warmup replay: not this user query's dispatches
    with _LOCK:
        agg = _AGG
        if agg is not None:
            agg[entry_key] = agg.get(entry_key, 0) + 1


def note_shards(n_shards: int, rows) -> None:
    """One SPMD wave of a sharded stage (exec/sharded.py): tally the
    per-shard live output rows into the active query. `rows` is the
    [n_shards] device vector — stored UNRESOLVED so the dispatch path
    never syncs; finish_query fetches every wave in one bulk device_get.
    No active query, or a warmup-replay thread: drop (the note()
    discipline)."""
    if _AGG is None:
        return
    from spark_rapids_tpu.runtime.obs import attribution as _attr
    if _attr.thread_suppressed():
        return
    with _LOCK:
        if _AGG is not None:
            _SHARD_NOTES.append((int(n_shards), rows))


def _note_kernel_trace(entry_key: Tuple) -> None:
    """Module-level kernels dispatch beneath jax's signature cache where
    no per-call choke point exists: credit one observation per audited
    shape to the query that traced it (documented approximation)."""
    note(entry_key)


#: what compile_cache stores as its auditor (the module itself keeps the
#: hook surface to three attribute reads: note / wrap_traced /
#: wrap_kernel)
import sys as _sys  # noqa: E402 (module-handle export)

_MODULE = _sys.modules[__name__]


# ---------------------------------------------------------------------------
# deferred resolution
# ---------------------------------------------------------------------------

def resolve_pending() -> int:
    """Resolve every queued (entry, shape) through XLA's compiled cost
    analysis. Runs OFF the dispatch path — the query epilogue and the
    report tools call it; with nothing pending it is one list check.
    Returns the number resolved."""
    with _LOCK:
        if not _PENDING:
            return 0
        work, _PENDING[:] = list(_PENDING), []
    done = 0
    _TLS.resolving = getattr(_TLS, "resolving", 0) + 1
    try:
        for entry_key, sig, jfn_box, args, kwargs in work:
            rec = _RECORDS.get(entry_key, {}).get(sig)
            if rec is None:
                continue
            jfn = jfn_box.get("jfn")
            try:
                if jfn is None:
                    raise RuntimeError("jitted fn never bound")
                lowered = jfn.lower(*args, **kwargs)
                compiled = lowered.compile()
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                ca = ca or {}
                out_bytes = 0
                import jax
                for oi in jax.tree_util.tree_leaves(lowered.out_info):
                    shape = getattr(oi, "shape", None)
                    dt = getattr(oi, "dtype", None)
                    if shape is None or dt is None:
                        continue
                    n = 1
                    for d in shape:
                        n *= int(d)
                    out_bytes += n * int(jax.numpy.dtype(dt).itemsize)
                with _LOCK:
                    rec["flops"] = float(ca.get("flops", 0.0) or 0.0)
                    rec["bytes_accessed"] = float(
                        ca.get("bytes accessed", 0.0) or 0.0)
                    rec["out_bytes"] = out_bytes
                    _STATS["resolved"] += 1
                done += 1
            except Exception as e:  # noqa: BLE001 - an unresolvable
                # entry is a FINDING, never a query failure
                with _LOCK:
                    rec["error"] = f"{type(e).__name__}: {e}"
                    _STATS["resolve_failures"] += 1
                _finding(f"cost analysis failed for {entry_key[0]} "
                         f"{sig!r}: {type(e).__name__}: {e}")
    finally:
        _TLS.resolving -= 1
    return done


# ---------------------------------------------------------------------------
# padding-waste math (the bucket-ladder exposure)
# ---------------------------------------------------------------------------

#: plane itemsizes whose tile-aligned ladders a capacity may have come
#: from (None = the unaligned base ladder). Under the default 2.0
#: growth factor all of these coincide; tighter factors align per
#: itemsize, so membership is checked against each.
_LADDER_ITEMSIZES = (None, 1, 2, 4, 8)


def bucket_floor_live(capacity: int) -> Optional[int]:
    """Smallest live row count that buckets to `capacity` under the
    active shapes policy (None when `capacity` is off every ladder).
    Every dispatch at this capacity carries between floor and capacity
    live rows, so (capacity - floor)/capacity bounds the padding waste.

    The audit cannot know which plane dtype produced a capacity, so it
    checks membership against each per-itemsize tile-aligned ladder
    (byte planes bucket with itemsize=1 under non-2.0 growth factors
    and would otherwise read as off-ladder with waste 0.0) and returns
    the SMALLEST matching floor — the largest waste, keeping the
    reported 'waste <=' an honest upper bound."""
    from spark_rapids_tpu.runtime import shapes
    cap = int(capacity)
    if cap <= 0:
        return None
    floors = []
    for itemsize in _LADDER_ITEMSIZES:
        if not shapes.is_bucketed(cap, 1, itemsize):
            continue
        lo, hi = 1, cap  # bucket_rows is monotone: bisect the threshold
        while lo < hi:
            mid = (lo + hi) // 2
            if shapes.bucket_rows(mid, 1, itemsize) >= cap:
                hi = mid
            else:
                lo = mid + 1
        floors.append(lo)
    return min(floors) if floors else None


def padding_waste(live_rows: int, capacity: int) -> float:
    """Fraction of `capacity` that is dead padding for a dispatch
    carrying `live_rows` live rows: 0.0 at an exact bucket boundary,
    rising to the ladder's worst case just past the previous bucket."""
    cap = int(capacity)
    if cap <= 0:
        return 0.0
    return max(0.0, (cap - int(live_rows)) / cap)


def max_padding_waste(capacity: int) -> float:
    """The ladder's worst-case waste ratio at `capacity` (0.0 for
    off-ladder capacities, which the engine never produces)."""
    floor = bucket_floor_live(capacity)
    if floor is None:
        return 0.0
    return padding_waste(floor, capacity)


# ---------------------------------------------------------------------------
# per-query summary + golden signature
# ---------------------------------------------------------------------------

def on_query_start(conf=None) -> None:
    """Open the active query's dispatch tally (depth-0 collect). When
    the session conf rides along, (re)apply it FIRST: the tally opens
    at collect entry, before prepare_execution re-runs configure — a
    mid-session `conf.set` enabling the audit must cover the very next
    query, not silently skip it."""
    global _AGG
    if conf is not None:
        try:
            configure(conf)
        except Exception:  # noqa: BLE001 - a malformed conf must not
            pass  # fail the query; prepare_execution will re-raise
    if not _ENABLED:
        return
    with _LOCK:
        _AGG = {}
        del _SHARD_NOTES[:]  # a query that never finished must not leak


def finish_query() -> Optional[dict]:
    """Close the active query: resolve pending cost analyses and join
    the dispatch tally with the audit record table. Returns the query
    audit summary (None when the audit is off / nothing dispatched)."""
    global _AGG
    with _LOCK:
        agg, _AGG = _AGG, None
        shard_notes, _SHARD_NOTES[:] = list(_SHARD_NOTES), []
    # resolve even when this query dispatched nothing: trace-time
    # audits queued by nested/background work must not pile up
    resolve_pending()
    if not agg:
        return None
    summary = _summarize(agg)
    shards = _resolve_shards(shard_notes)
    if shards is not None:
        # conditional key: query_signature reads only summary["classes"],
        # and default-path (non-multichip) summaries never carry this —
        # golden cost signatures stay byte-identical
        summary["shards"] = shards
    return summary


def _resolve_shards(notes: List[Tuple[int, object]]) -> Optional[dict]:
    """Fold the per-wave shard row vectors into the skew document the
    roofline table and EXPLAIN ANALYZE print. ONE bulk device_get for
    all waves (off the dispatch path)."""
    if not notes:
        return None
    import jax as _jax
    try:
        fetched = _jax.device_get([r for _n, r in notes])
    except Exception:  # noqa: BLE001 - an unresolvable vector drops the
        return None  # skew column, never the query
    n_shards = max(n for n, _r in notes)
    totals = [0] * n_shards
    for (_n, _r), vals in zip(notes, fetched):
        flat = list(map(int, getattr(vals, "flat", vals)))
        for i, v in enumerate(flat[:n_shards]):
            totals[i] += v
    mean = sum(totals) / n_shards if n_shards else 0.0
    return {
        "n_shards": int(n_shards),
        "waves": len(notes),
        "rows_per_shard": totals,
        "skew": round(max(totals) / mean, 4) if mean > 0 else 0.0,
    }


def _summarize(agg: Dict[Tuple, int]) -> dict:
    classes: Dict[str, dict] = {}
    findings: List[str] = []
    with _LOCK:
        for entry_key, count in sorted(agg.items(), key=lambda kv:
                                       (kv[0][0], repr(kv[0]))):
            family = entry_key[0]
            shapes = _RECORDS.get(entry_key)
            dst = classes.setdefault(family, {
                "dispatches": 0, "entries": 0, "shapes": 0,
                "flops": 0.0, "bytes_accessed": 0.0,
                "in_bytes": 0.0, "out_bytes": 0.0,
                "padded_row_bytes_max_waste": 0.0,
            })
            dst["dispatches"] += count
            dst["entries"] += 1
            if not shapes:
                findings.append(
                    f"{count} dispatch(es) of unaudited entry "
                    f"{family!r}: traced before the audit armed — "
                    f"clear the compile cache (clear_for_cold_audit) "
                    f"for complete accounting")
                continue
            recs = list(shapes.values())
            n = len(recs)
            dst["shapes"] += n
            # mean-of-shapes apportioning: deterministic because the
            # shape SET is (accounting is shape-complete); exact
            # per-dispatch weighting would cost per-dispatch arg walks
            scale = count / n
            for rec in recs:
                waste = max_padding_waste(rec.get("row_capacity") or 0)
                ib = rec.get("in_bytes") or 0
                dst["in_bytes"] += ib * scale
                dst["padded_row_bytes_max_waste"] += ib * waste * scale
                if rec.get("flops") is None:
                    continue
                dst["flops"] += rec["flops"] * scale
                dst["bytes_accessed"] += rec["bytes_accessed"] * scale
                dst["out_bytes"] += (rec.get("out_bytes") or 0) * scale
    for msg in findings:
        _finding(msg)
    total = {"dispatches": 0, "entries": 0, "shapes": 0, "flops": 0.0,
             "bytes_accessed": 0.0, "in_bytes": 0.0, "out_bytes": 0.0,
             "padded_row_bytes_max_waste": 0.0}
    for c in classes.values():
        for k in total:
            total[k] += c[k]
    return {"classes": classes, "total": total,
            "query_findings": findings}


def family_bucket(family: str) -> str:
    """Which attribution bucket a kernel family's device time lands in
    (exchange/partitioning kernels time into 'shuffle')."""
    f = family.lower()
    if any(m in f for m in _SHUFFLE_FAMILY_MARKERS):
        return "shuffle"
    return "device_compute"


def query_signature(summary: Optional[dict]) -> Optional[dict]:
    """Canonical integer form of a query audit summary — what the golden
    cost-signature artifact pins. Rounded to ints so two runs serialize
    byte-identically."""
    if not summary:
        return None
    out = {}
    for family in sorted(summary["classes"]):
        c = summary["classes"][family]
        out[family] = {
            "dispatches": int(c["dispatches"]),
            "entries": int(c["entries"]),
            "shapes": int(c["shapes"]),
            "flops": int(round(c["flops"])),
            "bytes_accessed": int(round(c["bytes_accessed"])),
            "in_bytes": int(round(c["in_bytes"])),
            "out_bytes": int(round(c["out_bytes"])),
        }
    return out


#: the signature dimensions a golden diff reports, in severity order
_SIG_DIMS = ("dispatches", "entries", "shapes", "flops",
             "bytes_accessed", "in_bytes", "out_bytes")


def compare_signature(query: str, golden: Optional[dict],
                      got: Optional[dict],
                      rel_tol: float = 0.0) -> List[str]:
    """Diff one query's cost signature against its golden pin, naming
    the regressed dimension per class (the dispatch-budget diff
    pattern). `rel_tol` admits a relative slack on the float-derived
    dimensions (flops/bytes) for cross-XLA-version use; the CI gate
    runs at 0.0 — byte-identical."""
    diffs: List[str] = []
    golden, got = golden or {}, got or {}
    for family in sorted(set(golden) | set(got)):
        g, a = golden.get(family), got.get(family)
        if g is None:
            diffs.append(f"{query}: unexpected new kernel class "
                         f"{family!r} ({a})")
            continue
        if a is None:
            diffs.append(f"{query}: kernel class {family!r} vanished "
                         f"(golden: {g})")
            continue
        for dim in _SIG_DIMS:
            gv, av = g.get(dim, 0), a.get(dim, 0)
            if gv == av:
                continue
            if rel_tol and dim in ("flops", "bytes_accessed", "in_bytes",
                                   "out_bytes"):
                if abs(av - gv) <= rel_tol * max(abs(gv), 1):
                    continue
            diffs.append(f"{query}: {family} {dim} regressed "
                         f"{gv} -> {av}")
    return diffs


# ---------------------------------------------------------------------------
# the roofline join
# ---------------------------------------------------------------------------

def roofline(summary: Optional[dict], snaps: Optional[Dict[str, dict]],
             duration_ns: int,
             extra: Optional[Dict[str, int]] = None) -> Optional[dict]:
    """Join one query's audited bytes/FLOPs with its measured device
    seconds into roofline attribution.

    Device seconds come from ``attribution.classify_exec_times`` over
    the same metric snapshot attribute() folds — with the same
    compile-correction cascade — so the 'device_compute' group's
    seconds reconcile with the attribution bucket by construction.
    Groups: 'device_compute' (fused stages, aggregations, joins,
    windows) and 'shuffle' (exchange partitioning kernels), each with
    achieved GB/s + GFLOP/s, % of the configured rooflines
    (spark.rapids.obs.audit.peak*), a boundedness verdict, and the
    padding-waste exposure of the shape-bucket ladder."""
    if not summary:
        return None
    from spark_rapids_tpu.runtime.obs import attribution as _attr
    per_cls = _attr.classify_exec_times(snaps)
    bucket_ns = {"device_compute": 0, "shuffle": 0}
    for buckets in per_cls.values():
        for b in bucket_ns:
            bucket_ns[b] += buckets.get(b, 0)
    # THE attribute() compile-correction cascade (shared helper, same
    # order): a compile-laden first dispatch also ran under its exec's
    # span, so its wall sits in device_compute/shuffle too — subtract
    # it identically so the roofline denominator matches the
    # attribution bucket by construction
    _attr.subtract_compile(bucket_ns, (extra or {}).get("compile", 0))
    shares = device_is_v5e()
    groups = {}
    for gname in ("device_compute", "shuffle"):
        gbytes = gflops = gin = gdisp = gwaste = 0.0
        for family, c in summary["classes"].items():
            if family_bucket(family) != gname:
                continue
            gbytes += c["bytes_accessed"]
            gflops += c["flops"]
            gin += c["in_bytes"]
            gdisp += c["dispatches"]
            gwaste += c["padded_row_bytes_max_waste"]
        secs = bucket_ns[gname] / 1e9
        if not gdisp and not secs:
            continue
        est_mem_s = gbytes / (_PEAK_GBPS * 1e9) if _PEAK_GBPS else 0.0
        est_flop_s = gflops / (_PEAK_GFLOPS * 1e9) if _PEAK_GFLOPS \
            else 0.0
        est = max(est_mem_s, est_flop_s)
        if secs > 0 and est > 0 and secs > _OVERHEAD_FACTOR * est:
            bound = "dispatch_overhead"
        elif est_mem_s >= est_flop_s:
            bound = "memory"
        else:
            bound = "compute"
        achieved_gbps = gbytes / secs / 1e9 if secs > 0 else 0.0
        achieved_gflops = gflops / secs / 1e9 if secs > 0 else 0.0
        groups[gname] = {
            "seconds": round(secs, 9),
            "dispatches": int(gdisp),
            "bytes_accessed": int(round(gbytes)),
            "flops": int(round(gflops)),
            "achieved_gbps": round(achieved_gbps, 4),
            "achieved_gflops": round(achieved_gflops, 4),
            "roofline_pct_bw": round(100.0 * achieved_gbps
                                     / _PEAK_GBPS, 4)
            if shares and _PEAK_GBPS else None,
            "roofline_pct_flops": round(100.0 * achieved_gflops
                                        / _PEAK_GFLOPS, 4)
            if shares and _PEAK_GFLOPS else None,
            "bound": bound,
            "padding_waste_ratio": round(gwaste / gin, 4)
            if gin else 0.0,
        }
    if not groups:
        return None
    tot_bytes = sum(g["bytes_accessed"] for g in groups.values())
    tot_flops = sum(g["flops"] for g in groups.values())
    tot_secs = sum(g["seconds"] for g in groups.values())
    doc = {
        "wall_seconds": round(int(duration_ns) / 1e9, 9),
        "peak_gbps": _PEAK_GBPS,
        "peak_gflops": _PEAK_GFLOPS,
        "groups": groups,
        "total": {
            "seconds": round(tot_secs, 9),
            "bytes_accessed": int(tot_bytes),
            "flops": int(tot_flops),
            "achieved_gbps": round(tot_bytes / tot_secs / 1e9, 4)
            if tot_secs > 0 else 0.0,
            "roofline_pct_bw": round(100.0 * tot_bytes / tot_secs / 1e9
                                     / _PEAK_GBPS, 4)
            if shares and tot_secs > 0 and _PEAK_GBPS else None,
        },
        "kernels": {family: {
            "bucket": family_bucket(family),
            "dispatches": int(c["dispatches"]),
            "bytes_accessed": int(round(c["bytes_accessed"])),
            "flops": int(round(c["flops"])),
            "est_memory_seconds": round(
                c["bytes_accessed"] / (_PEAK_GBPS * 1e9), 9)
            if _PEAK_GBPS else None,
            "est_compute_seconds": round(
                c["flops"] / (_PEAK_GFLOPS * 1e9), 9)
            if _PEAK_GFLOPS else None,
        } for family, c in sorted(summary["classes"].items())},
    }
    shards = summary.get("shards")
    if shards is not None:
        # the per-shard skew column (multichip runs only): conditional
        # key so default-path roofline docs stay byte-identical
        doc["shards"] = shards
    return doc


def _pct_text(pct: Optional[float]) -> str:
    """A roofline share, or n/a off the v5e (device_is_v5e)."""
    return "   n/a" if pct is None else f"{pct:>6.3f}%"


def render_text(doc: Optional[dict], width: int = 24) -> List[str]:
    """Roofline lines for explain(mode="analyze"), the render_text
    pattern of attribution."""
    if not doc:
        return []
    lines = [f"-- roofline (audit; peaks {doc['peak_gbps']:g} GB/s, "
             f"{doc['peak_gflops']:g} GFLOP/s) --"]
    for gname in sorted(doc.get("groups", {})):
        g = doc["groups"][gname]
        pct = g.get("roofline_pct_bw")
        bar = "#" * max(1, int(min(pct, 100.0) / 100.0 * width)) \
            if pct else ""
        lines.append(
            f"  {gname:<15} {g['seconds']:>8.3f}s "
            f"{g['achieved_gbps']:>9.2f} GB/s ({_pct_text(pct)} roofline) "
            f"{g['achieved_gflops']:>9.2f} GFLOP/s  {g['bound']}-bound"
            f"  waste<={g['padding_waste_ratio'] * 100:.0f}%"
            + (f"  {bar}" if bar else ""))
    t = doc.get("total") or {}
    if t:
        lines.append(
            f"  {'total':<15} {t['seconds']:>8.3f}s "
            f"{t['achieved_gbps']:>9.2f} GB/s "
            f"({_pct_text(t['roofline_pct_bw'])} roofline) "
            f"over {sum(g['dispatches'] for g in doc['groups'].values())}"
            f" audited dispatches")
    sh = doc.get("shards")
    if sh:
        rows = sh.get("rows_per_shard") or []
        lines.append(
            f"  {'shards':<15} n={sh['n_shards']} "
            f"waves={sh['waves']} skew={sh['skew']:.2f}x "
            f"rows/shard=[{', '.join(str(r) for r in rows)}]")
    return lines


def records_doc(limit: int = 0) -> List[dict]:
    """Flat view of the audit record table (report tools): one row per
    (entry, shape)."""
    out = []
    with _LOCK:
        for entry_key, shapes in _RECORDS.items():
            for sig, rec in shapes.items():
                out.append({
                    "family": entry_key[0],
                    "shape_sig": repr(sig),
                    "row_capacity": rec.get("row_capacity"),
                    "in_bytes": rec.get("in_bytes"),
                    "out_bytes": rec.get("out_bytes"),
                    "flops": rec.get("flops"),
                    "bytes_accessed": rec.get("bytes_accessed"),
                    "max_padding_waste": max_padding_waste(
                        rec.get("row_capacity") or 0),
                    "error": rec.get("error"),
                })
    out.sort(key=lambda r: (-(r["bytes_accessed"] or 0), r["family"]))
    return out[:limit] if limit else out
