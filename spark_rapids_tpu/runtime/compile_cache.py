"""The sanctioned compile choke point: one warm-trace cache for the engine.

Every XLA compilation the engine triggers routes through this module —
tpulint TPU-L010 enforces it the way TPU-L002 funnels threads through
host_pool.py. Three layers, cheapest first:

1. **Warm-trace cache** (``get``): a process-wide executable cache keyed
   by (exec-class, semantic key, compile-relevant conf fingerprint).
   ``exec/fuse.py`` and ``exec/compiled.py`` — i.e. every fused stage,
   absorbed aggregation, exchange kernel and expression stage — resolve
   their jitted entries here. A hit is one dict probe; a miss builds the
   jitted function, and its FIRST execution (which pays XLA trace +
   compile, dominating the batch's compute 10x+) is timed into the
   attribution ``compile`` bucket before the raw jitted function swaps
   into the cache, so steady-state dispatches pay nothing.

2. **Sanctioned jit sites** (``jit``): module-level kernels with stable
   signatures (gather/compact/slice helpers in ops/) decorate through
   this thin wrapper — jax.jit's own signature cache keys them by
   (bucketed shapes, dtypes, static args), which is exactly the
   shape-canonicalization contract of runtime/shapes.py. The wrapper
   adds ZERO per-call overhead (it returns the PjitFunction itself);
   what it buys is the single audited compile entry point.

3. **Global compile accounting**: a jax.monitoring listener observes
   every backend compile in the process — including re-traces under an
   existing jit entry when a NEW shape bucket arrives, which no
   first-call timer can see — and feeds hit/miss/compile-second
   counters to the obs registry, the attribution ``compile`` bucket
   (only for compiles outside a first-call timing window: those are
   already attributed wholesale), and trace instants. The same listener
   counts the persistent compilation cache's cross-process hits and
   misses, which ``tools/compile_smoke.py`` CI-gates.

The persistent layer (jax's compilation cache) makes compiled
executables survive the process: a restarted engine pays trace +
deserialize, not a backend compile. ``configure`` is the ONE place that
decides its directory and entry thresholds: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (then no directory is set in code), else
``spark.rapids.compile.cacheDir``, else a fixed ``.jax_cache`` inside
the checkout (no default on the CPU simulator). jax config is process-global,
so the first session to configure it wins.

Pallas kernels are not jit entries — ``pl.pallas_call`` lowers inside an
enclosing traced computation — so they cannot route through ``get``;
instead the modules allowed to contain pallas_call sites are rostered
here (``SANCTIONED_PALLAS_MODULES``, the TPU-L008 SITES pattern) and
TPU-L010 flags the call anywhere else.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax

from spark_rapids_tpu.analysis import sanitizer as _san
from spark_rapids_tpu.runtime.obs import attribution as _attr

#: modules allowed to contain raw ``pl.pallas_call`` sites (tpulint
#: TPU-L010 AST-extracts this roster): the hand-tiled kernel homes,
#: whose public entries are invoked beneath computations that DID route
#: through this cache.
SANCTIONED_PALLAS_MODULES = (
    "ops/pallas_decode.py",
    "ops/pallas_kernels.py",
    "ops/pallas_segsum.py",
)

_CACHE: Dict[Tuple, Callable] = {}
_LOCK = _san.lock("runtime.compile_cache")

#: plain-int counters: hits/misses bump without a lock (a lost update
#: under the GIL costs a count, never correctness; `misses` and
#: `compile_ns` only move under _LOCK / the first-call swap, so the
#: determinism tests' "zero new compiles" assertions are exact)
_STATS = {
    "hits": 0,            # warm-trace cache hits (get)
    "misses": 0,          # fresh entries built (get)
    "compile_ns": 0,      # summed first-call walls of fresh entries
    "xla_compiles": 0,    # backend compiles observed process-wide
    "xla_compile_ns": 0,  # summed backend-compile durations
    "persistent_hits": 0,    # persistent-cache executable loads
    "persistent_misses": 0,  # compile requests the persistent layer missed
    # facts of TRACES, bumped by the traced code itself (note_traced):
    # once where a program is traced, never at a dispatch. In the
    # programs this process traced: string comparisons answered on a
    # dict column's vocabulary (expr/core._vocab_eq_literal), and dict
    # columns flattened at the static bound capacity x vocab bytes
    # (ops/kernels.flatten_dict_column)
    "vocab_predicates_traced": 0,
    "dict_flattens_traced": 0,
    # a string matched against literal runs by passes over the byte plane
    # (ops/strmatch.match_runs), and a LIKE that went to the NFA's loop
    # over row positions (expr/strings.Like: a `_` in the pattern)
    "like_plane_traced": 0,
    "like_nfa_traced": 0,
    # a tiny-bucket aggregate's float sums and counts contracted as exact
    # integer digits on the MXU in one pass (ops/groupby.bucket_digit_agg)
    "bucket_digit_traced": 0,
    # live rows' positions found 32 rows a word (ops/kernels._compact_indices)
    "compact_words_traced": 0,
}

#: set while a fresh entry's first call runs on this thread: the
#: monitoring listener must not ALSO attribute that compile (the whole
#: first-call wall already lands in the 'compile' bucket)
_TLS = threading.local()

_MONITORING_INSTALLED = False
#: the directory ``configure`` placed the persistent cache at (None
#: until a session places one — on the CPU simulator, possibly never)
_PLACED: Optional[str] = None
#: conf directories already reported as ignored (log once each)
_IGNORED: set = set()

#: the default persistent-cache directory: a FIXED path inside the
#: checkout (the directory is part of what a chip-tool copy carries and
#: must not move between processes) — never /tmp, a uid, a pid or a time
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: the kernel cost auditor (analysis/kernel_audit.py) when armed, else
#: None: get() notes every keyed resolution (one call per dispatch) and
#: wraps fresh traced bodies so (entry, shape) costs are audited at
#: trace time. Disabled cost: this one module-global None check — the
#: fuse._DISPATCH_HOOK pattern.
_AUDITOR = None


def set_auditor(mod) -> None:
    """Arm/disarm the kernel cost auditor (kernel_audit.configure)."""
    global _AUDITOR
    _AUDITOR = mod


#: fingerprint of the most recently ACTIVATED session conf: the
#: fallback for threads that never had a conf bound thread-locally.
#: Task-wave threads inherit the submitter's conf (host_pool binds it),
#: so this fallback only decides for stragglers (service threads) —
#: concurrent sessions with DIFFERENT compile-relevant confs racing on
#: an unbound thread share the tracer-singleton known limit.
_FALLBACK_FP: Tuple = (False, True)


def publish_conf(conf) -> None:
    """Called by config.set_session_conf: refresh the unbound-thread
    fallback fingerprint."""
    global _FALLBACK_FP
    _FALLBACK_FP = _fp_of(conf)


def _fp_of(c) -> Tuple:
    from spark_rapids_tpu import config as C
    fp = getattr(c, "_compile_fp", None)
    if fp is None:
        fp = (bool(c.get(C.ANSI_ENABLED)),
              bool(c.get(C.IMPROVED_FLOAT_OPS)))
        from spark_rapids_tpu.parallel import mesh as _mesh
        if _mesh.multichip_on(c):
            # sharded executables trace against a specific mesh shape:
            # 1-dev and 8-dev sessions must never share an entry. The
            # component is appended ONLY while multichip is on, so
            # default-path keys (and every artifact derived from them)
            # stay byte-identical to pre-multichip builds. RapidsConf.set
            # pops the memo, so flipping the conf re-fingerprints.
            fp = fp + ("mesh",) + _mesh.mesh_fingerprint(c)
        try:
            c._compile_fp = fp
        except Exception:  # noqa: BLE001 - a frozen conf object just
            pass  # recomputes the two lookups per call
    return fp


def _conf_fingerprint() -> Tuple:
    """The compile-relevant slice of the active session conf, folded
    into every warm-trace key: two sessions whose traced bodies differ
    (ANSI error planes, float-op orderings) must never share an
    executable. Reads the THREAD-BOUND conf when one exists (collect
    threads via set_session_conf, task-wave threads via the host_pool
    binding); a thread with no binding uses the last-activated
    session's fingerprint — never the registry defaults, which would
    split one query's entries across two fingerprints by thread."""
    from spark_rapids_tpu import config as C
    c = getattr(C._local, "conf", None)
    if c is None:
        return _FALLBACK_FP
    return _fp_of(c)


# ---------------------------------------------------------------------------
# layer 1: the warm-trace cache
# ---------------------------------------------------------------------------

def get(exec_class: str, key: Tuple, builder: Callable[[], Callable]
        ) -> Callable:
    """Resolve (exec-class, key, conf-fingerprint) to a jitted callable,
    building it from `builder` on a miss. The first call of a fresh
    entry is timed into the attribution 'compile' bucket and the
    entry's raw jitted function then swaps into the cache."""
    fp = _conf_fingerprint()
    full_key = (exec_class, key, fp)
    # ONE read of the auditor global per call (the fuse._DISPATCH_HOOK
    # pattern): a concurrent disarm (another session's configure) must
    # not crash a dispatch between the None check and the note
    auditor = _AUDITOR
    fn = _CACHE.get(full_key)
    if fn is not None:
        _STATS["hits"] += 1
        if auditor is not None:
            auditor.note(full_key)
        return fn
    # the compile choke point is the last cooperative checkpoint before
    # an UNINTERRUPTIBLE stretch: a fresh build's first call parks in
    # the XLA compiler for seconds, where no cancel token can reach.
    # Check before building so a cancelled query's task thread never
    # enters a compile it cannot leave (the test_cancel leak-sweep
    # flake: reaping waited out exactly these parked threads). The hit
    # path above stays checkpoint-free — it is the per-dispatch path.
    from spark_rapids_tpu.runtime import lifecycle as _lc
    _lc.check_current()
    body = builder()
    bind = None
    if auditor is not None:
        # trace-time cost audit: jax executes the wrapped Python body
        # only while tracing (once per shape signature, re-traces
        # included), so steady-state dispatches never touch it
        body, bind = auditor.wrap_traced(exec_class, key, fp, body)
    jfn = jax.jit(body)  # the ONE sanctioned keyed jit site
    if bind is not None:
        bind(jfn)
        auditor.note(full_key)  # the build's first call is a dispatch
    wrapped = _timed_first_call(full_key, jfn)
    with _LOCK:
        fn = _CACHE.get(full_key)
        if fn is not None:  # lost a build race: the first entry wins
            _STATS["hits"] += 1
            return fn
        _STATS["misses"] += 1
        _CACHE[full_key] = wrapped
    return wrapped


def _timed_first_call(full_key: Tuple, jfn: Callable) -> Callable:
    """Attribute the first execution of a fresh entry to the 'compile'
    bucket: the first call pays XLA trace+compile (7-11s first-run vs
    0.6s steady on NDS — compile dominates that batch's compute 10x+).
    After it completes, the raw jitted fn swaps into the cache so
    steady-state dispatches pay nothing."""
    done = [False]

    def first(*args, **kwargs):
        # last checkpoint before the backend compile itself: get()'s
        # check covered the build, but the entry may have been built by
        # an earlier (cancelled) call and left unexecuted — raising
        # here leaves done[0] unconsumed, so an uncancelled retry still
        # records the compile and swaps in the raw fn
        from spark_rapids_tpu.runtime import lifecycle as _lc
        _lc.check_current()
        _TLS.in_first_call = getattr(_TLS, "in_first_call", 0) + 1
        t0 = time.perf_counter_ns()
        try:
            out = jfn(*args, **kwargs)
        finally:
            _TLS.in_first_call -= 1
        # claim AFTER success, under the lock: a raised first call (an
        # OOM the retry framework replays, a trace failure the fallback
        # catches) must leave the claim unconsumed so the successful
        # retry still records the compile and swaps in the raw fn; and
        # two task threads completing the same fresh entry concurrently
        # must record the compile wall exactly once
        with _LOCK:
            claimed = not done[0]
            done[0] = True
        if claimed:
            dt = time.perf_counter_ns() - t0
            _CACHE[full_key] = jfn
            _STATS["compile_ns"] += dt
            _attr.record("compile", dt)
        return out

    return first


def clear() -> None:
    """Drop every warm-trace entry (tests; also releases any device
    buffers pinned by jitted closures)."""
    with _LOCK:
        _CACHE.clear()


def reset_stats_for_tests() -> None:
    for k in _STATS:
        _STATS[k] = 0


def note_traced(counter: str) -> None:
    """Bump one of the `*_traced` counters; the traced code calls this."""
    _STATS[counter] += 1


def stats() -> Dict[str, int]:
    """A point-in-time copy of the compile counters (the /healthz
    compile document and the smoke gates read this)."""
    out = dict(_STATS)
    out["entries"] = len(_CACHE)
    # the directory jax is ACTUALLY using (environment, conf or the
    # default), not only one this module set
    out["persistent_dir"] = jax.config.jax_compilation_cache_dir or None
    return out


def cache_keys() -> list:
    """Snapshot of warm-trace keys (profiling tools)."""
    return list(_CACHE.keys())


# ---------------------------------------------------------------------------
# layer 2: sanctioned module-level jit sites
# ---------------------------------------------------------------------------

def jit(fn: Optional[Callable] = None, **jit_kwargs) -> Callable:
    """Decorator/wrapper for module-level kernels with stable
    signatures: ``@compile_cache.jit(static_argnums=(2,))``. Applies
    jax.jit directly — jax's own signature cache keys the executable by
    (bucketed shapes, dtypes, statics), and the process-wide monitoring
    listener accounts any compile it triggers — so calls cost exactly
    what a raw jax.jit call would.

    The kernel cost auditor's wrapper rides INSIDE the traced body
    (installed unconditionally here because decoration happens at
    import, before any conf exists): it runs only while jax traces and
    checks the armed flag then, so per-call cost stays exactly one
    PjitFunction invocation. functools.wraps preserves the kernel's
    signature for static_argnames resolution."""
    if fn is None:
        return lambda f: jit(f, **jit_kwargs)
    from spark_rapids_tpu.analysis import kernel_audit as _ka
    body, bind = _ka.wrap_kernel(fn)
    jfn = jax.jit(body, **jit_kwargs)  # the ONE sanctioned raw-jit site
    bind(jfn)
    return jfn


# ---------------------------------------------------------------------------
# layer 3: process-wide compile accounting + the persistent layer
# ---------------------------------------------------------------------------

def _on_compile_duration(event: str, duration_secs: float, **kw) -> None:
    # fires on every backend compile in the process, including jax.jit
    # signature-cache re-traces this module's keyed layer cannot see
    if not event.endswith("backend_compile_duration"):
        return
    ns = int(duration_secs * 1e9)
    _STATS["xla_compiles"] += 1
    _STATS["xla_compile_ns"] += ns
    try:
        from spark_rapids_tpu.runtime import obs as _obs
        st = _obs.state()
        if st is not None:
            st.registry.counter("rapids_xla_compiles_total").inc()
            st.registry.float_counter(
                "rapids_xla_compile_seconds_total").inc(duration_secs)
    except Exception:  # noqa: BLE001 - accounting never fails a compile
        pass
    if not getattr(_TLS, "in_first_call", 0):
        # a re-trace outside any first-call window (a NEW shape bucket
        # arriving at an existing entry): attribute it, or it smears
        # into device_compute and hides exactly the recompiles the
        # shape-bucketing policy exists to kill
        _attr.record("compile", ns)


def _on_cache_event(event: str, **kw) -> None:
    if event.endswith("/cache_hits"):
        _STATS["persistent_hits"] += 1
        name = "rapids_persistent_cache_hits_total"
    elif event.endswith("/cache_misses"):
        _STATS["persistent_misses"] += 1
        name = "rapids_persistent_cache_misses_total"
    else:
        return
    try:
        from spark_rapids_tpu.runtime import obs as _obs
        st = _obs.state()
        if st is not None:
            st.registry.counter(name).inc()
    except Exception:  # noqa: BLE001 - accounting never fails a compile
        pass


def _install_monitoring() -> None:
    """Register the process-wide jax.monitoring listeners once. They
    fire only when XLA actually compiles or consults the persistent
    cache — zero steady-state cost."""
    global _MONITORING_INSTALLED
    if _MONITORING_INSTALLED:
        return
    with _LOCK:
        if _MONITORING_INSTALLED:
            return
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        jax.monitoring.register_event_listener(_on_cache_event)
        _MONITORING_INSTALLED = True


_install_monitoring()


def _on_cpu_simulator() -> bool:
    """True when jax's default backend is the CPU. Asks jax itself (this
    initialises the backend, which a session is about to use anyway): a
    platform list such as "tpu,cpu" names the CPU without running on
    it."""
    return jax.default_backend() == "cpu"


def configure(conf) -> None:
    """Place the persistent compilation cache (idempotent; called from
    every TpuSession.__init__) — the ONE site that decides its directory
    and entry thresholds. Precedence: ``JAX_COMPILATION_CACHE_DIR`` (jax
    reads it itself; no directory is set in code and
    ``spark.rapids.compile.cacheDir`` only logs that the environment
    wins), then the conf, then DEFAULT_CACHE_DIR. jax config is
    process-global: the first placement wins, and a later session naming
    a DIFFERENT directory keeps the first (logged once)."""
    global _PLACED
    from spark_rapids_tpu import config as C
    log = logging.getLogger("spark_rapids_tpu")
    d = str(conf.get(C.COMPILE_CACHE_DIR) or "").strip()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if d and d != (env or _PLACED or d) and d not in _IGNORED:
        _IGNORED.add(d)
        log.warning(
            "spark.rapids.compile.cacheDir=%s ignored: %s", d,
            f"JAX_COMPILATION_CACHE_DIR={env} wins" if env else
            f"the process persistent cache is already {_PLACED}")
    if _PLACED:
        return
    if env:
        d = env
    elif not d and not _on_cpu_simulator():
        d = DEFAULT_CACHE_DIR
    # no default on the CPU simulator: the suite's compile-count
    # assertions need every process to start cold, and CPU compiles are
    # cheap enough to redo (an explicit conf or env dir still applies)
    if d:
        if not env:
            os.makedirs(d, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", d)
        # the engine's computations are many and individually small:
        # cache everything (jax's defaults skip sub-second / sub-size
        # entries, which is most of an analytic plan's kernel zoo)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _PLACED = d


def doc() -> Dict[str, object]:
    """The /healthz compile document."""
    s = stats()
    return {
        "warm_entries": s["entries"],
        "hits": s["hits"],
        "misses": s["misses"],
        "compile_seconds": round(s["compile_ns"] / 1e9, 3),
        "xla_compiles": s["xla_compiles"],
        "xla_compile_seconds": round(s["xla_compile_ns"] / 1e9, 3),
        "persistent_dir": s["persistent_dir"],
        "persistent_hits": s["persistent_hits"],
        "persistent_misses": s["persistent_misses"],
        "vocab_predicates_traced": s["vocab_predicates_traced"],
        "dict_flattens_traced": s["dict_flattens_traced"],
        "bucket_digit_traced": s["bucket_digit_traced"],
        "compact_words_traced": s["compact_words_traced"],
    }
