"""Sanitizer smoke: the concurrency sanitizer must be FREE when disabled
and sharp when enabled.

Gate 1 (overhead, the tracing bar): the total cost the DISABLED lock
proxies add to one drive of the unfused Filter→Project chain
(tools/stage_harness.py's dispatch-bound shape — every batch acquires the
TPU semaphore, so the drive generates real sanitized-lock traffic) must
be under --tolerance (2%) of the drive's wall time. Same method as
tools/trace_overhead.py, for the same reason (run-to-run noise on shared
CI machines is ±10%+, an order of magnitude above the quantity under
test):

1. count how many sanitized acquire/release pairs one drive performs
   (class-level counting wrappers, sanitizer disabled);
2. measure the proxy's DISABLED per-cycle cost minus a raw
   threading.Lock cycle over 10^5 tight-loop iterations;
3. overhead = pairs × max(delta, 0) against best-of drive time.

Gate 2 (detection): with the sanitizer enabled, a seeded ABBA lock
inversion and a seeded held-lock blocking call must BOTH be reported —
and a re-run of the engine drive must report nothing (the clean engine
stays clean under instrumentation).

Run:  python tools/sanitizer_smoke.py [--rows 400000] [--batch 2048]
                                      [--reps 9] [--tolerance 0.02]

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import stage_harness as SH  # noqa: E402


def _count_lock_ops(san, drive):
    """Sanitized acquire/release counts for one drive (sanitizer stays
    disabled; the wrappers call through)."""
    counts = {"acquire": 0, "release": 0}
    orig_acq = san._SanLock.acquire
    orig_rel = san._SanLock.release

    def acq(self, blocking=True, timeout=-1):
        counts["acquire"] += 1
        return orig_acq(self, blocking, timeout)

    def rel(self):
        counts["release"] += 1
        return orig_rel(self)

    san._SanLock.acquire = acq
    san._SanLock.release = rel
    try:
        drive()
    finally:
        san._SanLock.acquire = orig_acq
        san._SanLock.release = orig_rel
    return counts


def _per_cycle_delta(san, iters=100_000):
    """Disabled-path cost of one proxy acquire+release cycle MINUS a raw
    threading.Lock cycle, in seconds (clamped >= 0)."""
    raw = threading.Lock()
    proxy = san.lock("smoke.timing")

    def loop(lk):
        t0 = time.perf_counter()
        for _ in range(iters):
            lk.acquire()
            lk.release()
        return (time.perf_counter() - t0) / iters

    base = min(loop(raw) for _ in range(3))
    cost = min(loop(proxy) for _ in range(3))
    return max(cost - base, 0.0), base, cost


def _seeded_findings(san):
    """Enabled run over two deliberate bugs: ABBA inversion + held-lock
    blocking. Returns the kinds reported."""
    san.uninstall()
    san.install(hold_warn_ms=5.0)
    try:
        a, b = san.lock("smoke.A"), san.lock("smoke.B")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        hold = san.lock("smoke.hold")
        with hold:
            time.sleep(0.02)  # stand-in for I/O under the lock
        return sorted({f["kind"] for f in san.report()["findings"]})
    finally:
        san.uninstall()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--tolerance", type=float, default=0.02)
    args = ap.parse_args()

    from spark_rapids_tpu.analysis import sanitizer as san

    san.uninstall()  # the overhead half measures the DISABLED path

    drive = SH.make_chain_stage(args.rows, args.batch, fused=False)
    drive()  # warm kernel caches before measuring

    drive_s = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        drive()
        drive_s.append(time.perf_counter() - t0)
    drive_best = min(drive_s)

    counts = _count_lock_ops(san, drive)
    delta, base_cycle, proxy_cycle = _per_cycle_delta(san)
    pairs = max(counts["acquire"], counts["release"])
    added_s = pairs * delta
    overhead = added_s / drive_best

    kinds = _seeded_findings(san)

    # clean-engine check: the instrumented drive must report nothing
    san.install(hold_warn_ms=250.0)
    try:
        drive()
        clean = san.report()["findings"]
    finally:
        san.uninstall()

    result = {
        "drive_best_s": round(drive_best, 5),
        "lock_ops_per_drive": counts,
        "raw_cycle_ns": round(base_cycle * 1e9, 1),
        "proxy_cycle_ns": round(proxy_cycle * 1e9, 1),
        "per_cycle_delta_ns": round(delta * 1e9, 1),
        "disabled_overhead_s": round(added_s, 7),
        "disabled_overhead_pct": round(overhead * 100, 4),
        "tolerance_pct": args.tolerance * 100,
        "seeded_findings": kinds,
        "clean_engine_findings": len(clean),
    }
    print(json.dumps(result))

    ok = True
    if counts["acquire"] == 0:
        print("FAIL: drive performed no sanitized lock operations — the "
              "overhead gate is vacuous", file=sys.stderr)
        ok = False
    if overhead > args.tolerance:
        print(f"FAIL: disabled-sanitizer overhead {overhead * 100:.3f}% "
              f"exceeds {args.tolerance * 100:.1f}%", file=sys.stderr)
        ok = False
    if "lock-inversion" not in kinds or "held-lock-blocking" not in kinds:
        print(f"FAIL: seeded bugs not both reported (got {kinds}; need "
              f"lock-inversion AND held-lock-blocking)", file=sys.stderr)
        ok = False
    if clean:
        print(f"FAIL: clean engine drive produced {len(clean)} "
              f"finding(s): {json.dumps(clean)}", file=sys.stderr)
        ok = False
    if not ok:
        return 1
    print(f"PASS: disabled-sanitizer overhead {overhead * 100:.3f}% of "
          f"the drive ({pairs} lock cycles, tolerance "
          f"{args.tolerance * 100:.1f}%); seeded inversion + held-lock "
          f"both caught; clean engine silent")
    return 0


if __name__ == "__main__":
    sys.exit(main())
