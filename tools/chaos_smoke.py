"""Chaos smoke: seeded fault injection over NDS probe queries.

The failure-domain acceptance gate (the robustness twin of
sanitizer_smoke/trace_overhead):

Gate 1 (overhead, the tracing bar): the DISABLED fault hooks
(`faults.site`/`site_bytes` with no schedule armed — one module-global
read each; the watchdog adds literally nothing when off because
exec/fuse.py returns the raw jitted function) must cost under
--tolerance (2%) of a clean query drive. Same methodology as
tools/sanitizer_smoke.py: count hook passes in one drive, measure the
disabled per-pass cost minus an empty-call baseline over tight-loop
iterations, multiply.

Gate 2 (chaos): with a FIXED seed, run the probe query set under
randomized injection schedules (spec strings generated from the seeded
RNG — a failing schedule is reproducible from the seed alone) until at
least --min-faults faults have fired across at least --min-sites
distinct sites. EVERY run must end status ok or degraded with results
identical to the clean run of the same query — never a wrong answer,
never an unhandled failure.

Gate 3 (no hangs, no leaks): the whole smoke runs under a global
deadline enforced by a watchdog thread (stack dump + hard exit on
breach), and the thread census at the end must contain nothing beyond
the sanctioned long-lived services (host pool, obs, watchdog) — a
leaked pipeline refill or task thread fails the gate.

Gate 4 (cancellation storm, PR 12): seeded cancels delivered
mid-scan/mid-shuffle/mid-retry (query.cancel:cancel schedules at random
checkpoint passes), externally mid-flight (session.cancel from another
thread), and while-queued (admission gate at maxConcurrent=1) across
--cancel-runs NDS runs. Every cancelled query must land the `cancelled`
terminal state within 2x the longest measured checkpoint interval
(lifecycle's probe), with zero leaked threads, zero stranded semaphore
permits, device_bytes_held() back to baseline, and surviving queries'
results identical to clean. The overhead half of gate 1 also prices the
always-on lifecycle checkpoint (count x delta, same bar).

Run:  python tools/chaos_smoke.py [--seed 20260803] [--sf 0.002]
          [--max-rounds 14] [--min-faults 200] [--min-sites 6]
          [--cancel-runs 20] [--deadline 480] [--tolerance 0.02]

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import nds_probe as NDS  # noqa: E402

from spark_rapids_tpu import config as C  # noqa: E402
from spark_rapids_tpu.runtime import faults, watchdog  # noqa: E402
from spark_rapids_tpu.runtime import lifecycle  # noqa: E402
from spark_rapids_tpu.sql.session import TpuSession  # noqa: E402

#: probe queries: join + aggregate shapes so exchanges, retries, spills
#: and pipelines all engage (broadcast disabled below forces the joins
#: through real SERIALIZED shuffles)
CHAOS_QUERIES = (3, 7, 42, 52, 55)

#: (site, eligible kinds) the schedule generator draws from. Kind
#: weights favor delay (fires without failing the run, so fault volume
#: accumulates fast) while keeping every failure class in rotation.
SITE_KINDS = (
    ("scan.decode", ("delay", "delay", "ioerror", "oom")),
    ("shuffle.read", ("delay", "corrupt", "corrupt", "ioerror")),
    ("shuffle.write", ("delay", "delay", "corrupt")),
    ("spill.disk", ("delay", "delay", "ioerror")),
    ("device.dispatch", ("delay", "delay", "wedge", "oom")),
    ("pipeline.producer", ("delay", "delay", "ioerror", "oom")),
    ("exchange.fetch", ("delay", "delay", "ioerror")),
    ("retry.oom", ("oom",)),
)

CHAOS_CONF = {
    # real serialized shuffles (blob integrity, store spill) on every
    # exchange; broadcast disabled so the probe joins actually shuffle
    "spark.rapids.shuffle.mode": "SERIALIZED",
    "spark.rapids.sql.join.broadcastRowThreshold": "1",
    "spark.rapids.sql.adaptive.enabled": "false",
    "spark.rapids.sql.reader.batchSizeRows": "2048",
    # tiny store budget: every few blobs spill to disk (spill.disk site)
    "spark.rapids.shuffle.hostSpillBudget": "8192",
    "spark.rapids.fallback.cpu.enabled": "true",
    "spark.rapids.watchdog.enabled": "true",
    # wedge (1.0s) ABOVE the watchdog timeout (0.6s): every wedge-kind
    # fault must drive the full wedge -> watchdogDispatchTimeout ->
    # breaker-failure path, not just sleep unnoticed. Steady dispatches
    # stay well under 0.6s; a first-compile overshoot merely adds a
    # harmless report against the high breaker threshold.
    "spark.rapids.watchdog.dispatchTimeoutSeconds": "0.6",
    # chaos wants the DEVICE path exercised every round: a latched-open
    # breaker would route everything to CPU and starve the fault sites
    "spark.rapids.watchdog.breakerFailureThreshold": "1000",
    "spark.rapids.retry.backoffBaseMs": "1",
    "spark.rapids.debug.faults.delayMs": "5",
    "spark.rapids.debug.faults.wedgeSeconds": "1.0",
}


def _arm_deadline(seconds: float):
    """Global hang-breaker: past the deadline, dump every thread's stack
    and hard-exit — a wedged chaos run must fail loudly, not hang CI."""
    done = threading.Event()

    def trip():
        if not done.wait(seconds):
            print(f"FAIL: chaos smoke exceeded the {seconds:.0f}s global "
                  f"deadline — dumping stacks", file=sys.stderr)
            faulthandler.dump_traceback(file=sys.stderr)
            os._exit(3)

    t = threading.Thread(target=trip, name="chaos-deadline", daemon=True)
    t.start()
    return done


def _gen_spec(rng: random.Random) -> str:
    """One round's injection schedule: 2-4 entries drawn from the
    site/kind table with small counts and skips."""
    n = rng.randint(2, 4)
    parts = []
    for _ in range(n):
        site, kinds = SITE_KINDS[rng.randrange(len(SITE_KINDS))]
        kind = kinds[rng.randrange(len(kinds))]
        count = rng.randint(1, 4)
        skip = rng.randint(0, 2)
        parts.append(f"{site}:{kind}:{count},{skip}")
    return ";".join(parts)


def _canon(table):
    return NDS._canon_rows(table)


def _overhead_gate(session, dfs, tolerance: float) -> dict:
    """Gate 1: disabled-hook cost of one clean drive (sanitizer_smoke
    methodology) — the fault sites AND the always-on lifecycle
    cancellation checkpoint, priced together against the same bar."""
    session.conf.set(C.FAULTS_SPEC, "")
    session.conf.set(C.WATCHDOG_ENABLED, False)

    def drive():
        NDS.QUERIES[CHAOS_QUERIES[0]](session, dfs).collect()

    drive()  # warm kernel caches
    best = min((lambda t0=time.perf_counter(): (drive(),
                time.perf_counter() - t0)[1])() for _ in range(3))

    counts = {"passes": 0, "lc_passes": 0}
    orig_site, orig_bytes = faults.site, faults.site_bytes
    orig_check = lifecycle.check_current

    def csite(name):
        counts["passes"] += 1
        return orig_site(name)

    def cbytes(name, data):
        counts["passes"] += 1
        return orig_bytes(name, data)

    def ccheck():
        counts["lc_passes"] += 1
        return orig_check()

    faults.site, faults.site_bytes = csite, cbytes
    lifecycle.check_current = ccheck
    try:
        drive()
    finally:
        faults.site, faults.site_bytes = orig_site, orig_bytes
        lifecycle.check_current = orig_check

    def loop(fn, *args, iters=100_000):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(*args)
        return (time.perf_counter() - t0) / iters

    def baseline(*_args):
        return None

    base = min(loop(baseline, "scan.decode") for _ in range(3))
    cost = min(loop(orig_site, "scan.decode") for _ in range(3))
    delta = max(cost - base, 0.0)
    # the checkpoint's real in-query cost: a live token registered and
    # bound to the measuring thread (the clean-path worst case — the
    # no-query fast path is a single dict truthiness read)
    tok = lifecycle.begin_action(None, session.conf)
    try:
        base0 = min(loop(baseline) for _ in range(3))
        lc_cost = min(loop(orig_check) for _ in range(3))
    finally:
        lifecycle.finish_action(tok, "ok")
    lc_delta = max(lc_cost - base0, 0.0)
    added = counts["passes"] * delta + counts["lc_passes"] * lc_delta
    overhead = added / best if best else 0.0
    return {
        "drive_best_s": round(best, 5),
        "hook_passes_per_drive": counts["passes"],
        "per_pass_delta_ns": round(delta * 1e9, 1),
        "lifecycle_passes_per_drive": counts["lc_passes"],
        "lifecycle_per_pass_delta_ns": round(lc_delta * 1e9, 1),
        "disabled_overhead_pct": round(overhead * 100, 4),
        "ok": (counts["passes"] > 0 and counts["lc_passes"] > 0
               and overhead <= tolerance),
    }


def _cancel_storm(session, dfs, expected, rng: random.Random,
                  n_runs: int) -> dict:
    """Gate 4: the seeded cancellation storm. Four delivery modes cycle
    across n_runs: `site` (a query.cancel:cancel schedule fires at a
    random checkpoint pass — mid-scan/mid-shuffle/mid-agg — sometimes
    stacked with retry OOMs so the cancel lands mid-retry), `external`
    (session.cancel from another thread mid-flight, latency measured),
    `queued` (admission gate at maxConcurrent=1, the parked query
    cancelled), and `survivor` (a clean run proving neighbors are
    untouched). Asserts the cancellation-latency bound, zero stranded
    permits, device bytes back to baseline, zero leaked tokens, and
    byte-identical surviving results."""
    from spark_rapids_tpu.runtime.lifecycle import QueryCancelledError
    from spark_rapids_tpu.runtime.memory import peek_spill_framework
    from spark_rapids_tpu.runtime.semaphore import peek_semaphore

    fw = peek_spill_framework()
    base_dev = fw.device_bytes_held() if fw is not None else 0
    lifecycle.set_checkpoint_probe(True)
    session.conf.set(C.FAULTS_SPEC, "")
    runs, failures, latencies = [], [], []
    slow_spec = "scan.decode:delay:80"

    def collect_one(qn, box):
        try:
            res = NDS.QUERIES[qn](session, dfs).collect()
            box["status"] = "ok"
            box["correct"] = _canon(res) == expected[qn]
        except QueryCancelledError as e:
            box["status"] = "cancelled"
            box["reason"] = e.reason
            box["correct"] = True  # a cancelled query returns nothing
        except BaseException as e:  # noqa: BLE001 - the gate inspects
            box["status"] = "raised:" + type(e).__name__
            box["correct"] = False
        box["done_mono"] = time.monotonic()

    def wait_for(cond, timeout=30.0):
        t0 = time.monotonic()
        while not cond():
            if time.monotonic() - t0 > timeout:
                return False
            time.sleep(0.005)
        return True

    for i in range(n_runs):
        qn = CHAOS_QUERIES[rng.randrange(len(CHAOS_QUERIES))]
        mode = ("site", "external", "queued", "survivor")[i % 4]
        rec = {"i": i, "q": qn, "mode": mode}
        if mode == "site":
            spec = f"query.cancel:cancel:1,{rng.randint(0, 120)}"
            if rng.random() < 0.5:
                spec += ";retry.oom:oom:2"  # cancel can land mid-retry
            session.conf.set(C.FAULTS_SPEC, spec)
            box = {}
            collect_one(qn, box)
            session.conf.set(C.FAULTS_SPEC, "")
            rec.update(box, spec=spec)
            # a skip past the query's total checkpoint passes completes
            # clean — that run doubles as a survivor check
            if box["status"] not in ("ok", "cancelled") \
                    or not box["correct"]:
                failures.append(rec)
        elif mode == "external":
            session.conf.set(C.FAULTS_SPEC, slow_spec)
            box = {}
            th = threading.Thread(target=collect_one, args=(qn, box))
            th.start()
            if not wait_for(lambda: lifecycle.token_ids()):
                failures.append(dict(rec, error="no token appeared"))
                th.join(60)
                continue
            time.sleep(rng.random() * 0.15)
            ids = lifecycle.token_ids()
            t_cancel = time.monotonic()
            fired = bool(ids) and session.cancel(ids[0], reason="storm")
            th.join(60)
            session.conf.set(C.FAULTS_SPEC, "")
            rec.update(box, fired=fired)
            if fired and box.get("status") == "cancelled":
                lat = box["done_mono"] - t_cancel
                latencies.append(lat)
                rec["latency_s"] = round(lat, 3)
            # raced completion (fired=False -> ok) is legal; anything
            # else outside ok/cancelled is not
            if box.get("status") not in ("ok", "cancelled") \
                    or not box.get("correct"):
                failures.append(rec)
        elif mode == "queued":
            session.conf.set(C.QUERY_MAX_CONCURRENT, 1)
            session.conf.set(C.FAULTS_SPEC, slow_spec)
            box_a, box_b = {}, {}
            tha = threading.Thread(target=collect_one, args=(qn, box_a))
            tha.start()
            if not wait_for(lambda: lifecycle.token_ids()):
                failures.append(dict(rec, error="A never started"))
                tha.join(60)
                session.conf.set(C.QUERY_MAX_CONCURRENT, 0)
                continue
            thb = threading.Thread(target=collect_one, args=(qn, box_b))
            thb.start()
            if not wait_for(
                    lambda: lifecycle.gate().doc()["queued"] == 1):
                failures.append(dict(rec, error="B never queued"))
            else:
                qb = max(lifecycle.token_ids())
                t_cancel = time.monotonic()
                session.cancel(qb, reason="storm")
                thb.join(60)
                if box_b.get("status") == "cancelled":
                    latencies.append(box_b["done_mono"] - t_cancel)
                else:
                    failures.append(dict(rec, b=dict(box_b),
                                         error="queued cancel missed"))
            tha.join(120)
            session.conf.set(C.FAULTS_SPEC, "")
            session.conf.set(C.QUERY_MAX_CONCURRENT, 0)
            rec.update(a=dict(box_a, done_mono=None),
                       b=dict(box_b, done_mono=None))
            if box_a.get("status") != "ok" or not box_a.get("correct"):
                failures.append(dict(rec, error="running neighbor "
                                     "disturbed by queued cancel"))
        else:  # survivor
            box = {}
            collect_one(qn, box)
            rec.update(box)
            if box["status"] != "ok" or not box["correct"]:
                failures.append(rec)
        runs.append(rec)

    lifecycle.set_checkpoint_probe(False)
    max_gap = lifecycle.checkpoint_max_gap_s()
    # terminal-latency bound: 2x the longest observed checkpoint
    # interval, plus a fixed epilogue allowance (the cancelled query
    # still flushes its trace/attribution/history after the unwind)
    bound = 2.0 * max_gap + 0.5
    over = [round(v, 3) for v in latencies if v > bound]
    cancelled_runs = sum(1 for r in runs if (r.get("status") == "cancelled"
                                             or (r.get("b") or {}).get(
                                                 "status") == "cancelled"))
    sem = peek_semaphore()
    stranded = 0 if sem is None else (sem.permits - sem.available)
    doc = {
        "runs": len(runs),
        "cancelled_runs": cancelled_runs,
        "max_checkpoint_gap_s": round(max_gap, 4),
        "latency_bound_s": round(bound, 4),
        "max_cancel_latency_s": round(max(latencies), 4) if latencies
        else None,
        "latencies_over_bound": over,
        "stranded_permits": stranded,
        "parked_waiters": 0 if sem is None else sem.waiting,
        "device_bytes_delta": (fw.device_bytes_held() - base_dev)
        if fw is not None else 0,
        "leaked_tokens": lifecycle.token_ids(),
        "failures": failures[:10],
        "ok": (not failures and not over and cancelled_runs >= n_runs // 3
               and stranded == 0
               and (sem is None or sem.waiting == 0)
               and not lifecycle.token_ids()
               and (fw is None
                    or fw.device_bytes_held() == base_dev)),
    }
    return doc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=20260803)
    ap.add_argument("--sf", type=float, default=0.002)
    ap.add_argument("--max-rounds", type=int, default=14)
    ap.add_argument("--min-faults", type=int, default=200)
    ap.add_argument("--min-sites", type=int, default=6)
    ap.add_argument("--cancel-runs", type=int, default=20)
    ap.add_argument("--deadline", type=float, default=480.0)
    ap.add_argument("--tolerance", type=float, default=0.02)
    args = ap.parse_args()

    deadline_done = _arm_deadline(args.deadline)
    threads_before = {t.name for t in threading.enumerate()}

    watchdog.uninstall_for_tests()
    faults.reset_counters()
    session = TpuSession(dict(CHAOS_CONF))
    dfs = {name: session.create_dataframe(t, num_partitions=2)
           for name, t in NDS.gen_tables(args.sf, seed=args.seed).items()}

    # gate 1 first: the overhead half measures DISABLED hooks, before
    # any chaos schedule or watchdog state exists
    ov = _overhead_gate(session, dfs, args.tolerance)
    session.conf.set(C.WATCHDOG_ENABLED, True)

    # clean expected results (same confs, no faults)
    session.conf.set(C.FAULTS_SPEC, "")
    expected = {}
    for qn in CHAOS_QUERIES:
        expected[qn] = _canon(NDS.QUERIES[qn](session, dfs).collect())
        assert session.last_action_status[0] == "ok", \
            f"clean run of q{qn} not ok: {session.last_action_status}"

    rng = random.Random(args.seed)
    runs = []
    failures = []
    rounds = 0
    while rounds < args.max_rounds:
        rounds += 1
        for qn in CHAOS_QUERIES:
            spec = _gen_spec(rng)
            session.conf.set(C.FAULTS_SPEC, spec)
            fired0 = faults.total_fired()
            t0 = time.perf_counter()
            try:
                result = NDS.QUERIES[qn](session, dfs).collect()
                status, reason = session.last_action_status
                correct = _canon(result) == expected[qn]
            except BaseException as e:  # noqa: BLE001 - a chaos run may
                # never raise: ok or degraded are the only legal ends
                status, reason, correct = "raised", type(e).__name__, False
            rec = {"q": qn, "spec": spec, "status": status,
                   "reason": reason, "correct": correct,
                   "fired": faults.total_fired() - fired0,
                   "seconds": round(time.perf_counter() - t0, 3)}
            runs.append(rec)
            if status not in ("ok", "degraded") or not correct:
                failures.append(rec)
        if faults.total_fired() >= args.min_faults and \
                len(faults.fault_counts()) >= args.min_sites:
            break

    # gate 4: the cancellation storm runs after the fault rounds (warm
    # caches keep its checkpoint intervals honest)
    session.conf.set(C.FAULTS_SPEC, "")
    faults.configure("")
    cancel_doc = _cancel_storm(session, dfs, expected, rng,
                               args.cancel_runs)

    session.conf.set(C.FAULTS_SPEC, "")
    faults.configure("")  # disarm leftovers before the thread census
    wedge_specs = sum(1 for r in runs if ":wedge" in r["spec"])
    from spark_rapids_tpu.runtime import obs
    st = obs.state()
    watchdog_timeouts = int(st.registry.counter(
        "rapids_watchdog_dispatch_timeouts_total").value) if st else 0
    watchdog.uninstall_for_tests()
    time.sleep(0.3)  # drained pool/service threads settle

    allowed = ("rapids-host-pool", "rapids-obs", "rapids-task",
               "rapids-query-deadline", "chaos-deadline", "pymain",
               "MainThread")
    leaked = sorted(
        t.name for t in threading.enumerate()
        if t.name not in threads_before
        and not any(t.name.startswith(p) for p in allowed))

    counts = faults.fault_counts()
    result = {
        "seed": args.seed,
        "rounds": rounds,
        "runs": len(runs),
        "faults_fired": faults.total_fired(),
        "distinct_sites": sorted(counts),
        "per_site": counts,
        "degraded_runs": sum(1 for r in runs if r["status"] == "degraded"),
        "ok_runs": sum(1 for r in runs if r["status"] == "ok"
                       and r["correct"]),
        "failures": failures[:10],
        "leaked_threads": leaked,
        "wedge_specs": wedge_specs,
        "watchdog_timeouts": watchdog_timeouts,
        "overhead": ov,
        "cancel_storm": cancel_doc,
    }
    print(json.dumps(result))

    ok = True
    if failures:
        print(f"FAIL: {len(failures)} chaos run(s) ended outside "
              f"ok/degraded or with wrong results:\n"
              + "\n".join(json.dumps(f) for f in failures[:10]),
              file=sys.stderr)
        ok = False
    if result["faults_fired"] < args.min_faults:
        print(f"FAIL: only {result['faults_fired']} faults fired "
              f"(need >= {args.min_faults})", file=sys.stderr)
        ok = False
    if len(counts) < args.min_sites:
        print(f"FAIL: only {len(counts)} distinct sites fired "
              f"({sorted(counts)}; need >= {args.min_sites})",
              file=sys.stderr)
        ok = False
    if leaked:
        print(f"FAIL: leaked threads after chaos: {leaked}",
              file=sys.stderr)
        ok = False
    if wedge_specs and watchdog_timeouts == 0:
        print(f"FAIL: {wedge_specs} schedule(s) included a wedge fault "
              f"but the watchdog reported no dispatch timeouts — the "
              f"wedge->watchdog->breaker path never ran", file=sys.stderr)
        ok = False
    if not ov["ok"]:
        print(f"FAIL: disabled fault-hook overhead "
              f"{ov['disabled_overhead_pct']}% exceeds "
              f"{args.tolerance * 100:.1f}% (or no hook passes counted)",
              file=sys.stderr)
        ok = False
    if not cancel_doc["ok"]:
        print(f"FAIL: cancellation storm gate failed: "
              f"{json.dumps(cancel_doc)}", file=sys.stderr)
        ok = False

    deadline_done.set()
    if not ok:
        return 1
    print(f"PASS: {result['faults_fired']} faults across "
          f"{len(counts)} sites over {len(runs)} runs "
          f"({result['degraded_runs']} degraded, all correct); "
          f"{cancel_doc['cancelled_runs']} cancels over "
          f"{cancel_doc['runs']} storm runs, max latency "
          f"{cancel_doc['max_cancel_latency_s']}s within bound "
          f"{cancel_doc['latency_bound_s']}s; no leaked threads; "
          f"disabled-hook overhead {ov['disabled_overhead_pct']}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
