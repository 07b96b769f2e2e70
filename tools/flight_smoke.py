"""Flight-recorder smoke: always-on must stay (nearly) free, triggers
must dump.

The retroactive-observability CI gate (tools/ci_check.sh):

1. **Overhead** (trace_overhead.py methodology — naive A/B wall-clock
   comparison is an order of magnitude noisier than the quantity under
   test on shared CI): count how often each instrumentation entry point
   fires during one drive of stage_harness's unfused chain, measure each
   entry point's per-call cost WITH THE RECORDER ON minus its pre-flight
   equivalent (the bare GpuMetric timer / nothing) over 10^5 tight-loop
   iterations, and gate sum(count_i x delta_i) < 2% of the drive's
   best-of wall time.

2. **Triggers** (chaos_smoke methodology — conf-armed fault injection,
   tracing OFF throughout):
   - a clean query writes NO dump;
   - an injected scan.decode ioerror fails the query and dumps a
     readable Chrome-trace file (validated by profiler_report) whose
     events cover the failing query (exec spans + faultInjected +
     queryError) with reason=query_failed;
   - the same fault under spark.rapids.fallback.cpu.enabled degrades
     the query (answers still correct vs the clean run) and dumps with
     reason=query_degraded;
   - an absolute SLO bound trips on a clean query: slo_breach dump,
     rapids_slo_breaches_total bumped, /healthz carries the last-slow
     digest + attribution summary + dump path;
   - opening the circuit breaker dumps with reason=breaker_open.

3. **Attribution**: the probe query's buckets sum to its wall time
   within 1% (the PR 3 reconciliation bar).

Run:  python tools/flight_smoke.py [--rows 400000] [--batch 2048]
                                   [--reps 9] [--tolerance 0.02]

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import stage_harness as SH  # noqa: E402

_ENTRY_POINTS = ("exec_span", "metric_span", "span", "instant")


def _count_calls(trace, drive):
    counts = {n: 0 for n in _ENTRY_POINTS}
    saved = {n: getattr(trace, n) for n in _ENTRY_POINTS}

    def wrap(name):
        inner = saved[name]

        def counted(*a, **kw):
            counts[name] += 1
            return inner(*a, **kw)
        return counted

    try:
        for n in _ENTRY_POINTS:
            setattr(trace, n, wrap(n))
        drive()
    finally:
        for n in _ENTRY_POINTS:
            setattr(trace, n, saved[n])
    return counts


def _per_call_deltas(trace, iters=100_000):
    """Flight-ON per-call cost of each entry point MINUS its pre-flight
    equivalent, in seconds (clamped >= 0). The recorder must be
    installed when this runs."""
    from spark_rapids_tpu.runtime.metrics import GpuMetric

    class _Node:
        lore_id = None

        def name(self):
            return "X"

    node, m = _Node(), GpuMetric("opTime")

    def loop(fn):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        return (time.perf_counter() - t0) / iters

    def bare_timer():
        with m.ns():
            pass

    def nothing():
        pass

    def exec_span_full():
        with trace.exec_span(node, m):
            pass

    def metric_span_full():
        with trace.metric_span("x", m):
            pass

    def span_full():
        with trace.span("x"):
            pass

    base_timer = min(loop(bare_timer) for _ in range(3))
    base_empty = min(loop(nothing) for _ in range(3))
    costs = {
        "exec_span": min(loop(exec_span_full) for _ in range(3)),
        "metric_span": min(loop(metric_span_full) for _ in range(3)),
        "span": min(loop(span_full) for _ in range(3)),
        "instant": min(loop(lambda: trace.instant("x")) for _ in range(3)),
    }
    return {
        "exec_span": max(costs["exec_span"] - base_timer, 0.0),
        "metric_span": max(costs["metric_span"] - base_timer, 0.0),
        "span": max(costs["span"] - base_empty, 0.0),
        "instant": max(costs["instant"] - base_empty, 0.0),
    }


def _dumps(d):
    return sorted(glob.glob(os.path.join(d, "flight_*.json")))


def _flight_conf(flight_dir, **extra):
    conf = {
        "spark.rapids.obs.flight.path": flight_dir,
        "spark.rapids.obs.flight.minIntervalSeconds": "0",
        "spark.rapids.sql.reader.batchSizeRows": "4096",
    }
    conf.update(extra)
    return conf


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--tolerance", type=float, default=0.02)
    args = ap.parse_args()

    import numpy as np
    import pyarrow as pa

    import profiler_report as PR
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.runtime import obs, trace, watchdog
    from spark_rapids_tpu.runtime.obs import flight
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.session import TpuSession

    # -- 1. overhead: recorder ON, tracing OFF ------------------------------
    flight_dir = tempfile.mkdtemp(prefix="flight_smoke_")
    flight.install(capacity=2048, out_dir=flight_dir, min_interval_s=0.0)
    # UNFUSED chain: per-batch exec_span traffic (the fused stage's hot
    # loop has no per-batch entry-point calls and would measure zero)
    drive = SH.make_chain_stage(args.rows, args.batch, fused=False)
    drive()  # warm every kernel cache before measuring
    drive_s = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        drive()
        drive_s.append(time.perf_counter() - t0)
    drive_best = min(drive_s)
    counts = _count_calls(trace, drive)
    deltas = _per_call_deltas(trace)
    added_s = sum(counts[n] * deltas[n] for n in _ENTRY_POINTS)
    overhead = added_s / drive_best

    # -- 2. triggers --------------------------------------------------------
    obs.shutdown_for_tests()
    flight.uninstall_for_tests()
    watchdog.uninstall_for_tests()
    rng = np.random.default_rng(20260804)
    table = pa.table({"k": rng.integers(0, 50, 60_000),
                      "v": rng.integers(0, 1000, 60_000)})

    def query(sess):
        return (sess.create_dataframe(table, num_partitions=2)
                .filter(col("v") > lit(10))
                .group_by("k").agg(F.sum(col("v")).alias("sv")).collect())

    # clean run: recorder armed, NO dump
    sess = TpuSession(_flight_conf(flight_dir))
    clean = query(sess)
    n0 = len(_dumps(flight_dir))
    assert n0 == 0, f"clean run wrote {n0} flight dump(s)"

    # failed query (tracing OFF): a readable Chrome-trace dump
    sess = TpuSession(_flight_conf(
        flight_dir, **{"spark.rapids.debug.faults": "scan.decode:ioerror"}))
    failed = False
    try:
        query(sess)
    except Exception:  # noqa: BLE001 - the injected fault
        failed = True
    assert failed, "injected scan.decode ioerror did not fail the query"
    dumps = _dumps(flight_dir)
    assert len(dumps) == 1 and "query_failed" in dumps[0], dumps
    events = PR.validate_chrome_trace(dumps[0])
    names = {e["name"] for e in events}
    spans = sum(1 for e in events if e["ph"] == "X")
    assert spans > 0, "failure dump has no spans"
    assert "faultInjected" in names and "queryError" in names \
        and "flightTrigger" in names, sorted(names)
    fail_doc = json.load(open(dumps[0]))["otherData"]
    assert fail_doc["reason"] == "query_failed" \
        and fail_doc["error"] == "InjectedFaultError", fail_doc

    # degraded query: CPU fallback answers, dump says query_degraded
    sess = TpuSession(_flight_conf(
        flight_dir, **{
            "spark.rapids.debug.faults": "scan.decode:ioerror",
            "spark.rapids.fallback.cpu.enabled": "true"}))
    degraded_result = query(sess)
    assert sess.last_action_status[0] == "degraded", \
        sess.last_action_status
    assert degraded_result.sort_by("k").equals(clean.sort_by("k")), \
        "degraded result differs from the clean run"
    dumps = _dumps(flight_dir)
    assert len(dumps) == 2 and "query_degraded" in dumps[1], dumps
    PR.validate_chrome_trace(dumps[1])

    # SLO breach: absolute bound trips a clean query
    obs.shutdown_for_tests()
    sess = TpuSession(_flight_conf(
        flight_dir, **{"spark.rapids.obs.slo.latencySeconds": "1e-6"}))
    query(sess)
    st = obs.state()
    assert st is not None and st.slo.breaches >= 1, "no SLO breach"
    hz = obs.healthz()
    last_slow = hz["slo"]["last_slow"]
    assert last_slow and last_slow["plan_digest"] \
        and last_slow["flight_dump"] \
        and last_slow["attribution"]["top_buckets"], last_slow
    assert hz["flight"]["last_dump"]["reason"] == "slo_breach", \
        hz["flight"]
    slow_events = PR.validate_chrome_trace(last_slow["flight_dump"])
    assert any(e["name"] == "slowQuery" for e in slow_events)
    breach_count = st.registry.counter("rapids_slo_breaches_total").value
    assert breach_count >= 1, breach_count

    # attribution reconciliation (the 1% bar) on the breaching query
    attr = sess.last_attribution()
    bucket_sum = sum(attr["buckets"].values())
    recon = abs(bucket_sum - attr["wall_seconds"]) / attr["wall_seconds"]
    assert recon < 0.01, (bucket_sum, attr["wall_seconds"])

    # breaker open: one more dump
    before = len(_dumps(flight_dir))
    brk = watchdog.breaker()
    brk.configure(1, 60.0, 60.0)
    brk.record_failure("SmokeError")
    assert brk.state == "open"
    dumps = _dumps(flight_dir)
    assert len(dumps) == before + 1 and "breaker_open" in dumps[-1], dumps
    watchdog.uninstall_for_tests()
    obs.shutdown_for_tests()
    flight.uninstall_for_tests()

    result = {
        "drive_best_s": round(drive_best, 5),
        "instr_calls_per_drive": counts,
        "per_call_delta_ns": {n: round(d * 1e9, 1)
                              for n, d in deltas.items()},
        "flight_overhead_s": round(added_s, 7),
        "flight_overhead_pct": round(overhead * 100, 4),
        "tolerance_pct": args.tolerance * 100,
        "failure_dump_spans": spans,
        "attribution_reconciliation_pct": round(recon * 100, 5),
        "dumps_written": len(_dumps(flight_dir)),
    }
    print(json.dumps(result))
    if overhead > args.tolerance:
        print(f"FAIL: always-on flight overhead {overhead * 100:.3f}% "
              f"exceeds {args.tolerance * 100:.1f}%", file=sys.stderr)
        return 1
    print(f"PASS: always-on recorder overhead {overhead * 100:.3f}% of "
          f"the drive (tolerance {args.tolerance * 100:.1f}%); "
          f"failure/degrade/SLO/breaker each dumped a validating "
          f"Chrome trace; clean run silent; attribution reconciles "
          f"({recon * 100:.4f}%)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
