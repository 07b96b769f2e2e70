"""Per-request tracing smoke: the CI gate for runtime/obs/reqtrace.py.

1. Disabled-path overhead: with reqtrace OFF (the default) the only new
   site a flight-armed workload executes is the ONE module-global read
   (``reqtrace._REC``) inside FlightRecorder.record. Count x delta
   methodology (tools/aqe_smoke.py): count record() firings during a
   drive, measure the read's per-call cost in a tight loop, bound the
   product under --tolerance (2%) of the drive. Runs FIRST, before this
   process installs any recorder.
2. Armed-path overhead: with a recorder installed AND a request bound,
   every flight event additionally runs ReqTraceRecorder.feed (one
   thread-local read + one tuple store + one integer bump). Same count
   x delta bound over a request-bound drive.
3. Verdicts over the serving surface (seeded sampler -> deterministic):
   the executed request breaches a tiny absolute SLO and ALWAYS exports
   (verdict slo_breach); injected scan ioerrors fail their requests and
   ALWAYS export (verdict error, 100% of them); N hot cache hits ride
   the seeded sampleRatio draw — the kept count must equal the seed's
   replay exactly and stay at the configured ratio. The incoming W3C
   traceparent is honored verbatim.
4. Timeline validation: every exported artifact is a loadable Chrome
   trace (tools/profiler_report.validate_chrome_trace) whose root
   "request" span carries the W3C identity; executed timelines contain
   the serving span tree AND engine exec spans joined by the request's
   query_id; every artifact has a well-formed OTLP-JSON sibling whose
   child spans parent on the request root.

Usage: python tools/reqtrace_smoke.py [--hits 240] [--ratio 0.05]
                                      [--tolerance 0.02]

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from profiler_report import validate_chrome_trace  # noqa: E402

SQL = "SELECT k, SUM(v) AS sv, COUNT(*) AS n FROM t GROUP BY k"
SEED = 20260807
#: an incoming W3C traceparent the server must honor verbatim
TP_TID = "ab" * 16
TP = f"00-{TP_TID}-{'cd' * 8}-01"


def _probe_table(n=30_000, seed=17):
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(0, 12, n),
                     "v": rng.integers(1, 1000, n)})


def _session(extra=None):
    from spark_rapids_tpu.sql.session import TpuSession
    sess = TpuSession(extra or {})
    sess.create_or_replace_temp_view(
        "t", sess.create_dataframe(_probe_table()))
    return sess


def _counted_drive(drive):
    """Run one drive counting FlightRecorder.record firings (each one
    executes the reqtrace feed site being charged)."""
    from spark_rapids_tpu.runtime.obs import flight
    counts = [0]
    real = flight.FlightRecorder.record

    def counting(self, *a, **kw):
        counts[0] += 1
        return real(self, *a, **kw)

    flight.FlightRecorder.record = counting
    try:
        drive()
    finally:
        flight.FlightRecorder.record = real
    return counts[0]


# ---------------------------------------------------------------------------
# gate 1: disabled-path overhead — MUST run before any recorder install
# ---------------------------------------------------------------------------

def disabled_overhead(reps: int) -> dict:
    from spark_rapids_tpu.runtime.obs import reqtrace
    assert reqtrace.recorder() is None, \
        "gate 1 must run before a reqtrace recorder exists"
    sess = _session()

    def drive():
        sess.sql(SQL).collect()

    drive()  # warm the trace cache out of the timed drives
    count = _counted_drive(drive)
    assert count > 0, "flight recorder not armed — nothing to charge"

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        drive()
        best = min(best, time.perf_counter() - t0)

    iters = 1_000_000
    t0 = time.perf_counter()
    for _ in range(iters):
        rr = reqtrace._REC
        if rr is not None:
            raise AssertionError("recorder appeared mid-measurement")
    per_call = (time.perf_counter() - t0) / iters

    added = count * per_call
    return {"feed_sites": count,
            "per_call_ns": round(per_call * 1e9, 1),
            "drive_best_s": round(best, 6),
            "disabled_overhead_pct": round(added / best * 100, 5)}


# ---------------------------------------------------------------------------
# gate 2: armed-path overhead (recorder installed, request bound)
# ---------------------------------------------------------------------------

def armed_overhead(reps: int, out_dir: str) -> dict:
    from spark_rapids_tpu.runtime.obs import live, reqtrace
    rec = reqtrace.install(out_dir=out_dir, sample_ratio=0.0,
                           replica_id="smoke")
    sess = _session()

    def drive():
        sess.sql(SQL).collect()

    drive()
    ctx = rec.begin()
    prev = live.bind_request(ctx)
    try:
        count = _counted_drive(drive)
        assert ctx.idx > 0, "bound drive fed no events into the ring"
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            drive()
            best = min(best, time.perf_counter() - t0)
        iters = 200_000
        t0 = time.perf_counter()
        for _ in range(iters):
            rec.feed("smoke", "exec", 0, 1, None, 7)
        per_call = (time.perf_counter() - t0) / iters
    finally:
        live.bind_request(prev)
        reqtrace.uninstall_for_tests()

    added = count * per_call
    return {"feed_sites": count,
            "per_call_ns": round(per_call * 1e9, 1),
            "drive_best_s": round(best, 6),
            "armed_overhead_pct": round(added / best * 100, 5)}


# ---------------------------------------------------------------------------
# gate 3: verdicts over the serving surface (seeded -> deterministic)
# ---------------------------------------------------------------------------

def serving_verdicts(out_dir: str, hits: int, ratio: float,
                     errors: int, result: dict) -> list:
    from spark_rapids_tpu.runtime import serving
    from spark_rapids_tpu.runtime.obs import reqtrace
    fails = []
    rec = reqtrace.install(out_dir=out_dir, sample_ratio=ratio,
                           min_interval_s=0.0, max_dumps=10_000,
                           replica_id="smoke-replica", sample_seed=SEED)
    # the serving session: reqtrace armed (first-wins -> the seeded
    # recorder above), a tiny absolute SLO so the one EXECUTED request
    # breaches (cache hits never run the epilogue, so they stay clean)
    _session({"spark.rapids.serving.enabled": "true",
              "spark.rapids.obs.slo.latencySeconds": "0.0005"})

    # -- the executed request: always kept, verdict slo_breach ----------
    code, doc = serving.handle_sql({"sql": SQL})
    rt = doc.get("reqtrace") or {}
    if code != 200 or doc.get("cache") != "miss":
        fails.append(f"seed request: code={code} cache={doc.get('cache')}")
    if rt.get("verdict") != "slo_breach" or not rt.get("path") \
            or not os.path.exists(rt.get("path") or ""):
        fails.append(f"executed SLO breach not exported: {rt}")
    if doc.get("replica_id") != "smoke-replica" or not doc.get("trace_id"):
        fails.append(f"response doc missing trace identity: "
                     f"replica={doc.get('replica_id')} "
                     f"trace={doc.get('trace_id')}")
    result["slo_breach"] = {"code": code, "verdict": rt.get("verdict"),
                            "path": rt.get("path")}

    # -- failed requests: 100% kept, verdict error ----------------------
    err_payload = {
        "sql": SQL, "session": "faulty", "cache": False,
        "conf": {"spark.rapids.debug.faults":
                 f"scan.decode:ioerror:{errors}"}}
    err_kept = 0
    for _ in range(errors):
        code, doc = serving.handle_sql(dict(err_payload))
        rt = doc.get("reqtrace") or {}
        if code != 500 or doc.get("status") != "failed":
            fails.append(f"fault request: code={code} "
                         f"status={doc.get('status')}")
        if rt.get("verdict") == "error" and rt.get("path") \
                and os.path.exists(rt["path"]):
            err_kept += 1
    if err_kept != errors:
        fails.append(f"only {err_kept}/{errors} failed requests exported")
    result["errors"] = {"sent": errors, "kept": err_kept}

    # -- hot cache hits: the seeded sampleRatio draw --------------------
    rng = random.Random(SEED)
    expected = sum(1 for _ in range(hits) if rng.random() < ratio)
    kept = 0
    for i in range(hits):
        payload = {"sql": SQL}
        if i == 0:
            payload["_traceparent"] = TP
        code, doc = serving.handle_sql(payload)
        if code != 200 or doc.get("cache") != "hit":
            fails.append(f"hit {i}: code={code} cache={doc.get('cache')}")
            break
        rt = doc.get("reqtrace") or {}
        if rt.get("verdict") == "sampled":
            kept += 1
        elif rt.get("verdict") != "dropped":
            fails.append(f"hit {i} landed verdict {rt.get('verdict')}")
            break
        if i == 0 and doc.get("trace_id") != TP_TID:
            fails.append(f"incoming traceparent not honored: "
                         f"{doc.get('trace_id')}")
    if kept != expected:
        fails.append(f"seeded sampler kept {kept} hits, expected "
                     f"{expected} (ratio {ratio})")
    if kept > max(1, int(hits * ratio * 3)):
        fails.append(f"kept {kept}/{hits} hot hits — far over the "
                     f"{ratio} sampleRatio")
    stats = rec.doc()
    if stats["exports"] != 1 + err_kept + kept:
        fails.append(f"recorder exports {stats['exports']} != "
                     f"{1 + err_kept + kept} kept requests")
    result["hits"] = {"sent": hits, "ratio": ratio, "kept": kept,
                      "expected": expected,
                      "dropped": stats["dropped"]}
    return fails


# ---------------------------------------------------------------------------
# gate 4: exported timelines validate (Chrome trace + OTLP sibling)
# ---------------------------------------------------------------------------

def validate_timelines(out_dir: str, result: dict) -> list:
    fails = []
    names = sorted(n for n in os.listdir(out_dir)
                   if n.startswith("req_") and n.endswith(".json")
                   and not n.endswith(".otlp.json"))
    if not names:
        return ["no exported timelines to validate"]
    joined = 0
    for name in names:
        path = os.path.join(out_dir, name)
        try:
            events = validate_chrome_trace(path)
        except ValueError as e:
            fails.append(str(e))
            continue
        meta = json.load(open(path)).get("otherData") or {}
        roots = [e for e in events if e["name"] == "request"]
        if len(roots) != 1 or not meta.get("trace_id", "").startswith(
                name[:-len(".json")].split("_")[-1]):
            fails.append(f"{name}: bad root span / trace id")
        serving_spans = {e["name"] for e in events
                         if e.get("cat") == "serving"}
        if "intake" not in serving_spans:
            fails.append(f"{name}: no serving intake span")
        # executed requests: engine exec spans joined by the query id
        if "execute" in serving_spans and meta.get("status") == "ok":
            qid = meta.get("query_id")
            exec_evs = [e for e in events if e.get("cat") != "serving"
                        and (e.get("args") or {}).get("query_id") == qid]
            if qid is None or not exec_evs:
                fails.append(f"{name}: executed timeline has no exec "
                             f"spans joined to query {qid}")
            else:
                joined += 1
        otlp = path[:-5] + ".otlp.json"
        if not os.path.exists(otlp):
            fails.append(f"{name}: missing OTLP sibling")
            continue
        spans = json.load(open(otlp))[
            "resourceSpans"][0]["scopeSpans"][0]["spans"]
        root_ids = {s["spanId"] for s in spans
                    if s["name"] == "POST /sql"}
        if len(root_ids) != 1 or any(
                s["traceId"] != meta["trace_id"] for s in spans):
            fails.append(f"{name}: OTLP trace/root identity broken")
        elif any(s["name"] != "POST /sql"
                 and s.get("parentSpanId") not in root_ids
                 and not any(p["spanId"] == s["parentSpanId"]
                             for p in spans) for s in spans):
            fails.append(f"{name}: OTLP span parents dangle")
    if joined == 0:
        fails.append("no executed timeline carried joined exec spans")
    result["timelines"] = {"artifacts": len(names), "joined": joined}
    return fails


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.02)
    ap.add_argument("--hits", type=int, default=240)
    ap.add_argument("--ratio", type=float, default=0.05)
    ap.add_argument("--errors", type=int, default=3)
    args = ap.parse_args()

    fails = []
    result = {}

    print("[gate 1] disabled-path overhead (count x delta)...",
          flush=True)
    oh = disabled_overhead(args.reps)
    result["disabled"] = oh
    print(f"  {oh['feed_sites']} feed sites x {oh['per_call_ns']}ns over "
          f"{oh['drive_best_s']}s drive -> {oh['disabled_overhead_pct']}%"
          f" (gate < {args.tolerance * 100:.0f}%)")
    if oh["disabled_overhead_pct"] > args.tolerance * 100:
        fails.append("disabled-path reqtrace overhead over budget")

    with tempfile.TemporaryDirectory(prefix="reqtrace_smoke_") as d:
        print("[gate 2] armed-path overhead (request-bound drive)...",
              flush=True)
        ah = armed_overhead(args.reps, os.path.join(d, "unused"))
        result["armed"] = ah
        print(f"  {ah['feed_sites']} feed sites x {ah['per_call_ns']}ns "
              f"over {ah['drive_best_s']}s drive -> "
              f"{ah['armed_overhead_pct']}%")
        if ah["armed_overhead_pct"] > args.tolerance * 100:
            fails.append("armed reqtrace overhead over budget")

        out_dir = os.path.join(d, "reqtrace")
        print("[gate 3] verdicts over the serving surface...", flush=True)
        fails.extend(serving_verdicts(out_dir, args.hits, args.ratio,
                                      args.errors, result))
        print(f"  slo_breach={result.get('slo_breach', {}).get('verdict')}"
              f" errors={result.get('errors')} hits={result.get('hits')}")

        print("[gate 4] exported timelines validate...", flush=True)
        fails.extend(validate_timelines(out_dir, result))
        print(f"  {result.get('timelines')}")

    print(json.dumps(result, sort_keys=True))
    if fails:
        print("reqtrace_smoke: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    h = result["hits"]
    print(f"reqtrace_smoke: PASS (errors/SLO breaches 100% exported; "
          f"{h['kept']}/{h['sent']} hot hits kept at ratio {h['ratio']}; "
          f"disabled {oh['disabled_overhead_pct']}% / armed "
          f"{ah['armed_overhead_pct']}%; {result['timelines']['artifacts']}"
          f" timelines Chrome+OTLP valid)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
