"""AQE smoke: adaptive execution must be RIGHT, VISIBLE, and FREE when off.

Three gates, CI-blocking (tools/ci_check.sh):

1. CORRECTNESS — the q3join- and q72shfl-shaped probes (the two shapes
   the kernel audit attributed to dispatch_overhead) produce
   byte-identical results with adaptive execution on and off
   (canonically sorted: conversion legitimately reorders rows across
   partitions, it must never change them).
2. DECISIONS — the probes run cold then HISTORY-WARM against one
   history store: the q3join probe's shuffle-hash -> broadcast
   conversion fires (runtime-measured, so cold AND warm), and the
   q72shfl probe's measured-cost replan fires on the warm run only —
   from the cold run's own audited dispatch_overhead verdict, the warm
   plan collapses the hash exchange. Every decision must be visible in
   last_aqe() and the history record.
3. OVERHEAD — with spark.rapids.sql.adaptive.enabled=false the hook
   sites must cost <2% of a probe drive. Same count x delta
   methodology as tools/trace_overhead.py (end-to-end A/B timing is
   noise-bound on shared CI machines): count how often each disabled
   hook fires during one drive, measure each hook's per-call disabled
   cost in a 10^5-iteration tight loop, overhead = sum(count_i x
   cost_i) / best-of drive time.

Run:  python tools/aqe_smoke.py [--rows 60000] [--reps 5]
                                [--tolerance 0.02]

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402


def make_tables(rows: int):
    rng = np.random.default_rng(42)
    lineitem = pa.table({
        "l_orderkey": pa.array(rng.integers(0, rows // 4, rows)
                               .astype(np.int64)),
        "l_quantity": pa.array(rng.uniform(1, 50, rows)),
        "l_extendedprice": pa.array(rng.uniform(100, 10_000, rows)),
        "l_discount": pa.array(rng.uniform(0, 0.1, rows)),
    })
    orders = pa.table({
        "o_orderkey": pa.array(rng.integers(0, rows // 4, rows // 10)
                               .astype(np.int64)),
        "o_orderdate": pa.array(rng.integers(8000, 10_000, rows // 10)
                                .astype(np.int64)),
    })
    return lineitem, orders


def q3join_probe(sess, lineitem, orders):
    """lineitem x orders through the SHUFFLED branch (row threshold 1
    defeats the static broadcast estimate) -> the adaptive join node
    measures the build exchange and converts."""
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.sql import functions as F
    li = sess.create_dataframe(lineitem, num_partitions=4)
    od = sess.create_dataframe(orders, num_partitions=2)
    j = li.join(od, on=[(col("l_orderkey"), col("o_orderkey"))],
                how="inner")
    g = (j.select(col("l_orderkey"),
                  (col("l_extendedprice")
                   * (lit(1.0) - col("l_discount"))).alias("rev"))
         .group_by(col("l_orderkey")).agg(F.sum("rev").alias("rev")))
    return g.order_by(col("rev").desc(), col("l_orderkey").asc()).limit(10)


def q72shfl_probe(sess, lineitem):
    """4-partition high-cardinality group-by: partial agg -> hash
    exchange -> final, the shape whose exchange the audit called pure
    dispatch tax — the measured cost pass's collapse target."""
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.sql import functions as F
    sh = sess.create_dataframe(
        lineitem.select(["l_orderkey", "l_quantity"]), num_partitions=4)
    return (sh.select((col("l_orderkey") % lit(1000)).alias("k"),
                      col("l_quantity"))
            .group_by(col("k"))
            .agg(F.sum("l_quantity").alias("s"),
                 F.count("l_quantity").alias("c")))


def canon(table: pa.Table):
    rows = table.to_pylist()

    def key(r):
        return [(v is not None, str(v)) for _, v in sorted(r.items())]

    return sorted(rows, key=key)


def decisions(sess, kind):
    return [d for d in (sess.last_aqe() or {}).get("decisions", [])
            if d["kind"] == kind]


def correctness_and_decisions(rows: int) -> dict:
    from spark_rapids_tpu.sql.session import TpuSession
    lineitem, orders = make_tables(rows)
    hist = tempfile.mkdtemp(prefix="aqe_smoke_hist_")
    base = {"spark.rapids.sql.join.broadcastRowThreshold": 1,
            "spark.rapids.obs.audit.enabled": "true",
            "spark.rapids.obs.historyDir": hist}
    off_conf = dict(base)
    off_conf["spark.rapids.sql.adaptive.enabled"] = "false"

    out: dict = {"history_dir": hist}

    # -- cold pass (empty history) --
    s_cold = TpuSession(base)
    t3_cold = q3join_probe(s_cold, lineitem, orders).collect()
    conv = decisions(s_cold, "broadcast_conversion")
    if not conv:
        raise SystemExit("FAIL: q3join probe made no broadcast_conversion "
                         f"decision (aqe={s_cold.last_aqe()!r})")
    out["q3join_conversion"] = conv[0]
    t72_cold = q72shfl_probe(s_cold, lineitem).collect()
    if decisions(s_cold, "measured_cost"):
        raise SystemExit("FAIL: measured_cost decision fired on a COLD "
                         "history — hints must need an audited record")
    roof = s_cold.last_roofline() or {}
    shuffle_bound = (roof.get("groups", {}).get("shuffle") or {}).get("bound")
    if shuffle_bound != "dispatch_overhead":
        raise SystemExit(
            f"FAIL: cold q72shfl shuffle verdict is {shuffle_bound!r}, "
            "expected dispatch_overhead (tiny-partition exchange should "
            "be pure launch tax — did the audit or roofline change?)")

    # -- history-warm pass: same store, fresh session --
    s_warm = TpuSession(base)
    t72_warm = q72shfl_probe(s_warm, lineitem).collect()
    mc = decisions(s_warm, "measured_cost")
    if not mc:
        raise SystemExit("FAIL: warm q72shfl made no measured_cost "
                         f"decision (aqe={s_warm.last_aqe()!r})")
    if mc[0].get("exchange_parts") != 1:
        raise SystemExit(f"FAIL: warm decision did not collapse the "
                         f"exchange: {mc[0]!r}")
    out["q72shfl_warm_decision"] = mc[0]
    t3_warm = q3join_probe(s_warm, lineitem, orders).collect()
    if not decisions(s_warm, "broadcast_conversion"):
        raise SystemExit("FAIL: warm q3join lost its conversion decision")

    # -- AQE-off reference: byte-identical results --
    s_off = TpuSession(off_conf)
    t3_off = q3join_probe(s_off, lineitem, orders).collect()
    if s_off.last_aqe() is not None:
        raise SystemExit("FAIL: adaptive-off session recorded decisions")
    t72_off = q72shfl_probe(s_off, lineitem).collect()
    for name, got, ref in (("q3join/cold", t3_cold, t3_off),
                           ("q3join/warm", t3_warm, t3_off),
                           ("q72shfl/cold", t72_cold, t72_off),
                           ("q72shfl/warm", t72_warm, t72_off)):
        if canon(got) != canon(ref):
            raise SystemExit(f"FAIL: {name} results differ from the "
                             "AQE-off plan")
    out["parity"] = "byte-identical (canonical order) on/off, cold+warm"
    return out


# -- disabled-path overhead (count x delta) ---------------------------------

#: the hook sites the disabled path still executes, as (module attr
#: path, callable builder for the tight loop)
def _hooks():
    from spark_rapids_tpu.exec import adaptive as AQ
    from spark_rapids_tpu.plan import cost as COST
    return AQ, COST


def count_and_cost(rows: int, reps: int) -> dict:
    from spark_rapids_tpu.sql.session import TpuSession
    AQ, COST = _hooks()
    lineitem, _orders = make_tables(rows)
    off = TpuSession({"spark.rapids.sql.adaptive.enabled": "false"})
    conf = off.conf
    df = q72shfl_probe(off, lineitem)
    df.collect()  # warm compile caches out of the timed drives

    counts = {"adaptive.enabled": 0, "cost.measured_hints": 0,
              "cost.current_hints": 0, "adaptive.on_query_start": 0,
              "adaptive.finish_query": 0}
    orig = (AQ.enabled, COST.measured_hints, COST.current_hints,
            AQ.on_query_start, AQ.finish_query)

    def wrap(name, fn):
        def w(*a, **k):
            counts[name] += 1
            return fn(*a, **k)
        return w

    AQ.enabled = wrap("adaptive.enabled", orig[0])
    COST.measured_hints = wrap("cost.measured_hints", orig[1])
    COST.current_hints = wrap("cost.current_hints", orig[2])
    AQ.on_query_start = wrap("adaptive.on_query_start", orig[3])
    AQ.finish_query = wrap("adaptive.finish_query", orig[4])
    try:
        q72shfl_probe(off, lineitem).collect()
    finally:
        (AQ.enabled, COST.measured_hints, COST.current_hints,
         AQ.on_query_start, AQ.finish_query) = orig

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        q72shfl_probe(off, lineitem).collect()
        best = min(best, time.perf_counter() - t0)

    iters = 100_000
    plan = df.plan
    loops = {
        "adaptive.enabled": lambda: AQ.enabled(conf),
        "cost.measured_hints": lambda: COST.measured_hints(plan, conf),
        "cost.current_hints": COST.current_hints,
        "adaptive.on_query_start": lambda: AQ.on_query_start(conf),
        "adaptive.finish_query": AQ.finish_query,
    }
    per_call = {}
    for name, fn in loops.items():
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        per_call[name] = (time.perf_counter() - t0) / iters
    AQ.reset_for_tests()

    added = sum(counts[n] * per_call[n] for n in counts)
    return {"drive_best_s": round(best, 6),
            "hook_counts": counts,
            "per_call_ns": {n: round(c * 1e9, 1)
                            for n, c in per_call.items()},
            "disabled_overhead_s": round(added, 9),
            "disabled_overhead_pct": round(added / best * 100, 4)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=60_000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tolerance", type=float, default=0.02)
    args = ap.parse_args()

    result = correctness_and_decisions(args.rows)
    overhead = count_and_cost(args.rows, args.reps)
    result.update(overhead)
    print(json.dumps(result, sort_keys=True))
    pct = overhead["disabled_overhead_pct"]
    if pct > args.tolerance * 100:
        print(f"FAIL: disabled-path AQE overhead {pct:.3f}% exceeds "
              f"{args.tolerance * 100:.0f}% of the probe drive")
        return 1
    print(f"PASS: AQE on/off byte-identical (q3join conversion + warm "
          f"q72shfl measured-cost collapse fired); disabled-path "
          f"overhead {pct:.4f}% of the drive")
    return 0


if __name__ == "__main__":
    sys.exit(main())
