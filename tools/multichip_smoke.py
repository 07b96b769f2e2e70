"""Multi-chip smoke (round 19): the CI gate for sharded execution over
the ICI mesh.

1. 8-virtual-device parity: multi-partition scan / filter-project /
   group-by-agg (shuffle) probes must be byte-identical with
   spark.rapids.sql.multichip.enabled on and off — the on-path must
   actually engage (ShardedStageExec in the plan, shardWaves >= 1, and
   iciExchangeTime > 0 on the shuffle probe), the off-path must not.
2. Disabled-path overhead: with multichip OFF the only new code the old
   path executes is the planner's conf gate at convert_plan (plus the
   ICI-first check in ShuffleExchangeExec). Same count x delta
   methodology as tools/decode_smoke.py (end-to-end A/B timing is
   noise-bound on shared CI machines): count the gate's firings during
   a probe drive, measure the per-call cost in a tight loop, overhead
   must stay under --tolerance (2%) of the drive.

Usage: python tools/multichip_smoke.py [--rows 50000] [--tolerance 0.02]

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
import argparse
import json
import os
import sys
import time

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["XLA_FLAGS"] = _flags
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu import config as C  # noqa: E402
from spark_rapids_tpu.expr.core import col, lit  # noqa: E402
from spark_rapids_tpu.sql import functions as F  # noqa: E402
from spark_rapids_tpu.sql.session import TpuSession  # noqa: E402


def _data(rows: int) -> dict:
    return {
        "g": [i % 37 for i in range(rows)],
        "v": list(range(rows)),
        "d": [float(i % 11) * 0.5 for i in range(rows)],
    }


def queries(rows: int):
    data = _data(rows)
    return {
        "scan": lambda s: s.create_dataframe(data, num_partitions=8),
        "narrow": lambda s: (
            s.create_dataframe(data, num_partitions=8)
            .filter(col("v") % lit(3) != lit(0))
            .select(col("g"), (col("v") * lit(2) + lit(1)).alias("v2"),
                    (col("d") * lit(4.0)).alias("d4"))),
        "shuffle": lambda s: (
            s.create_dataframe(data, num_partitions=8)
            .group_by(col("g")).agg(F.sum("v").alias("sv"),
                                    F.count().alias("n"),
                                    F.min("d").alias("md"))),
    }


def _sorted(tbl):
    return tbl.sort_by([(c, "ascending") for c in tbl.column_names])


def parity_and_engagement(rows: int, result: dict) -> list:
    """Returns a list of failure strings (empty = pass)."""
    fails = []
    qs = queries(rows)
    outs = {}
    for flag in ("true", "false"):
        sess = TpuSession({C.MULTICHIP_ENABLED.key: flag})
        key = "multichip" if flag == "true" else "single"
        outs[key] = {}
        engaged = {}
        for name, q in qs.items():
            df = q(sess)
            outs[key][name] = _sorted(df.collect())
            plan = sess._last_exec.tree_string() \
                if getattr(sess, "_last_exec", None) else ""
            snaps = sess.last_metrics()
            engaged[name] = {
                "sharded_in_plan": "ShardedStageExec" in plan,
                "shard_waves": sum(v.get("shardWaves", 0)
                                   for v in snaps.values()),
                "ici_ns": sum(v.get("iciExchangeTime", 0)
                              for v in snaps.values()),
            }
        result[key] = engaged
        if flag == "true":
            if not engaged["narrow"]["sharded_in_plan"]:
                fails.append("multichip path did not plan the narrow "
                             "chain as ShardedStageExec")
            if engaged["narrow"]["shard_waves"] < 1:
                fails.append("multichip narrow probe recorded no "
                             "shardWaves")
            if not engaged["shuffle"]["ici_ns"]:
                fails.append("multichip shuffle probe recorded no "
                             "iciExchangeTime: the in-program all_to_all "
                             "did not run")
        else:
            for name, e in engaged.items():
                if e["sharded_in_plan"] or e["shard_waves"]:
                    fails.append(f"disabled path still shards ({name})")
    for name in qs:
        if not outs["multichip"][name].equals(outs["single"][name]):
            fails.append(f"parity: {name} differs between multichip "
                         f"on/off")
    return fails


def disabled_overhead(rows: int, reps: int) -> dict:
    """Count x delta: the disabled path's new sites are the multichip
    conf gate reads (convert_plan's planner gate + the exchange's
    ICI-first check)."""
    off = TpuSession({C.MULTICHIP_ENABLED.key: "false"})
    drive = queries(rows)["shuffle"]
    drive(off).collect()  # warm compile caches out of the timed drives

    conf = off.conf
    counts = {"multichip.enabled": 0}
    orig_get = type(conf).get

    def counting_get(self, entry, *a, **k):
        if getattr(entry, "key", None) == C.MULTICHIP_ENABLED.key:
            counts["multichip.enabled"] += 1
        return orig_get(self, entry, *a, **k)

    type(conf).get = counting_get
    try:
        drive(off).collect()
    finally:
        type(conf).get = orig_get

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        drive(off).collect()
        best = min(best, time.perf_counter() - t0)

    iters = 100_000
    t0 = time.perf_counter()
    for _ in range(iters):
        conf.get(C.MULTICHIP_ENABLED)
    per_call = (time.perf_counter() - t0) / iters

    added = counts["multichip.enabled"] * per_call
    return {"drive_best_s": round(best, 6),
            "gate_counts": counts,
            "gate_per_call_ns": round(per_call * 1e9, 1),
            "disabled_overhead_s": round(added, 9),
            "disabled_overhead_pct": round(added / best * 100, 4)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.02)
    args = ap.parse_args()

    import jax
    result = {"rows": args.rows, "devices": len(jax.devices())}
    fails = parity_and_engagement(args.rows, result)
    overhead = disabled_overhead(args.rows, args.reps)
    result.update(overhead)
    print(json.dumps(result, sort_keys=True))
    pct = overhead["disabled_overhead_pct"]
    if pct > args.tolerance * 100:
        fails.append(f"disabled-path multichip overhead {pct:.3f}% "
                     f"exceeds {args.tolerance * 100:.0f}% of the drive")
    if fails:
        for f in fails:
            print("FAIL:", f)
        return 1
    print(f"PASS: multichip on/off byte-identical across "
          f"{len(queries(args.rows))} probe queries on "
          f"{result['devices']} virtual devices; "
          f"narrow chain sharded in "
          f"{result['multichip']['narrow']['shard_waves']} wave(s), "
          f"shuffle spent {result['multichip']['shuffle']['ici_ns']}ns "
          f"in the in-program all_to_all; disabled-path overhead "
          f"{pct:.4f}% of the drive")
    return 0


if __name__ == "__main__":
    sys.exit(main())
