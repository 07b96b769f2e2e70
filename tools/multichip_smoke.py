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

3. A cache placed over the mesh: TPC-H Q1 and Q6 (the benchmark's query
   text and generator, at --tpch-rows lineitem rows) over tables cached
   under a mesh of 4 and of 8 virtual devices, each in a child process
   that has that many devices. Each partition's arrays must live on its
   own device, the partial aggregate must run sharded (shardWaves 1 a
   query, meshPutBytes 0), and the answers must equal the one-device
   run's (keys and counts exactly, double sums to 1e-11: four or eight
   partial sums are merged in another order than one).

Usage: python tools/multichip_smoke.py [--rows 50000] [--tolerance 0.02]

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
import argparse
import json
import os
import subprocess
import sys
import time

#: --placed-child N (the child of check 3) pins its own device count
_child = sys.argv[sys.argv.index("--placed-child") + 1] \
    if "--placed-child" in sys.argv else None
_flags = os.environ.get("XLA_FLAGS", "")
if _child is not None:
    _flags = " ".join(f for f in _flags.split()
                      if "xla_force_host_platform_device_count" not in f)
    _flags += f" --xla_force_host_platform_device_count={_child}"
elif "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["XLA_FLAGS"] = _flags.strip()
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


def placed_child(devices: int, tpch_rows: int) -> int:
    """Check 3's child: Q1 and Q6 over tables cached under a mesh of
    `devices` (1 = no mesh); one JSON line of answers and placement."""
    import jax
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    import datagen
    import run as harness
    from spark_rapids_tpu.runtime import obs
    sess = TpuSession({C.MULTICHIP_ENABLED.key: devices > 1})
    tables = datagen.generate(tpch_rows / 6_000_000, 28)
    placement = {}
    for name, table in tables.items():
        df = sess.create_dataframe(harness.plain_strings(table)).cache()
        df.count()
        sess.create_or_replace_temp_view(name, df)
        where = []
        for part in df.plan.materialized:
            c = part[0].get_batch().columns[0]
            arr = c.data["codes"] if c.is_dict else c.data
            where.append(next(iter(arr.devices())).id)
        placement[name] = where
    out = {"devices": len(jax.devices()), "placement": placement,
           "answers": {}, "plans": {}, "counters": {}}
    for q in ("q1", "q6"):
        out["answers"][q] = sess.sql(harness.load_query(q)).to_pydict()
        out["plans"][q] = sess._last_exec.tree_string()
        out["counters"][q] = obs.recent_queries(1)[0]["counters"]
    print(json.dumps(out))
    return 0


def placed_cache(tpch_rows: int, result: dict) -> list:
    """Check 3: one child a mesh size, compared with the one-device run."""
    fails, runs = [], {}
    for n in (1, 4, 8):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--placed-child",
             str(n), "--tpch-rows", str(tpch_rows)],
            capture_output=True, text=True, timeout=900)
        if r.returncode != 0:
            return [f"placed-cache child ({n} devices) failed: "
                    f"{r.stderr[-800:]}"]
        runs[n] = json.loads(r.stdout.strip().splitlines()[-1])
    for n in (4, 8):
        run, base = runs[n], runs[1]
        for table, where in run["placement"].items():
            if where != list(range(n)):
                fails.append(f"{n} devices: {table}'s partitions live on "
                             f"devices {where}, not one each")
        for q, want in base["answers"].items():
            got = run["answers"][q]
            same = set(got) == set(want) and all(
                len(got[k]) == len(v) and all(
                    abs(g - w) <= 1e-11 * abs(w) if isinstance(w, float)
                    else g == w for g, w in zip(got[k], v))
                for k, v in want.items())
            if not same:
                fails.append(f"{n} devices: {q} differs from the "
                             f"one-device run")
            if f"[sharded n={n}]" not in run["plans"][q]:
                fails.append(f"{n} devices: {q} did not plan the sharded "
                             f"aggregate")
            c = run["counters"][q]
            if c["shard_waves"] != 1 or c["mesh_put_bytes"] != 0:
                fails.append(f"{n} devices: {q} ran {c['shard_waves']} "
                             f"waves and moved {c['mesh_put_bytes']} bytes "
                             f"(want 1 and 0)")
    result["placed_cache"] = {
        str(n): {"placement": runs[n]["placement"],
                 "counters": runs[n]["counters"]} for n in (4, 8)}
    return fails


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.02)
    ap.add_argument("--tpch-rows", type=int, default=20_000)
    ap.add_argument("--placed-child", type=int, default=0,
                    help="internal: run check 3's child on this many devices")
    args = ap.parse_args()
    if args.placed_child:
        return placed_child(args.placed_child, args.tpch_rows)

    import jax
    result = {"rows": args.rows, "devices": len(jax.devices())}
    fails = parity_and_engagement(args.rows, result)
    fails += placed_cache(args.tpch_rows, result)
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
          f"in the in-program all_to_all; Q1/Q6 over a cache placed on "
          f"4 and 8 devices equal the one-device run; disabled-path "
          f"overhead {pct:.4f}% of the drive")
    return 0


if __name__ == "__main__":
    sys.exit(main())
