"""Multi-chip scaling bench (round 19): whole fused stages sharded over
the ICI mesh, the all-to-all exchange as the real shuffle.

Sweeps the virtual-device mesh at 1/2/4/8 devices. The device count is
baked into XLA at process start (``--xla_force_host_platform_device_
count`` is read once, before jax imports), so the parent re-execs ONE
CHILD PROCESS PER DEVICE COUNT and aggregates their JSON lines — the
decode_smoke/ci pattern for device-count-parameterized runs.

Probes (in-memory, 8-way partitioned at every device count so the
workload is identical and only the mesh varies):

- ``q72_shuffle`` (shuffle-heavy, q72-shaped): narrow filter/project
  chain -> hash repartition -> narrow chain. Both chains run as
  ShardedStageExec waves on the mesh and the repartition is the
  in-program ``lax.all_to_all`` when the mesh covers the partition
  count.
- ``q6_scan`` (scan-heavy, q6-shaped): a wide filter/project chain with
  no exchange — pure ShardedStageExec wave scaling.

Host CPU simulation cannot reproduce ICI link latency or TPU kernel
launch cost, so the bench models a FIXED per-dispatch device-occupancy
cost with the fuse-layer dispatch hook (``simulated_dispatch_latency_
ms``, recorded in the artifact): every device dispatch — sharded or
not — holds a device-occupancy lock for the same interval, because a
device retires one program at a time, and the measured walls are real
end-to-end clocks over that identical per-dispatch tax. Sharding wins
by issuing FEWER, WIDER dispatches (one SPMD wave instead of one
dispatch per partition batch; one all_to_all program instead of the
per-(dst,src) host loop) — the same mechanism that wins on real ICI.

Acceptance (ROADMAP item 4): the shuffle-heavy probe must scale >= 3x
at 8 virtual devices over the 1-device engine. Results land in
MULTICHIP_r06.json (replacing round 5's literal ``ok: true``).

Usage: python tools/bench_multichip.py [--rows 200000] [--sim-ms 5]
           [--out MULTICHIP_r06.json]

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

TOOLS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TOOLS)
PARTITIONS = 8
DEVICE_SWEEP = (1, 2, 4, 8)


def build_probes(rows: int):
    from spark_rapids_tpu.expr.core import col, lit

    data = {
        "g": [i % 97 for i in range(rows)],
        "v": list(range(rows)),
        "d": [float(i % 13) * 0.25 for i in range(rows)],
    }

    def q72_shuffle(s):
        return (s.create_dataframe(data, num_partitions=PARTITIONS)
                .filter(col("v") % lit(5) != lit(0))
                .select(col("g"), (col("v") * lit(3)).alias("v3"),
                        col("d"))
                .repartition(PARTITIONS, col("g"))
                .filter(col("v3") % lit(2) == lit(0))
                .select(col("g"), (col("v3") + lit(7)).alias("v7"),
                        (col("d") * lit(2.0)).alias("d2")))

    def q6_scan(s):
        return (s.create_dataframe(data, num_partitions=PARTITIONS)
                .filter(col("v") % lit(3) != lit(1))
                .select(col("g"), (col("v") * lit(2) + lit(1)).alias("v2"),
                        (col("d") * lit(0.5) + lit(1.0)).alias("dh"))
                .filter(col("v2") % lit(7) != lit(0))
                .select((col("g") + lit(1)).alias("g1"), col("v2"),
                        (col("dh") * col("dh")).alias("dsq")))

    return {"q72_shuffle": q72_shuffle, "q6_scan": q6_scan}


def _sorted(tbl):
    return tbl.sort_by([(c, "ascending") for c in tbl.column_names])


def run_child(args) -> int:
    """One device count, one process: run every probe, print one JSON
    line. Multichip is ON for every mesh size > 1; the 1-device run is
    the plain single-device engine (the scaling baseline)."""
    import threading

    import jax
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exec import fuse
    from spark_rapids_tpu.sql.session import TpuSession

    n = len(jax.devices())
    multichip = n > 1
    # row-group-granular scan batches (reader.batchSizeRows), as a real
    # Parquet scan produces them: the single-device engine dispatches
    # once per batch per stage, the sharded engine coalesces a
    # partition's batches into one wave — identical workload on both
    # paths, only the dispatch granularity differs.
    conf = {C.MULTICHIP_ENABLED.key: multichip,
            C.MAX_READER_BATCH_SIZE_ROWS.key: args.batch_rows}
    stats = {"dispatches": 0}
    sim_s = args.sim_ms / 1e3
    # A device retires ONE program at a time: the modeled per-dispatch
    # cost must serialize, or 8 host task threads would let a single
    # virtual device "execute" 8 programs concurrently and no dispatch
    # reduction could ever show up in the wall clock. Every dispatch —
    # single-device or SPMD — pays the same occupancy slot; sharding
    # wins by issuing FEWER, WIDER dispatches (one wave instead of one
    # program per partition batch), which is the ICI mechanism.
    device_occupancy = threading.Lock()

    def hook(_key):
        stats["dispatches"] += 1
        with device_occupancy:
            time.sleep(sim_s)

    out = {"devices": n, "multichip": multichip, "probes": {}}
    for name, build in build_probes(args.rows).items():
        s = TpuSession(dict(conf))
        fuse.set_dispatch_hook(hook)
        try:
            tbl = _sorted(build(s).collect())  # warm: compiles excluded
            digest = hashlib.sha256(
                json.dumps(tbl.to_pylist(), sort_keys=True, default=str)
                .encode()).hexdigest()[:16]
            walls, disp = [], []
            for _ in range(args.reps):
                stats["dispatches"] = 0
                t0 = time.perf_counter()
                build(s).collect()
                walls.append(time.perf_counter() - t0)
                disp.append(stats["dispatches"])
        finally:
            fuse.set_dispatch_hook(None)
        snaps = s.last_metrics()
        out["probes"][name] = {
            "wall_s": round(min(walls), 6),
            "dispatches": disp[-1],
            "shard_waves": int(sum(v.get("shardWaves", 0)
                                   for v in snaps.values())),
            "ici_ns": int(sum(v.get("iciExchangeTime", 0)
                              for v in snaps.values())),
            "rows_out": int(tbl.num_rows),
            "digest": digest,
        }
    print(json.dumps(out))
    return 0


def run_parent(args) -> int:
    per_devices = {}
    for n in DEVICE_SWEEP:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "").replace(
                "--xla_force_host_platform_device_count=8", "").strip()
            + f" --xla_force_host_platform_device_count={n}").strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
        cmd = [sys.executable, os.path.abspath(__file__), "--child",
               "--rows", str(args.rows), "--reps", str(args.reps),
               "--sim-ms", str(args.sim_ms),
               "--batch-rows", str(args.batch_rows)]
        print(f"-- devices={n}", file=sys.stderr)
        proc = subprocess.run(cmd, env=env, cwd=ROOT,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-2000:])
            print(f"FAIL: child at devices={n} rc={proc.returncode}")
            return 1
        line = proc.stdout.strip().splitlines()[-1]
        per_devices[n] = json.loads(line)

    # one row of input crosses ~3 int64/float64 planes per probe
    probe_bytes = args.rows * 3 * 8
    doc = {
        "bench": "bench_multichip",
        "round": 19,
        "devices_swept": list(DEVICE_SWEEP),
        "partitions": PARTITIONS,
        "rows": args.rows,
        "reps": args.reps,
        "reader_batch_rows": args.batch_rows,
        "simulated_dispatch_latency_ms": args.sim_ms,
        "note": "walls are measured end-to-end; every dispatch (sharded"
                " or not) pays the same simulated per-dispatch device-"
                "occupancy cost, serialized because a device retires one"
                " program at a time, so scaling comes from issuing"
                " fewer, wider dispatches — the ICI mechanism, modeled"
                " on a CPU host",
        "probes": {},
        "digest_parity": True,
    }
    fails = []
    for probe in ("q72_shuffle", "q6_scan"):
        base = per_devices[DEVICE_SWEEP[0]]["probes"][probe]
        digests = {per_devices[n]["probes"][probe]["digest"]
                   for n in DEVICE_SWEEP}
        if len(digests) != 1:
            doc["digest_parity"] = False
            fails.append(f"{probe}: results differ across device counts")
        rows = {}
        for n in DEVICE_SWEEP:
            p = per_devices[n]["probes"][probe]
            scaling = base["wall_s"] / p["wall_s"] if p["wall_s"] else 0.0
            rows[str(n)] = {
                "wall_s": p["wall_s"],
                "eff_gbps": round(probe_bytes / p["wall_s"] / 1e9, 4)
                if p["wall_s"] else 0.0,
                "dispatches": p["dispatches"],
                "shard_waves": p["shard_waves"],
                "ici_ns": p["ici_ns"],
                "scaling_x": round(scaling, 3),
                "scaling_efficiency": round(scaling / n, 3),
            }
        doc["probes"][probe] = {
            "rows_out": base["rows_out"],
            "input_bytes": probe_bytes,
            "per_devices": rows,
            "scaling_at_8": rows[str(DEVICE_SWEEP[-1])]["scaling_x"],
        }
    shuffle8 = doc["probes"]["q72_shuffle"]["scaling_at_8"]
    if shuffle8 < 3.0:
        fails.append(f"shuffle-heavy probe scaled {shuffle8}x at 8 "
                     f"devices — acceptance floor is 3x")
    out_path = args.out if os.path.isabs(args.out) \
        else os.path.join(ROOT, args.out)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps({p: doc["probes"][p]["per_devices"]
                      for p in doc["probes"]}, sort_keys=True))
    if fails:
        for fmsg in fails:
            print("FAIL:", fmsg)
        return 1
    print(f"PASS: shuffle-heavy probe {shuffle8}x at 8 devices "
          f"(scan-heavy {doc['probes']['q6_scan']['scaling_at_8']}x); "
          f"results byte-identical across "
          f"{list(DEVICE_SWEEP)} device meshes; wrote {out_path}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--sim-ms", type=float, default=5.0)
    ap.add_argument("--batch-rows", type=int, default=2048)
    ap.add_argument("--out", default="MULTICHIP_r06.json")
    args = ap.parse_args()
    if args.child:
        sys.path.insert(0, ROOT)
        return run_child(args)
    return run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
