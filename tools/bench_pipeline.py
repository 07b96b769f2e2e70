"""Micro-benchmark: pipelined batch execution vs the synchronous path,
end-to-end through the session API.

The query is the shape the pipeline layer targets — a host-decode-heavy
scan feeding device compute and an exchange write:

    read_parquet(gzip)  ->  filter  ->  repartition(k)   ->  group_by(k)
    [host: decompress +     [device]    [SERIALIZED shuffle:   .agg(sum, n)
     decode + upload]                    partition kernel +
                                         async serde write]

With `spark.rapids.sql.pipeline.enabled=true` (default) three overlaps
engage at once: the scan->compute PipelineExec boundary decodes batch
i+1 on the host pool while batch i computes; the exchange consumes its
child partitions as live streams with a one-deep deferred offsets
fetch; and the serialized writer's ThrottlingExecutor serializes
sub-batch i while the device partitions batch i+1. With it disabled,
every one of those host steps sits serially between device dispatches.

Device-latency simulation (default --device-ms 25): each fused device
dispatch sleeps via the fuse dispatch hook, modeling an accelerator
where a dispatch spends its time OFF-HOST (device execution) while the
host CPU is free. The 25 ms is a simulation knob, not a measurement of
any chip. That off-host window is precisely what the pipeline hides host
decode/serde under. The simulation is applied identically to both
modes, so the comparison stays apples-to-apples.

Why simulate at all: on the CPU backend "device" compute is itself host
CPU work, so pipelined wall-clock can only beat synchronous if spare
cores exist — and this repo's CI container advertises 2 CPUs but
schedules them as effectively ONE core of quota (two pure-C matmuls in
parallel take exactly their serial time; measured, not assumed). On
such a box every CPU-vs-CPU overlap measures 1.0x by construction, and
only latency-shaped device time (GIL-released, off-CPU) can demonstrate
the mechanism. Pass --device-ms 0 for the pure-CPU measurement; on a
host with real spare cores it shows the overlap without simulation.

Run:  python tools/bench_pipeline.py [--rows 2500000] [--reps 3]
                                     [--device-ms 25] [--data-dir DIR]

Prints per-mode wall clock and a JSON summary line; exits nonzero if
the pipelined and synchronous results differ (they must be identical).

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

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pyarrow.parquet as pq  # noqa: E402


def make_data(d: str, rows: int) -> None:
    """Gzip parquet with small row groups: maximum host decode work per
    byte, many batches for the pipeline to look ahead over."""
    import glob
    if glob.glob(os.path.join(d, "*.parquet")):
        return
    rng = np.random.default_rng(5)
    t = pa.table({
        "k": rng.integers(0, 500, rows),
        "v": rng.uniform(0, 1000, rows),
        "a": rng.uniform(0, 1, rows), "b": rng.uniform(0, 1, rows),
        "c": rng.uniform(0, 1, rows), "e": rng.uniform(0, 1, rows),
        "f": rng.uniform(0, 1, rows), "g": rng.uniform(0, 1, rows),
    })
    pq.write_table(t, os.path.join(d, "f0.parquet"),
                   compression="gzip", row_group_size=131072)


def _session(enabled: bool):
    from spark_rapids_tpu.sql.session import TpuSession
    return TpuSession({
        "spark.rapids.sql.pipeline.enabled": str(enabled).lower(),
        "spark.rapids.sql.reader.batchSizeRows": "131072",
        "spark.rapids.sql.batchSizeBytes": str(8 << 20),
        "spark.rapids.sql.format.parquet.reader.type": "PERFILE",
        "spark.rapids.shuffle.mode": "SERIALIZED",
    })


def _query(s, d: str):
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.sql import functions as F
    return (s.read_parquet(d)
            .filter(col("v") > lit(700.0))
            .repartition(2, col("k"))
            .group_by("k").agg(F.sum(col("a")).alias("sa"),
                               F.count().alias("n")))


def _norm(tbl):
    return sorted(zip(tbl["k"].to_pylist(),
                      [round(v, 6) for v in tbl["sa"].to_pylist()],
                      tbl["n"].to_pylist()))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2_500_000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--device-ms", type=float, default=25.0,
                    help="simulated off-host latency per device dispatch "
                         "(0 = pure CPU-backend timing; see module doc)")
    ap.add_argument("--data-dir", default=None,
                    help="reuse/create the parquet input here instead of "
                         "a fresh temp dir")
    args = ap.parse_args()

    from spark_rapids_tpu.exec import fuse

    tmp = None
    if args.data_dir:
        d = args.data_dir
        os.makedirs(d, exist_ok=True)
    else:
        tmp = tempfile.TemporaryDirectory(prefix="bench_pipeline_")
        d = tmp.name
    make_data(d, args.rows)

    sessions = {True: _session(True), False: _session(False)}
    results = {}
    best = {True: float("inf"), False: float("inf")}
    # warmup (no simulated latency) compiles kernels and captures the
    # comparison results
    for mode, s in sessions.items():
        results[mode] = _norm(_query(s, d).collect())

    dev_s = max(0.0, args.device_ms) / 1e3
    if dev_s:
        fuse.set_dispatch_hook(lambda key: time.sleep(dev_s))
    try:
        order = [True, False]
        for i in range(max(1, args.reps)):
            for mode in (order if i % 2 == 0 else reversed(order)):
                df = _query(sessions[mode], d)
                t0 = time.perf_counter()
                df.collect()
                best[mode] = min(best[mode], time.perf_counter() - t0)
    finally:
        fuse.set_dispatch_hook(None)

    same = results[True] == results[False]
    lm = sessions[True].last_metrics()
    pipe = {k: v for k, v in lm.items()
            if k.startswith(("PipelineExec", "ShuffleExchangeExec"))}
    stall_ms = sum(v.get("pipelineStallTime", 0) for v in pipe.values()) / 1e6
    prod_ms = sum(v.get("pipelineProducerTime", 0)
                  for v in pipe.values()) / 1e6

    speedup = best[False] / best[True]
    label = (f"simulated {args.device_ms:g}ms/dispatch device"
             if dev_s else "pure CPU backend")
    print(f"mode: {label}")
    print(f"pipelined:   {best[True] * 1e3:8.1f} ms")
    print(f"synchronous: {best[False] * 1e3:8.1f} ms   ({speedup:.2f}x)")
    print(f"producer time (overlapped host work): {prod_ms:8.1f} ms")
    print(f"consumer stall (host-bound residue):  {stall_ms:8.1f} ms")
    print(json.dumps({
        "rows": args.rows, "reps": args.reps,
        "device_ms": args.device_ms,
        "pipelined_s": round(best[True], 4),
        "synchronous_s": round(best[False], 4),
        "speedup": round(speedup, 3),
        "producer_ms": round(prod_ms, 1),
        "stall_ms": round(stall_ms, 1),
        "identical_results": same,
    }))
    if tmp is not None:
        tmp.cleanup()
    if not same:
        print("FAIL: pipelined and synchronous results differ",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
