"""Micro-benchmark: 'compact' (counting-sort) vs 'masked' shuffle
repartitioning, end-to-end on the CPU backend.

Two measurements, both shuffle-shaped:

1. repartition-only: drive a ShuffleExchangeExec directly and force every
   output sub-batch's planes (what the exchange itself costs);
2. repartition + group-by: the full partial-agg -> hash exchange ->
   final-merge pipeline through the session API (what downstream
   operators save when sub-batches are right-sized instead of
   n_out x capacity mask slices).

Run:  python tools/bench_exchange.py [--rows 200000] [--nout 4] [--reps 3]

Prints per-mode wall-clock and a JSON summary line; exits nonzero if the
two modes disagree on query results (they must be identical).

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402


def _table(rows: int) -> pa.Table:
    rng = np.random.default_rng(7)
    return pa.table({
        "k": rng.integers(0, 5000, rows),
        "v": rng.integers(-(1 << 40), 1 << 40, rows),
        "d": rng.uniform(-1e9, 1e9, rows),
        "s": np.array(["tag%d" % i for i in range(64)])[
            rng.integers(0, 64, rows)],
    })


def _session(partitioning: str):
    from spark_rapids_tpu.sql.session import TpuSession
    return TpuSession({"spark.rapids.shuffle.partitioning": partitioning})


def bench_repartition(t: pa.Table, partitioning: str, n_out: int,
                      reps: int) -> float:
    """Exchange-only: materialize + force every output plane."""
    from spark_rapids_tpu.exec import tpu_nodes as X
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.plan.nodes import bind_expr
    from spark_rapids_tpu.plan.overrides import convert_plan
    from spark_rapids_tpu.runtime.task import TaskContext

    def run():
        s = _session(partitioning)
        df = s.create_dataframe(t, num_partitions=n_out)
        child, _ = convert_plan(df.plan, s.conf)
        ex = X.ShuffleExchangeExec(df.plan, [child], s.conf,
                                   [bind_expr(col("k"), df.plan.schema)],
                                   n_out=n_out)
        leaves = []
        for p in range(n_out):
            with TaskContext(partition_id=p) as ctx:
                for b in ex.execute_partition(ctx, p):
                    leaves.extend(jax.tree_util.tree_leaves(b))
        jax.block_until_ready(leaves)

    run()  # warm the kernel caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_groupby(t: pa.Table, partitioning: str, n_out: int,
                  reps: int):
    """Shuffle-shaped repartition + group-by: exchange RAW rows by the
    group key, then aggregate each partition completely — the exact
    pipeline the planner builds for no-partial-state aggregates
    (plan/overrides.py) and the q72shfl bench shape. Downstream work is
    proportional to what the exchange emits: n_out x capacity mask
    slices vs right-sized compact slices."""
    from spark_rapids_tpu.columnar.batch import to_arrow
    from spark_rapids_tpu.exec import tpu_nodes as X
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.plan import nodes as P
    from spark_rapids_tpu.plan.overrides import convert_plan
    from spark_rapids_tpu.runtime.task import TaskContext
    from spark_rapids_tpu.sql import functions as F

    def run():
        s = _session(partitioning)
        df = s.create_dataframe(t, num_partitions=n_out)
        gdf = df.group_by(col("k")).agg(
            F.sum("v").alias("sv"), F.count().alias("n"),
            F.min("d").alias("md"))
        node = gdf.plan
        while not isinstance(node, P.Aggregate):
            node = node.children[0]
        scan, _ = convert_plan(node.children[0], s.conf)
        exch = X.ShuffleExchangeExec(node, [scan], s.conf,
                                     node.group_exprs, n_out=n_out)
        agg = X.HashAggregateExec(node, [exch], s.conf, mode="complete")
        rows = []
        names = list(agg.schema.names)
        for p in range(n_out):
            with TaskContext(partition_id=p) as ctx:
                for b in agg.execute_partition(ctx, p):
                    rows.extend(to_arrow(b, names).to_pylist())
        return sorted(rows, key=lambda r: r["k"])

    result = run()  # warm + capture for the equality check
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best, result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--nout", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    t = _table(args.rows)

    out = {"rows": args.rows, "n_out": args.nout}
    results = {}
    for mode in ("compact", "masked"):
        rp = bench_repartition(t, mode, args.nout, args.reps)
        gb, res = bench_groupby(t, mode, args.nout, args.reps)
        results[mode] = res
        out[mode] = {"repartition_s": round(rp, 4),
                     "repartition_groupby_s": round(gb, 4)}
        print(f"{mode:8s} repartition: {rp*1e3:8.1f} ms   "
              f"repartition+group-by: {gb*1e3:8.1f} ms")

    same = results["compact"] == results["masked"]
    out["identical_results"] = same
    out["compact_speedup_groupby"] = round(
        out["masked"]["repartition_groupby_s"]
        / out["compact"]["repartition_groupby_s"], 3)
    out["compact_speedup_repartition"] = round(
        out["masked"]["repartition_s"] / out["compact"]["repartition_s"], 3)
    print(json.dumps(out))
    if not same:
        print("FAIL: compact and masked query results differ",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
