"""Micro-benchmark: whole-stage vertical fusion vs per-operator dispatch,
end-to-end on the CPU backend (tools/bench_exchange.py's shape).

Three measurements over a filter + project + group-by pipeline fed by
MANY small batches (the dispatch-bound regime the fusion pass targets —
every dispatch has a fixed host-side cost; the CPU backend's
per-dispatch overhead is the proxy):

1. pipeline: the full query through the session API (collect), int group
   key — scan upload and arrow hand-back included, so the fusion win is
   diluted by shared I/O;
2. chain_stage (direct exec drive over DEVICE-RESIDENT batches, the
   bench_exchange.py idiom): the Filter→Project stage alone — fused it is
   ONE dispatch per batch (FusedStageExec), unfused two;
3. partial_agg_stage (direct drive, device-resident, float group key so
   the aggregate takes the general update path): Filter→Project→partial-
   HashAggregate — fused, the WHOLE stage is one dispatch per batch
   (HashAggregateExec.pre_chain), unfused three.

Run:  python tools/bench_fusion.py [--rows 400000] [--batch 2048]
                                   [--parts 4] [--reps 7]

Prints per-mode wall-clock and a JSON summary line; exits nonzero if the
fused and unfused pipelines disagree on query results (they must be
identical).

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402


def _table(rows: int) -> pa.Table:
    rng = np.random.default_rng(11)
    return pa.table({
        "k": rng.integers(0, 2000, rows),
        "g": rng.uniform(0, 64, rows).round(0),  # float key: general agg
        "v": rng.integers(-(1 << 30), 1 << 30, rows),
        "d": rng.uniform(-1e6, 1e6, rows),
    })


def _session(fused: bool, batch_rows: int):
    from spark_rapids_tpu.sql.session import TpuSession
    return TpuSession({
        "spark.rapids.sql.stageFusion.enabled": str(fused).lower(),
        "spark.rapids.sql.reader.batchSizeRows": str(batch_rows),
    })


def _query(s, t: pa.Table, parts: int, key: str, grouped: bool):
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.sql import functions as F
    df = (s.create_dataframe(t, num_partitions=parts)
          .filter((col("v") > lit(-(1 << 29))) & (col("d") < lit(9e5)))
          .select(col(key), (col("v") % lit(9973)).alias("m"),
                  (col("d") * lit(0.5) + lit(1.0)).alias("dd")))
    if grouped:
        df = df.group_by(col(key)).agg(F.sum("m").alias("sm"),
                                       F.count().alias("n"))
    return df


def _norm(rows, key):
    def k(r):
        v = r[key]
        bad = v is None or (isinstance(v, float) and math.isnan(v))
        return (bad, 0 if bad else v)
    return sorted(rows, key=k)


def _device_batches(t: pa.Table, batch_rows: int):
    from spark_rapids_tpu.columnar.batch import from_arrow
    batches = [from_arrow(t.slice(o, batch_rows))
               for o in range(0, t.num_rows, batch_rows)]
    jax.block_until_ready(jax.tree_util.tree_leaves(batches))
    return batches


def _reroot(chain_root, source):
    """Replace the chain's scan leaf with a pre-materialized source."""
    from spark_rapids_tpu.exec import tpu_nodes as X
    cur = chain_root
    while cur.children and not isinstance(cur.children[0],
                                          X.InMemoryScanExec):
        cur = cur.children[0]
    cur.children = [source]
    return chain_root


def _paired_best(run_fused, run_unfused, reps: int):
    """Interleave fused/unfused reps (ABBA) so machine-load drift lands on
    both modes equally; report the best of each."""
    best = {"fused": float("inf"), "unfused": float("inf")}
    order = [("fused", run_fused), ("unfused", run_unfused)]
    for i in range(reps):
        for mode, run in (order if i % 2 == 0 else reversed(order)):
            t0 = time.perf_counter()
            run()
            best[mode] = min(best[mode], time.perf_counter() - t0)
    return best["fused"], best["unfused"]


def make_pipeline(t, fused, parts, batch_rows, batches):
    """(run, result) for the full query through the session API."""
    def run():
        s = _session(fused, batch_rows)
        return _query(s, t, parts, "k", grouped=True).collect().to_pylist()
    return run, lambda: _norm(run(), "k")


def make_chain_stage(t, fused, parts, batch_rows, batches):
    """(run, result) for the Filter→Project stage over device batches."""
    from spark_rapids_tpu.columnar.batch import to_arrow
    from spark_rapids_tpu.exec import tpu_nodes as X
    from spark_rapids_tpu.plan.overrides import convert_plan
    from spark_rapids_tpu.runtime.task import TaskContext

    s = _session(fused, batch_rows)
    df = _query(s, t, 1, "k", grouped=False)
    root, _ = convert_plan(df.plan, s.conf)
    _reroot(root, X._MaterializedExec(df.plan, batches, s.conf))

    def drain(rows=None):
        outs = []
        with TaskContext(partition_id=0) as ctx:
            for b in root.execute_partition(ctx, 0):
                if rows is not None:
                    rows.extend(to_arrow(b, ["k", "m", "dd"]).to_pylist())
                else:
                    outs.extend(jax.tree_util.tree_leaves(b))
        jax.block_until_ready(outs)

    def result():
        rows = []
        drain(rows)
        return _norm(rows, "k")

    return drain, result


def make_partial_agg_stage(t, fused, parts, batch_rows, batches):
    """(run, result) for Filter→Project→partial-HashAggregate (float key:
    the general update path, so fusion composes the WHOLE stage)."""
    from spark_rapids_tpu.columnar.batch import to_arrow
    from spark_rapids_tpu.exec import tpu_nodes as X
    from spark_rapids_tpu.exec.stage_fusion import fuse_stages
    from spark_rapids_tpu.plan import nodes as P
    from spark_rapids_tpu.plan.overrides import convert_plan
    from spark_rapids_tpu.runtime.task import TaskContext

    s = _session(fused, batch_rows)
    df = _query(s, t, 1, "g", grouped=True)
    node = df.plan
    while not isinstance(node, P.Aggregate):
        node = node.children[0]
    chain_root, _ = convert_plan(node.children[0], s.conf)
    _reroot(chain_root,
            X._MaterializedExec(node.children[0], batches, s.conf))
    agg = X.HashAggregateExec(node, [chain_root], s.conf, mode="partial")
    root = fuse_stages(agg, s.conf)
    names = [f.name for f in root.state_fields()]

    def drain(rows=None):
        outs = []
        with TaskContext(partition_id=0) as ctx:
            for b in root.execute_partition(ctx, 0):
                if rows is not None:
                    rows.extend(to_arrow(b, names).to_pylist())
                else:
                    outs.extend(jax.tree_util.tree_leaves(b))
        jax.block_until_ready(outs)

    def result():
        rows = []
        drain(rows)
        return _norm(rows, "g")

    return drain, result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--batch", type=int, default=2048,
                    help="rows per batch (small = dispatch-bound)")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args()
    t = _table(args.rows)
    batches = _device_batches(t, args.batch)

    out = {"rows": args.rows, "batch_rows": args.batch,
           "parts": args.parts, "n_batches": len(batches)}
    ok = True
    scenarios = [("pipeline", make_pipeline),
                 ("chain_stage", make_chain_stage),
                 ("partial_agg_stage", make_partial_agg_stage)]
    for name, make in scenarios:
        run_f, res_f = make(t, True, args.parts, args.batch, batches)
        run_u, res_u = make(t, False, args.parts, args.batch, batches)
        same = res_f() == res_u()  # warms both kernel caches too
        bf, bu = _paired_best(run_f, run_u, args.reps)
        ok = ok and same
        print(f"{name:18s} fused: {bf * 1e3:8.1f} ms   "
              f"unfused: {bu * 1e3:8.1f} ms   ({bu / bf:.2f}x)")
        out[name] = {"fused_s": round(bf, 4), "unfused_s": round(bu, 4),
                     "speedup": round(bu / bf, 3),
                     "identical_results": same}

    print(json.dumps(out))
    if not ok:
        print("FAIL: fused and unfused query results differ",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
