"""Time the distinct level of TPC-DS query 28's first band, both ways.

Query 28's distinct level (plan/distinct.py) groups one band's rows of
store_sales by the list price and carries avg's and count's states. At
dsdgen's SF10 the resident table is one batch of 33,554,432 rows of
capacity, 28,800,991 live, and a band keeps some 300 K of them. This
script builds the four columns the query reads on the device from a seed
(the domains of benchmark/tpcds_datagen.py) and times, as the engine runs
them (`exec/tpu_nodes._AggKernels`):

- ``mask``: the update over the masked batch, the filter fused in: the
  group-by sorts the whole capacity with the dead rows last;
- ``compact``: the filter and the inputs as one program, the live rows
  compacted (one count read sizes them), then the group-by over those
  (`HashAggregateExec._distinct_inputs`, the engine's path).

It prints one JSON line: the device, each path's median and spread of
``--reps`` timed runs after a warm run, the groups and live rows each
found (they must agree).

Usage (on the chip): python tools/distinct_agg_timing.py [--reps 10]
    [--rows 28800991] [--capacity 33554432] [--seed 7]
"""
import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from spark_rapids_tpu import types as T  # noqa: E402
from spark_rapids_tpu.columnar.batch import ColumnarBatch, ColumnVector  # noqa: E402
from spark_rapids_tpu.expr import core as E  # noqa: E402
from spark_rapids_tpu.expr.aggregates import Average, Count, NamedAgg  # noqa: E402
from spark_rapids_tpu.exec.tpu_nodes import _AggKernels  # noqa: E402
from spark_rapids_tpu.ops import kernels as K  # noqa: E402
from spark_rapids_tpu.plan import nodes as P  # noqa: E402
from spark_rapids_tpu.plan.distinct import plan_aggregate  # noqa: E402
from spark_rapids_tpu.sql import functions as F  # noqa: E402

COLUMNS = (("ss_quantity", pa.int32()), ("ss_list_price", pa.float64()),
           ("ss_coupon_amt", pa.float64()), ("ss_wholesale_cost",
                                             pa.float64()))


def band_one():
    """Query 28's first band: the filter, and its aggregate's distinct
    level bound to the four columns."""
    q, lp, cp, wc = (E.col(n) for n, _ in COLUMNS)
    cond = (q >= 0) & (q <= 5) & (
        ((lp >= 8) & (lp <= 18)) | ((cp >= 459) & (cp <= 1459))
        | ((wc >= 57) & (wc <= 77)))
    src = P.InMemorySource(pa.schema(list(COLUMNS)).empty_table())
    upper = plan_aggregate([], [NamedAgg(Average(lp), "lp"),
                                NamedAgg(Count(lp), "cnt"),
                                NamedAgg(F.count_distinct(lp), "cntd")], src)
    lower = upper.children[0]
    return _AggKernels(lower.group_exprs, lower.group_names, lower.aggs,
                       P.bind_expr(cond, src.schema))


def make_batch(cap, nrows, seed):
    """The four columns on the device, in tpcds_datagen's domains: whole
    cents, a twentieth of each column null, padding past `nrows`."""
    k = jax.random.split(jax.random.PRNGKey(seed % (1 << 31)), 8)
    live = jnp.arange(cap) < nrows
    qty = jax.random.randint(k[0], (cap,), 1, 101).astype(jnp.int32)
    whs = jax.random.randint(k[1], (cap,), 100, 10_001)
    lst = whs + whs * jax.random.randint(k[2], (cap,), 0, 101) // 100
    coupon = jnp.where(jax.random.uniform(k[3], (cap,)) < 0.2,
                       jax.random.randint(k[4], (cap,), 0, 2_000_000), 0)
    cols = []
    for i, (plane, dt) in enumerate(((qty, T.INT32), (lst, T.FLOAT64),
                                     (coupon, T.FLOAT64),
                                     (whs, T.FLOAT64))):
        if dt is T.FLOAT64:
            plane = plane.astype(jnp.float64) / 100.0
        valid = live & (jax.random.uniform(k[4 + i], (cap,)) >= 0.045)
        cols.append(ColumnVector(dt, plane, valid))
    return ColumnarBatch(cols, nrows)


def run(path, kern, batch):
    """One distinct level over `batch`: (its output, the live rows it
    grouped; None over the mask, where they are not counted)."""
    if path == "mask":
        out, _ = kern.update(batch, False)
        return out, None
    masked, _ = kern.project_inputs(batch, False)
    small = K.compact_batch(masked)
    out, _ = kern.over_inputs().update(small, False)
    return out, small.num_rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=28_800_991)
    ap.add_argument("--capacity", type=int, default=1 << 25)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    kern = band_one()
    batch = make_batch(args.capacity, args.rows, args.seed)
    out = {"device": jax.devices()[0].device_kind,
           "capacity": args.capacity, "rows": args.rows}
    for path in ("mask", "compact"):
        res, live = run(path, kern, batch)          # warm: compiles
        jax.block_until_ready(res.columns[-1].data)
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            res, live = run(path, kern, batch)
            jax.block_until_ready(res.columns[-1].data)
            times.append(time.perf_counter() - t0)
        ms = sorted(t * 1e3 for t in times)
        out[path] = {"median_ms": statistics.median(ms),
                     "min_ms": ms[0], "max_ms": ms[-1],
                     "groups": int(res.num_rows),
                     "count_state": int(np.asarray(jnp.sum(jnp.where(
                         res.live_mask(), res.columns[-1].data, 0))))}
        if live is not None:
            out[path]["live_rows"] = int(live)
    print(json.dumps(out))
    assert out["mask"]["groups"] == out["compact"]["groups"]
    assert out["mask"]["count_state"] == out["compact"]["count_state"]


if __name__ == "__main__":
    main()
