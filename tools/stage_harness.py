"""Direct drives of one stage over device-resident batches, shared by
tools/flight_smoke.py, tools/sanitizer_smoke.py, tools/trace_overhead.py
and tests/test_trace.py.

A filter + project (+ group-by) pipeline fed by MANY small batches is the
dispatch-bound shape those checks count instrumentation and lock traffic
on: every batch costs a fixed number of entry-point calls. Nothing here
times anything; the importer decides the backend before it imports jax.
"""
from __future__ import annotations

import jax
import numpy as np
import pyarrow as pa


def _table(rows: int) -> pa.Table:
    rng = np.random.default_rng(11)
    return pa.table({
        "k": rng.integers(0, 2000, rows),
        "g": rng.uniform(0, 64, rows).round(0),  # float key: general agg
        "v": rng.integers(-(1 << 30), 1 << 30, rows),
        "d": rng.uniform(-1e6, 1e6, rows),
    })


def _device_batches(t: pa.Table, batch_rows: int):
    from spark_rapids_tpu.columnar.batch import from_arrow
    batches = [from_arrow(t.slice(o, batch_rows))
               for o in range(0, t.num_rows, batch_rows)]
    jax.block_until_ready(jax.tree_util.tree_leaves(batches))
    return batches


def _session(fused: bool, batch_rows: int):
    from spark_rapids_tpu.sql.session import TpuSession
    return TpuSession({
        "spark.rapids.sql.stageFusion.enabled": str(fused).lower(),
        "spark.rapids.sql.reader.batchSizeRows": str(batch_rows),
    })


def _query(s, t: pa.Table, key: str, grouped: bool):
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.sql import functions as F
    df = (s.create_dataframe(t, num_partitions=1)
          .filter((col("v") > lit(-(1 << 29))) & (col("d") < lit(9e5)))
          .select(col(key), (col("v") % lit(9973)).alias("m"),
                  (col("d") * lit(0.5) + lit(1.0)).alias("dd")))
    if grouped:
        df = df.group_by(col(key)).agg(F.sum("m").alias("sm"),
                                       F.count().alias("n"))
    return df


def _reroot(chain_root, source) -> None:
    """Replace the chain's scan leaf with a pre-materialized source."""
    from spark_rapids_tpu.exec import tpu_nodes as X
    cur = chain_root
    while cur.children and not isinstance(cur.children[0],
                                          X.InMemoryScanExec):
        cur = cur.children[0]
    cur.children = [source]


def _drive_of(root):
    """A callable that runs `root`'s one partition to the end and waits
    for every output buffer."""
    from spark_rapids_tpu.runtime.task import TaskContext

    def drive():
        outs = []
        with TaskContext(partition_id=0) as ctx:
            for b in root.execute_partition(ctx, 0):
                outs.extend(jax.tree_util.tree_leaves(b))
        jax.block_until_ready(outs)

    return drive


def make_chain_stage(rows: int, batch_rows: int, fused: bool):
    """The drive of the Filter→Project stage over `rows` rows in device
    batches of `batch_rows`: fused it is ONE dispatch per batch
    (FusedStageExec), unfused two."""
    from spark_rapids_tpu.exec import tpu_nodes as X
    from spark_rapids_tpu.plan.overrides import convert_plan

    t = _table(rows)
    s = _session(fused, batch_rows)
    df = _query(s, t, "k", grouped=False)
    root, _ = convert_plan(df.plan, s.conf)
    _reroot(root, X._MaterializedExec(
        df.plan, _device_batches(t, batch_rows), s.conf))
    return _drive_of(root)


def make_partial_agg_stage(rows: int, batch_rows: int, fused: bool):
    """The drive of Filter→Project→partial-HashAggregate (float key: the
    general update path, so fusion composes the WHOLE stage into one
    dispatch per batch, HashAggregateExec.pre_chain)."""
    from spark_rapids_tpu.exec import tpu_nodes as X
    from spark_rapids_tpu.exec.stage_fusion import fuse_stages
    from spark_rapids_tpu.plan import nodes as P
    from spark_rapids_tpu.plan.overrides import convert_plan

    t = _table(rows)
    s = _session(fused, batch_rows)
    df = _query(s, t, "g", grouped=True)
    node = df.plan
    while not isinstance(node, P.Aggregate):
        node = node.children[0]
    chain_root, _ = convert_plan(node.children[0], s.conf)
    _reroot(chain_root, X._MaterializedExec(
        node.children[0], _device_batches(t, batch_rows), s.conf))
    agg = X.HashAggregateExec(node, [chain_root], s.conf, mode="partial")
    return _drive_of(fuse_stages(agg, s.conf))
