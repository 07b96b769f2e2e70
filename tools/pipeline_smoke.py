"""CI smoke for pipelined execution (runtime/pipeline.py): on a small
multi-batch query the pipeline boundary must actually engage (depth
recorded, producer time observed — i.e. host work ran on the pool and
overlapped the consumer), a LIMIT early exit must cancel the producer,
and neither path may leak a thread. Fast (<15s); wired into
tools/ci_check.sh.

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402


def _non_pool_threads():
    return {t for t in threading.enumerate()
            if not t.name.startswith("rapids-host-pool")}


def main() -> int:
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.session import TpuSession

    rng = np.random.default_rng(3)
    t = pa.table({"k": rng.integers(0, 50, 40_000),
                  "v": rng.uniform(0, 1, 40_000)})
    s = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "4096"})

    before = _non_pool_threads()
    r = (s.create_dataframe(t, num_partitions=1)
         .filter(col("v") > lit(0.25))
         .group_by("k").agg(F.count().alias("n"),
                            F.sum(col("v")).alias("sv"))).collect()
    assert r.num_rows == 50, r.num_rows
    lm = s.last_metrics()
    pipe = [v for k, v in lm.items() if k.startswith("PipelineExec")]
    assert pipe, f"no PipelineExec in plan: {sorted(lm)}"
    depth = max(v.get("pipelineDepth", 0) for v in pipe)
    produced = sum(v.get("pipelineProducerTime", 0) for v in pipe)
    batches = sum(v.get("numOutputBatches", 0) for v in pipe)
    assert depth >= 1, "pipeline fell back to synchronous"
    assert batches >= 2, f"want a multi-batch query, got {batches} batches"
    assert produced > 0, "no producer-side work observed — no overlap"

    # LIMIT early exit: producer cancelled, nothing leaked
    r2 = (s.create_dataframe(t, num_partitions=1)
          .filter(col("v") >= lit(0.0)).limit(5)).collect()
    assert r2.num_rows == 5
    leaked = _non_pool_threads() - before
    assert not leaked, f"leaked threads: {[t.name for t in leaked]}"

    print(f"pipeline smoke OK: depth={depth} batches={batches} "
          f"producer_ms={produced / 1e6:.1f} no leaked threads")
    return 0


if __name__ == "__main__":
    sys.exit(main())
