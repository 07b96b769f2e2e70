"""spark-rapids-tpu: a TPU-native columnar SQL execution framework.

A from-scratch re-design of the capabilities of NVIDIA spark-rapids
(reference: /root/reference, ~25.02.0-SNAPSHOT) for TPU hardware:

- Plan-rewrite engine with per-operator tagging, CPU fallback, and explain
  output (reference: sql-plugin/.../GpuOverrides.scala, RapidsMeta.scala).
- Columnar batch currency held in device HBM as Arrow-layout JAX arrays
  (reference: GpuColumnVector.java), with bucketed static shapes so XLA
  compiles each operator stage once per size class.
- Whole-stage compilation: each projection/filter/aggregate segment traces
  into a single jitted XLA computation instead of one kernel per expression
  (the TPU-idiomatic answer to cuDF's kernel-per-op model).
- Device & memory runtime: HBM budget accounting, spill (device->host->disk),
  retry-on-OOM with batch splitting, task semaphore (reference:
  GpuSemaphore.scala, spill/SpillFramework.scala, RmmRapidsRetryIterator.scala).
- Shuffle: host-staged flat serializer (kudo analog) plus an ICI all-to-all
  collective fast path over a jax.sharding.Mesh (reference: §2.7 of SURVEY.md).

Nothing in this package is a translation of the reference's Scala/CUDA code;
file-level docstrings cite reference files only to document behavioural parity.
"""

__version__ = "0.1.0"

# Spark SQL semantics require true 64-bit lanes (bigint, double, timestamp).
# XLA emulates i64/f64 on TPU where the hardware lacks them; correctness over
# parity with 32-bit defaults.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

from spark_rapids_tpu.config import RapidsConf, conf  # noqa: F401
from spark_rapids_tpu.types import (  # noqa: F401
    DataType, BooleanType, Int8Type, Int16Type, Int32Type, Int64Type,
    Float32Type, Float64Type, StringType, DateType, TimestampType,
    DecimalType, NullType,
)
