"""Whole-stage compilation of expression lists.

The TPU-idiomatic replacement for cuDF's kernel-per-expression model
(reference GpuProjectExec/GpuFilterExec calling one cudf kernel per op,
basicPhysicalOperators.scala): an entire projection/filter expression list
is traced once into a single jitted XLA computation per (expression
fingerprint, batch capacity bucket, column layout). XLA fuses the whole
stage; num_rows is a traced scalar so row-count changes don't recompile.

ANSI errors surface as per-code boolean planes returned from the jitted fn;
the host raises SparkException if any fire (data-dependent raising cannot
happen inside a trace).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnVector, ColumnarBatch, carry_host_stats, host_planes,
)
from spark_rapids_tpu.expr.core import EvalCtx, Expression, SparkException
from spark_rapids_tpu.runtime import compile_cache as _cc


def _planes_of(col: ColumnVector):
    if isinstance(col.data, dict):
        out = dict(col.data)
        out["validity"] = col.validity
        return out
    return {"data": col.data, "validity": col.validity}


def _col_from_planes(planes, dtype: T.DataType) -> ColumnVector:
    planes = dict(planes)
    validity = planes.pop("validity")
    if "data" in planes:
        return ColumnVector(dtype, planes["data"], validity)
    return ColumnVector(dtype, planes, validity)


def _layout_key(col: ColumnVector):
    if isinstance(col.data, dict):
        kind = ("dict" if "codes" in col.data else
                "arr" if "child" in col.data else
                "map" if "keys" in col.data else
                "struct" if "children" in col.data else "str")
        parts = []
        for k in sorted(col.data):
            v = col.data[k]
            if isinstance(v, ColumnVector):
                parts.append((k, _layout_key(v)))
            elif isinstance(v, list):
                parts.append((k, tuple(_layout_key(x) for x in v)))
            else:
                parts.append((k, v.shape))
        return (kind,) + tuple(parts) + (col.validity is None,)
    return (str(col.data.dtype), col.data.shape, col.validity is None)


def run_stage(exprs: Sequence[Expression], batch: ColumnarBatch,
              ansi: bool = False) -> List[ColumnVector]:
    """Evaluate expressions over a batch as one jitted stage."""
    # shape discipline (runtime/shapes.py): capacities arriving here are
    # bucketed BY CONSTRUCTION — every capacity decision in the engine
    # routes through round_capacity, which delegates to the bucket
    # ladder — so the capacity in the cache key below ranges over a
    # small set and traces share across batches and queries. (Padding
    # in-place here would be unsound: callers hold the ORIGINAL batch's
    # planes and combine them with these outputs — see
    # shapes.ensure_bucketed for the ingestion-side canonicalizer.
    # The one deliberate off-ladder source, masked concat's
    # sum-of-capacities, is bounded by its input buckets.)
    fp = tuple(e.fingerprint() for e in exprs)
    layout = tuple(_layout_key(c) for c in batch.columns)
    key = (fp, layout, batch.capacity, ansi)
    in_dtypes = [c.dtype for c in batch.columns]
    out_dtypes = [e.data_type() for e in exprs]
    cap = batch.capacity  # capture the int, NOT the batch (a closure
    # holding the batch would pin its device planes in the stage cache)

    def build():
        def stage(col_planes, num_rows, live):
            cols = [_col_from_planes(p, dt) for p, dt in zip(col_planes, in_dtypes)]
            ctx = EvalCtx(cols, num_rows, cap, ansi, live=live)
            outs = [e.eval_tpu(ctx) for e in exprs]
            out_planes = [_planes_of(c) for c in outs]
            err = {code: mask for code, mask in ctx.errors}
            return out_planes, err
        return stage

    # the sanctioned compile choke point (runtime/compile_cache.py):
    # storage, hit/miss stats, first-call compile attribution
    fn = _cc.get("run_stage", key, build)

    from spark_rapids_tpu.columnar.batch import traced_rows
    from spark_rapids_tpu.exec import fuse
    from spark_rapids_tpu.runtime import lifecycle as _lc
    _lc.check_current()  # run_stage is the OTHER per-batch dispatch path
    fuse.notify_dispatch(("run_stage", fp))  # dispatch-budget hook
    col_planes = [_planes_of(c) for c in batch.columns]
    out_planes, err = fn(col_planes,
                         jnp.asarray(traced_rows(batch.num_rows), jnp.int32),
                         batch.live_mask())
    raise_errors(err)
    outs = [_col_from_planes(p, dt) for p, dt in zip(out_planes, out_dtypes)]
    carry_bounds(exprs, batch.columns, outs)
    return outs


def carry_bounds(exprs, in_cols, out_cols) -> None:
    """Carry the host-side column stats (`bounds`, `str_width`: metadata,
    not pytree leaves) across a jit boundary for passthrough column
    references."""
    from spark_rapids_tpu.expr.core import Alias, BoundRef
    for e, o in zip(exprs, out_cols):
        inner = e.children[0] if isinstance(e, Alias) else e
        if isinstance(inner, BoundRef) and inner.index < len(in_cols):
            carry_host_stats([in_cols[inner.index]], [o])


def raise_errors(err: Dict[str, jax.Array]) -> None:
    """Check ANSI error planes from a fused stage. Only synchronizes when
    the stage ran in ANSI mode and produced error masks: ONE read of
    every plane's any()."""
    if err:
        hit = host_planes([jnp.any(mask) for mask in err.values()], "flags")
        for code, h in zip(err, hit):
            if h:
                raise SparkException(f"[{code}] ANSI mode error in stage")


def run_projection(exprs: Sequence[Expression], batch: ColumnarBatch,
                   ansi: bool = False) -> ColumnarBatch:
    cols = run_stage(exprs, batch, ansi)
    return ColumnarBatch(cols, batch.num_rows, batch.row_mask)


def can_compile(e: Expression) -> Tuple[bool, str]:
    """Best-effort static check that an expression will trace on device;
    the overrides engine uses this plus the registry checks."""
    sup = getattr(e, "supported_on_tpu", None)
    if sup is not None and not sup():
        return False, f"{type(e).__name__} not supported on TPU"
    for c in e.children:
        ok, why = can_compile(c)
        if not ok:
            return False, why
    return True, ""
