"""CPU reference backend: a pandas/numpy interpreter of the plan algebra.

This plays the role CPU Spark plays for the reference's differential test
harness (integration_tests asserts GPU results == CPU results;
SURVEY.md §4.1): an independent implementation the TPU engine is diffed
against, and the fallback executor for operators/expressions the TPU
planner rejects (reference per-operator fallback).

Implementation notes:
- Data currency is List[CpuCol] (numpy values + validity) per plan schema.
- Grouping/joining keys are pre-normalized to exact integer codes so SQL
  semantics hold where pandas' own NaN/NA rules differ (NaN groups equal,
  nulls group together, null join keys never match).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import CpuCol, Expression
from spark_rapids_tpu.expr.aggregates import CountAll, NamedAgg
from spark_rapids_tpu.plan import nodes as P


# ---------------------------------------------------------------------------
# pyarrow <-> CpuCol
# ---------------------------------------------------------------------------

def table_to_cols(table: pa.Table) -> List[CpuCol]:
    out = []
    for i, field in enumerate(table.schema):
        dtype = T.from_arrow(field.type)
        arr = table.column(i).combine_chunks()
        valid = np.ones(len(arr), np.bool_) if arr.null_count == 0 \
            else np.asarray(arr.is_valid())
        if isinstance(dtype, (T.StringType, T.ArrayType, T.StructType,
                              T.MapType)):
            vals = np.empty(len(arr), object)
            vals[:] = arr.to_pylist()
        elif isinstance(dtype, T.DecimalType):
            vals = np.array([0 if v is None else int(v.scaleb(dtype.scale))
                             for v in arr.to_pylist()], np.int64)
        elif isinstance(dtype, T.TimestampType):
            vals = np.asarray(arr.cast(pa.timestamp("us")).fill_null(0)) \
                .astype("datetime64[us]").astype(np.int64)
        elif isinstance(dtype, T.DateType):
            vals = np.asarray(arr.fill_null(0)).astype("datetime64[D]").astype(np.int32)
        elif isinstance(dtype, T.NullType):
            vals = np.zeros(len(arr), np.int8)
            valid = np.zeros(len(arr), np.bool_)
        else:
            fill = False if pa.types.is_boolean(arr.type) else 0
            vals = np.asarray(arr.fill_null(fill)).astype(dtype.np_dtype)
        out.append(CpuCol(dtype, vals, valid))
    return out


def cols_to_table(cols: List[CpuCol], names: List[str]) -> pa.Table:
    arrays = []
    fields = []
    for c, name in zip(cols, names):
        at = T.to_arrow(c.dtype)
        if isinstance(c.dtype, T.StringType):
            vals = [v if (ok and isinstance(v, str)) else None
                    for v, ok in zip(c.values, c.valid)]
            arr = pa.array(vals, type=at)
        elif isinstance(c.dtype, (T.ArrayType, T.StructType, T.MapType)):
            vals = [v if ok else None for v, ok in zip(c.values, c.valid)]
            arr = pa.array(vals, type=at)
        elif isinstance(c.dtype, T.NullType):
            arr = pa.nulls(len(c.values), type=at)
        elif isinstance(c.dtype, T.DecimalType):
            import decimal
            vals = [decimal.Decimal(int(v)).scaleb(-c.dtype.scale) if ok else None
                    for v, ok in zip(c.values, c.valid)]
            arr = pa.array(vals, type=at)
        elif isinstance(c.dtype, T.TimestampType):
            arr = pa.array(c.values.astype("datetime64[us]"), type=at, mask=~c.valid)
        elif isinstance(c.dtype, T.DateType):
            arr = pa.array(c.values.astype(np.int32).astype("datetime64[D]"),
                           type=at, mask=~c.valid)
        else:
            arr = pa.array(c.values.astype(c.dtype.np_dtype), type=at, mask=~c.valid)
        arrays.append(arr)
        fields.append(pa.field(name, at))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))


def _gather_cols(cols: List[CpuCol], idx: np.ndarray) -> List[CpuCol]:
    """Row gather with -1 -> null."""
    out = []
    oob = idx < 0
    safe = np.where(oob, 0, idx)
    for c in cols:
        is_obj = isinstance(c.dtype, (T.StringType, T.ArrayType,
                                      T.StructType, T.MapType))
        if len(c.values) == 0:
            np_dt = object if is_obj else c.dtype.np_dtype
            out.append(CpuCol(c.dtype, np.zeros(len(idx), np_dt),
                              np.zeros(len(idx), np.bool_)))
            continue
        vals = c.values[safe]
        if is_obj:
            vals = vals.copy()
            vals[oob] = None
        valid = c.valid[safe] & ~oob
        out.append(CpuCol(c.dtype, vals, valid))
    return out


# ---------------------------------------------------------------------------
# Key normalization for grouping/joining/sorting (exact SQL semantics)
# ---------------------------------------------------------------------------

def _norm_key_np(c: CpuCol, shared_dict: Optional[dict] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (uint64 order-preserving codes, null_mask). shared_dict lets
    join sides share one string dictionary."""
    nulls = ~c.valid
    if isinstance(c.dtype, T.StringType):
        if shared_dict is None:
            uniq = sorted({v for v, ok in zip(c.values, c.valid) if ok and v is not None})
            shared_dict = {s: i for i, s in enumerate(uniq)}
        codes = np.array([shared_dict.get(v, 0) if ok else 0
                          for v, ok in zip(c.values, c.valid)], np.uint64)
        return codes, nulls
    if isinstance(c.dtype, (T.Float32Type, T.Float64Type)):
        v = c.values.astype(np.float64)
        v = np.where(np.isnan(v), np.nan, v)
        v = np.where(v == 0.0, 0.0, v)  # -0.0 -> +0.0
        bits = v.view(np.uint64) if v.dtype == np.float64 else v.astype(np.float64).view(np.uint64)
        bits = np.where(np.isnan(v), np.uint64(0x7FF8000000000000), bits)
        neg = (bits >> np.uint64(63)) != 0
        key = np.where(neg, ~bits, bits | np.uint64(1 << 63))
        return np.where(nulls, np.uint64(0), key), nulls
    if isinstance(c.dtype, (T.ArrayType, T.StructType)):
        # Spark nested ordering: lexicographic, null element first, NaN
        # greatest. Rank rows by a recursive tuple encoding.
        keys = [(_encode_sortable(v, c.dtype) if ok else ())
                for v, ok in zip(c.values, c.valid)]
        order = sorted(range(len(keys)), key=lambda i: keys[i])
        ranks = np.zeros(len(keys), np.uint64)
        for pos, idx in enumerate(order):
            ranks[idx] = pos
        return np.where(nulls, np.uint64(0), ranks), nulls
    if isinstance(c.dtype, T.MapType):
        from spark_rapids_tpu.expr.core import SparkException
        raise SparkException("map type cannot be used in ORDER BY or "
                             "grouping keys")
    key = c.values.astype(np.int64).view(np.uint64) ^ np.uint64(1 << 63)
    return np.where(nulls, np.uint64(0), key), nulls


def _encode_sortable(v, dt: T.DataType):
    """Recursive tuple encoding whose python ordering matches Spark's
    nested-type ordering (element null-first, NaN greatest)."""
    if isinstance(dt, T.ArrayType):
        return tuple((0,) if x is None else (1, _encode_sortable(x, dt.element))
                     for x in v)
    if isinstance(dt, T.StructType):
        return tuple(
            (0,) if v.get(f.name) is None
            else (1, _encode_sortable(v[f.name], f.dtype))
            for f in dt.fields)
    if isinstance(dt, (T.Float32Type, T.Float64Type)):
        fv = float(v)
        if fv != fv:
            return (2, 0.0)
        return (1, 0.0 + fv)  # -0.0 -> +0.0 for total-order ties
    return (1, v)


def _shared_string_dict(*cols: CpuCol) -> dict:
    uniq = set()
    for c in cols:
        uniq |= {v for v, ok in zip(c.values, c.valid) if ok and v is not None}
    return {s: i for i, s in enumerate(sorted(uniq))}


# ---------------------------------------------------------------------------
# Node interpreters
# ---------------------------------------------------------------------------

def execute_cpu(plan: P.PlanNode, ansi: bool = False) -> pa.Table:
    cols = _exec(plan, ansi)
    return cols_to_table(cols, plan.schema.names)


def _exec(plan: P.PlanNode, ansi: bool) -> List[CpuCol]:
    return apply_node(plan, [_exec(c, ansi) for c in plan.children], ansi)


def apply_node(plan: P.PlanNode, children: List[List[CpuCol]],
               ansi: bool = False) -> List[CpuCol]:
    """Interpret one plan node given its children's results. Used both by the
    full-plan interpreter and by per-operator CPU fallback inside TPU plans
    (the reference's convertIfNeeded fallback path)."""
    if isinstance(plan, P.InMemorySource):
        return table_to_cols(plan.table)
    if isinstance(plan, P.ParquetScan):
        from spark_rapids_tpu.io import read_parquet_file
        tables = [plan.with_partition_cols(
            read_parquet_file(p, getattr(plan, "file_columns",
                                         plan.columns)), i)
            for i, p in enumerate(plan.paths)]
        table = pa.concat_tables(tables, promote_options="permissive") \
            if len(tables) > 1 else tables[0]
        return table_to_cols(table)
    if isinstance(plan, P.TextScan):
        tables = [plan.read_host(p) for p in plan.paths]
        table = pa.concat_tables(tables, promote_options="permissive") \
            if len(tables) > 1 else tables[0]
        return table_to_cols(table)
    if isinstance(plan, P.CachedRelation):
        return children[0]
    if isinstance(plan, P.ShuffleFileScan):
        from spark_rapids_tpu.columnar.batch import to_arrow
        from spark_rapids_tpu.shuffle.exchange_files import (
            read_partition_batches,
        )
        tables = []
        for r in range(plan.n_reduce):
            for b in read_partition_batches(plan.root, r):
                tables.append(to_arrow(b, plan.schema.names, "host"))
        table = pa.concat_tables(tables) if tables else \
            pa.table({n: pa.array([], T.to_arrow(t))
                      for n, t in zip(plan.schema.names, plan.schema.types)})
        return table_to_cols(table)
    if isinstance(plan, P.Range):
        vals = np.arange(plan.start, plan.end, plan.step, np.int64)
        return [CpuCol(T.INT64, vals, np.ones(len(vals), np.bool_))]
    if isinstance(plan, P.Project):
        return [e.eval_cpu(children[0], ansi) for e in plan.exprs]
    if isinstance(plan, P.Filter):
        pred = plan.condition.eval_cpu(children[0], ansi)
        keep = pred.values.astype(np.bool_) & pred.valid
        return _gather_cols(children[0], np.nonzero(keep)[0])
    if isinstance(plan, P.Aggregate):
        return _exec_aggregate(plan, children[0], ansi)
    if isinstance(plan, P.Sort):
        return _exec_sort(plan, children[0], ansi)
    if isinstance(plan, P.Limit):
        child = children[0]
        n = len(child[0].values) if child else 0
        return _gather_cols(child, np.arange(min(plan.n, n)))
    if isinstance(plan, P.Union):
        return _exec_union(plan, children)
    if isinstance(plan, P.Repartition):
        # partitioning is a physical-layout concern: row-wise the result
        # is the child unchanged (comparisons downstream ignore order)
        return children[0]
    if isinstance(plan, P.WindowNode):
        return _exec_window(plan, children[0], ansi)
    if isinstance(plan, P.Join):
        return _exec_join(plan, children[0], children[1], ansi)
    if isinstance(plan, P.Generate):
        return _exec_generate(plan, children[0], ansi)
    if isinstance(plan, P.Expand):
        child = children[0]
        parts = []
        for proj in plan.projections:
            parts.append([e.eval_cpu(child, ansi) for e in proj])
        out = []
        out_types = plan.schema.types
        for i in range(len(plan.projections[0])):
            vals = np.concatenate([_cast_vals(p[i], out_types[i]) for p in parts])
            valid = np.concatenate([p[i].valid for p in parts])
            out.append(CpuCol(out_types[i], vals, valid))
        return out
    raise NotImplementedError(f"CPU backend: {type(plan).__name__}")


def _cast_vals(c: CpuCol, dt: T.DataType):
    if isinstance(dt, (T.StringType, T.ArrayType, T.StructType, T.MapType)):
        return c.values
    return c.values.astype(dt.np_dtype)


def _exec_generate(plan: "P.Generate", child: List[CpuCol], ansi: bool
                   ) -> List[CpuCol]:
    gen = plan.generator
    src = gen.children[0].eval_cpu(child, ansi)
    is_map = isinstance(gen.children[0].data_type(), T.MapType)
    position = bool(getattr(gen, "position", False))
    outer = bool(gen.outer)
    parent_idx: List[int] = []
    pos_vals: List[int] = []
    gen_vals: List[list] = [[] for _ in plan.gen_fields]
    g_off = 1 if position else 0
    for i, (v, ok) in enumerate(zip(src.values, src.valid)):
        items = v if (ok and v is not None) else None
        if not items:
            if outer:
                parent_idx.append(i)
                pos_vals.append(None)
                for g in gen_vals:
                    g.append(None)
            continue
        for j, el in enumerate(items):
            parent_idx.append(i)
            pos_vals.append(j)
            if is_map:
                k, val = el
                gen_vals[g_off].append(k)
                gen_vals[g_off + 1].append(val)
            else:
                gen_vals[g_off].append(el)
    if position:
        gen_vals[0] = pos_vals
    out = _gather_cols([child[i] for i in plan.required],
                       np.asarray(parent_idx, np.int64))
    for (name, dt), vals in zip(plan.gen_fields, gen_vals):
        ok = [v is not None for v in vals]
        if isinstance(dt, (T.StringType, T.ArrayType, T.StructType, T.MapType)):
            arr = np.empty(len(vals), object)
            arr[:] = vals
            out.append(CpuCol(dt, arr, np.asarray(ok, np.bool_)))
        else:
            np_vals = np.array([0 if v is None else v for v in vals],
                               dt.np_dtype)
            out.append(CpuCol(dt, np_vals, np.asarray(ok, np.bool_)))
    return out


def _exec_union(plan: P.Union, parts: List[List[CpuCol]]) -> List[CpuCol]:
    out = []
    for i, f in enumerate(plan.schema.fields):
        vals = np.concatenate([_cast_vals(p[i], f.dtype) for p in parts])
        valid = np.concatenate([p[i].valid for p in parts])
        out.append(CpuCol(f.dtype, vals, valid))
    return out


def _exec_window(plan: "P.WindowNode", child: List[CpuCol], ansi: bool
                 ) -> List[CpuCol]:
    """Reference semantics for window functions, evaluated row-by-row per
    sorted partition (test-scale interpreter)."""
    from spark_rapids_tpu.expr import window as WE
    from spark_rapids_tpu.expr import aggregates as A
    n = len(child[0].values) if child else 0
    spec = plan.window_exprs[0].spec
    pc = [_norm_key_np(e.eval_cpu(child, ansi)) for e in spec.partition_exprs]
    oc = [_norm_key_np(o.expr.eval_cpu(child, ansi)) for o in spec.order_specs]
    # sort by (partition, order) with spark null ordering; lexsort's last
    # key is primary, so push order keys first then partition keys
    keys = []
    for (code, nulls), o in zip(reversed(oc), reversed(spec.order_specs)):
        nf = o.resolved_nulls_first()
        keys.append(code if o.ascending else ~code)
        keys.append(np.where(nulls, 0 if nf else 1, 1 if nf else 0).astype(np.uint8))
    for code, nulls in reversed(pc):
        keys.append(code)
        keys.append(nulls.astype(np.uint8))
    perm = np.lexsort(keys) if keys else np.arange(n)
    out = _gather_cols(child, perm)

    def boundary(cols_codes):
        b = np.zeros(n, np.bool_)
        if n:
            b[0] = True
        for code, nulls in cols_codes:
            cs, ns = code[perm], nulls[perm]
            b[1:] |= (cs[1:] != cs[:-1]) | (ns[1:] != ns[:-1])
        return b

    segb = boundary(pc)
    peerb = (segb | boundary(oc)) if oc else segb.copy()
    for w, name in zip(plan.window_exprs, plan.names):
        out.append(_one_window_cpu(w, child, perm, segb, peerb, n, ansi))
    return out


def _one_window_cpu(w, child, perm, segb, peerb, n, ansi) -> CpuCol:
    from spark_rapids_tpu.expr import window as WE
    from spark_rapids_tpu.expr import aggregates as A
    fn = w.fn
    rt = fn.result_type()
    frame = w.spec.resolved_frame()
    starts = np.flatnonzero(segb)
    bounds = list(starts) + [n]
    vals = np.zeros(n, object)
    valid = np.ones(n, np.bool_)
    src = None
    if fn.children:
        src = fn.children[0].eval_cpu(child, ansi)
        src = CpuCol(src.dtype, src.values[perm], src.valid[perm])
    for gi in range(len(starts)):
        lo, hi = bounds[gi], bounds[gi + 1]
        rows = range(lo, hi)
        if isinstance(fn, WE.RowNumber):
            for i in rows:
                vals[i] = i - lo + 1
        elif isinstance(fn, (WE.Rank, WE.DenseRank)):
            r = d = 0
            for i in rows:
                if peerb[i] or i == lo:
                    r = i - lo + 1
                    d += 1
                vals[i] = r if isinstance(fn, WE.Rank) else d
        elif isinstance(fn, WE.NTile):
            size = hi - lo
            base, rem = divmod(size, fn.n)
            for i in rows:
                pos = i - lo
                cut = (base + 1) * rem
                vals[i] = (pos // (base + 1) if pos < cut
                           else rem + (pos - cut) // max(base, 1)) + 1
        elif isinstance(fn, WE.LeadLag):
            off = fn.offset if fn.is_lead else -fn.offset
            for i in rows:
                j = i + off
                if lo <= j < hi:
                    vals[i] = src.values[j]
                    valid[i] = bool(src.valid[j])
                elif fn.default is not None:
                    vals[i] = fn.default
                else:
                    valid[i] = False
        elif isinstance(fn, WE.PercentRank):
            size = hi - lo
            r = 0
            for i in rows:
                if peerb[i] or i == lo:
                    r = i - lo + 1
                vals[i] = 0.0 if size <= 1 else (r - 1) / (size - 1)
        elif isinstance(fn, WE.CumeDist):
            size = hi - lo
            for i in rows:
                e = i
                while e + 1 < hi and not peerb[e + 1]:
                    e += 1
                vals[i] = (e - lo + 1) / size
        elif isinstance(fn, (WE.NthValue, WE.FirstValue, WE.LastValue)):
            for i in rows:
                if frame.upper is None:
                    fe = hi - 1
                elif frame.kind == "rows":
                    fe = min(i + frame.upper, hi - 1)
                else:  # range: frame end = end of peer group (+bound)
                    fe = i
                    while fe + 1 < hi and not peerb[fe + 1]:
                        fe += 1
                fs = lo
                if frame.lower is not None and frame.kind == "rows":
                    fs = max(i + frame.lower, lo)
                if isinstance(fn, WE.LastValue):
                    pos = fe
                elif isinstance(fn, WE.FirstValue):
                    pos = fs
                else:
                    pos = fs + fn.n - 1
                    if pos > fe:
                        valid[i] = False
                        continue
                if pos < fs or pos > fe:
                    valid[i] = False
                    continue
                vals[i] = src.values[pos]
                valid[i] = bool(src.valid[pos])
        elif isinstance(fn, WE.WindowAgg):
            agg = fn.fn
            for i in rows:
                if frame.kind == "range" and frame.upper == 0:
                    e = i
                    while e + 1 < hi and not peerb[e + 1]:
                        e += 1
                    a, b = lo, e
                elif frame.lower is None and frame.upper is None:
                    a, b = lo, hi - 1
                elif frame.kind == "rows":
                    a = lo if frame.lower is None else max(i + frame.lower, lo)
                    b = hi - 1 if frame.upper is None else min(i + frame.upper, hi - 1)
                else:
                    a, b = lo, i
                if isinstance(agg, A.CountAll):
                    vals[i] = max(b - a + 1, 0)
                    continue
                window_vals = [src.values[j] for j in range(a, b + 1)
                               if src.valid[j]] if b >= a else []
                if isinstance(agg, A.Count):
                    vals[i] = len(window_vals)
                elif not window_vals:
                    valid[i] = False
                elif isinstance(agg, A.Sum):
                    vals[i] = sum(window_vals)
                elif isinstance(agg, A.Average):
                    vals[i] = float(sum(window_vals)) / len(window_vals)
                elif isinstance(agg, (A.Min, A.Max)):
                    import math
                    key = (lambda x: (isinstance(x, float) and math.isnan(x), x))
                    vals[i] = (min if isinstance(agg, A.Min) else max)(
                        window_vals, key=key)
                elif isinstance(agg, (A.First, A.Last)):
                    vals[i] = window_vals[-1 if isinstance(agg, A.Last) else 0]
                elif isinstance(agg, A._MomentAgg):
                    arr = np.asarray(window_vals, np.float64)
                    ddof = 1 if isinstance(agg, (A.StddevSamp, A.VarianceSamp)) else 0
                    if len(arr) <= ddof:
                        valid[i] = False
                    elif isinstance(agg, (A.StddevSamp, A.StddevPop)):
                        vals[i] = float(np.std(arr, ddof=ddof))
                    else:
                        vals[i] = float(np.var(arr, ddof=ddof))
                else:
                    raise NotImplementedError(type(agg).__name__)
        else:
            raise NotImplementedError(type(fn).__name__)
    if isinstance(rt, T.StringType):
        np_vals = np.array([v if valid[i] else None
                            for i, v in enumerate(vals)], object)
    else:
        np_vals = np.array([v if valid[i] else 0 for i, v in enumerate(vals)]
                           ).astype(rt.np_dtype)
    return CpuCol(rt, np_vals, valid)


def _exec_sort(plan: P.Sort, child: List[CpuCol], ansi: bool) -> List[CpuCol]:
    n = len(child[0].values) if child else 0
    if n == 0:
        return child
    # np.lexsort: last key is primary
    keys = []
    for o in reversed(plan.orders):
        c = o.expr.eval_cpu(child, ansi)
        code, nulls = _norm_key_np(c)
        if not o.ascending:
            code = ~code
        nf = o.resolved_nulls_first()
        null_plane = np.where(nulls, 0 if nf else 1, 1 if nf else 0).astype(np.uint8)
        keys.append(code)
        keys.append(null_plane)
    perm = np.lexsort(keys)
    return _gather_cols(child, perm)


def _exec_aggregate(plan: P.Aggregate, child: List[CpuCol], ansi: bool) -> List[CpuCol]:
    n = len(child[0].values) if child else 0
    key_cols = [e.eval_cpu(child, ansi) for e in plan.group_exprs]

    # evaluate agg inputs (all children: min_by/max_by consume two)
    agg_inputs: List[Optional[List[CpuCol]]] = []
    for a in plan.aggs:
        if isinstance(a.fn, CountAll) or not a.fn.children:
            agg_inputs.append(None)
        else:
            agg_inputs.append([c.eval_cpu(child, ansi) for c in a.fn.children])

    if not key_cols:
        return _global_agg(plan, agg_inputs, n)

    # group ids via normalized codes
    df_data = {}
    for i, kc in enumerate(key_cols):
        code, nulls = _norm_key_np(kc)
        s = pd.array(code.view(np.int64), dtype="Int64")
        s[nulls] = pd.NA
        df_data[f"__k{i}"] = s
    df = pd.DataFrame(df_data)
    grouped = df.groupby(list(df_data.keys()), dropna=False, sort=True)
    gid = grouped.ngroup().to_numpy()
    n_groups = int(gid.max()) + 1 if n else 0
    first_idx = np.zeros(n_groups, np.int64)
    seen = np.zeros(n_groups, np.bool_)
    for i in range(n - 1, -1, -1):
        first_idx[gid[i]] = i
    out: List[CpuCol] = []
    for kc in key_cols:
        out.append(_gather_cols([kc], first_idx)[0])
    for a, inp in zip(plan.aggs, agg_inputs):
        out.append(_agg_by_gid(a, inp, gid, n_groups))
    return out


def _agg_by_gid(a: NamedAgg, inp, gid: np.ndarray,
                n_groups: int) -> CpuCol:
    from spark_rapids_tpu.expr.aggregates import MergedState, SegmentedAgg
    if isinstance(a.fn, (SegmentedAgg, MergedState)):
        return a.fn.eval_cpu_groups(inp, gid, n_groups)
    spec = a.fn.pandas_spec()
    rt = a.fn.result_type()
    if spec == "size":
        cnt = np.bincount(gid, minlength=n_groups).astype(np.int64)
        return CpuCol(T.INT64, cnt, np.ones(n_groups, np.bool_))
    assert inp is not None
    inp = inp[0]
    if isinstance(inp.dtype, (T.Float32Type, T.Float64Type)):
        # pandas conflates NaN with null; floats need explicit Spark
        # semantics (NaN is a VALUE: sums/avg propagate it, min/max use the
        # total order where NaN > +inf).
        return _agg_float_np(spec, rt, inp, gid, n_groups)
    valid = inp.valid
    if isinstance(inp.dtype, T.StringType):
        ser = pd.Series([v if ok else None for v, ok in zip(inp.values, valid)],
                        dtype=object)
    else:
        vals = inp.values.astype(np.float64) if not inp.dtype.is_integral \
            else inp.values.astype(np.int64)
        if inp.dtype.is_integral or isinstance(inp.dtype, (T.BooleanType, T.DateType,
                                                           T.TimestampType, T.DecimalType)):
            ser = pd.Series(pd.array(inp.values.astype(np.int64), dtype="Int64"))
        else:
            ser = pd.Series(pd.array(vals, dtype="Float64"))
        ser[~valid] = pd.NA
    g = ser.groupby(pd.Series(gid))
    ddof = None
    if isinstance(spec, tuple):
        spec, ddof = spec
    if spec == "sum":
        res = g.sum(min_count=1)
    elif spec == "count":
        res = g.count()
    elif spec == "mean":
        res = g.mean()
    elif spec == "min":
        res = g.min()
    elif spec == "max":
        res = g.max()
    elif spec == "first":
        res = g.first()
    elif spec == "last":
        res = g.last()
    elif spec == "std":
        res = g.std(ddof=1 if ddof is None else ddof)
    elif spec == "var":
        res = g.var(ddof=1 if ddof is None else ddof)
    else:
        raise NotImplementedError(spec)
    res = res.reindex(range(n_groups))
    na = res.isna().to_numpy()
    if isinstance(rt, T.StringType):
        vals = res.to_numpy(dtype=object)
        return CpuCol(rt, vals, ~na)
    # extract without a float64 round trip — int64 sums/minima beyond 2^53
    # must stay exact
    if np.dtype(rt.np_dtype).kind in "iub":
        filled = res.fillna(0).to_numpy(dtype=np.int64)
    else:
        filled = res.fillna(0).to_numpy(dtype=np.float64)
    if spec == "mean" and isinstance(a.fn.children[0].data_type(),
                                     T.DecimalType):
        # decimal state is unscaled int64; the mean must be a VALUE
        filled = filled / 10.0 ** a.fn.children[0].data_type().scale
    return CpuCol(rt, filled.astype(rt.np_dtype), ~na)


def _agg_float_np(spec, rt, inp: CpuCol, gid: np.ndarray, n_groups: int) -> CpuCol:
    ddof = None
    if isinstance(spec, tuple):
        spec, ddof = spec
    v = inp.values.astype(np.float64)
    valid = inp.valid
    order = np.argsort(gid, kind="stable")
    gs, vs, oks = gid[order], v[order], valid[order]
    starts = np.searchsorted(gs, np.arange(n_groups), side="left")
    nvalid = np.bincount(gs, weights=oks.astype(np.float64),
                         minlength=n_groups).astype(np.int64)
    has = nvalid > 0
    with np.errstate(all="ignore"):
        if spec == "count":
            return CpuCol(T.INT64, nvalid, np.ones(n_groups, np.bool_))
        if spec in ("sum", "mean", "std", "var"):
            sums = np.add.reduceat(np.where(oks, vs, 0.0), starts) \
                if n_groups else np.zeros(0)
            if spec == "sum":
                return CpuCol(rt, sums, has)
            if spec == "mean":
                return CpuCol(rt, sums / np.maximum(nvalid, 1), has)
            sq = np.add.reduceat(np.where(oks, vs * vs, 0.0), starts) \
                if n_groups else np.zeros(0)
            n_ = nvalid.astype(np.float64)
            m2 = np.maximum(sq - sums * sums / np.maximum(n_, 1.0), 0.0)
            # propagate NaN through m2 when sums are NaN
            m2 = np.where(np.isnan(sums) | np.isnan(sq), np.nan, m2)
            dd = 1 if ddof is None else ddof
            denom = n_ - dd
            var = np.where(denom <= 0, np.nan, m2 / np.where(denom <= 0, 1.0, denom))
            out = np.sqrt(var) if spec == "std" else var
            return CpuCol(rt, out, has)
        if spec in ("min", "max"):
            # total-order bits reduction
            vv = np.where(vs == 0.0, 0.0, vs)
            bits = vv.view(np.uint64)
            neg = (bits >> np.uint64(63)) != 0
            key = np.where(neg, ~bits, bits | np.uint64(1 << 63))
            ident = np.uint64(0xFFFFFFFFFFFFFFFF) if spec == "min" else np.uint64(0)
            key = np.where(oks, key, ident)
            red = np.minimum if spec == "min" else np.maximum
            out_key = red.reduceat(key, starts) if n_groups else key[:0]
            pos = (out_key & np.uint64(1 << 63)) != 0
            raw = np.where(pos, out_key ^ np.uint64(1 << 63), ~out_key)
            out = raw.view(np.float64)
            return CpuCol(rt, out.astype(rt.np_dtype), has)
        if spec in ("first", "last"):
            pos = np.where(oks, np.arange(len(vs)), len(vs) if spec == "first" else -1)
            red = np.minimum if spec == "first" else np.maximum
            sel = red.reduceat(pos, starts) if n_groups else pos[:0]
            ok = (sel >= 0) & (sel < len(vs))
            out = vs[np.clip(sel, 0, max(len(vs) - 1, 0))]
            return CpuCol(rt, out.astype(rt.np_dtype), has & ok)
    raise NotImplementedError(spec)


def _global_agg(plan: P.Aggregate, agg_inputs, n: int) -> List[CpuCol]:
    from spark_rapids_tpu.expr.aggregates import MergedState, SegmentedAgg
    out = []
    gid = np.zeros(max(n, 0), np.int64)
    for a, inp in zip(plan.aggs, agg_inputs):
        if n == 0 and not isinstance(a.fn, MergedState):
            rt = a.fn.result_type()
            if isinstance(a.fn, SegmentedAgg):
                if isinstance(rt, T.ArrayType):  # collect_* of empty = []
                    vals = np.empty(1, object)
                    vals[0] = []
                    out.append(CpuCol(rt, vals, np.ones(1, np.bool_)))
                else:
                    npdt = object if isinstance(rt, T.StringType) \
                        else rt.np_dtype
                    out.append(CpuCol(rt, np.zeros(1, npdt),
                                      np.zeros(1, np.bool_)))
                continue
            if a.fn.pandas_spec() in ("size", "count"):
                out.append(CpuCol(T.INT64, np.zeros(1, np.int64),
                                  np.ones(1, np.bool_)))
            else:
                npdt = object if isinstance(rt, T.StringType) else rt.np_dtype
                out.append(CpuCol(rt, np.zeros(1, npdt), np.zeros(1, np.bool_)))
        else:
            out.append(_agg_by_gid(a, inp, gid, 1))
    return out


def _exec_join(plan: P.Join, left: List[CpuCol], right: List[CpuCol],
               ansi: bool) -> List[CpuCol]:
    ln = len(left[0].values) if left else 0
    rn = len(right[0].values) if right else 0
    lk = [e.eval_cpu(left, ansi) for e in plan.left_keys]
    rk = [e.eval_cpu(right, ansi) for e in plan.right_keys]

    if plan.how == "cross" or not plan.left_keys:
        # cross join, or non-equi join (empty keys): all pairs, then the
        # condition filter below prunes; outer completion follows
        lidx = np.repeat(np.arange(ln), rn)
        ridx = np.tile(np.arange(rn), ln)
    else:
        # build pair lists via sorted codes per key, exact semantics: null
        # keys never match; NaN matches NaN (normalized code equality)
        lcodes = []
        rcodes = []
        lnull = np.zeros(ln, np.bool_)
        rnull = np.zeros(rn, np.bool_)
        for lc, rc in zip(lk, rk):
            shared = _shared_string_dict(lc, rc) \
                if isinstance(lc.dtype, T.StringType) else None
            lcd, lnu = _norm_key_np(lc, shared)
            rcd, rnu = _norm_key_np(rc, shared)
            lcodes.append(lcd)
            rcodes.append(rcd)
            lnull |= lnu
            rnull |= rnu
        ldf = pd.DataFrame({f"k{i}": c.view(np.int64) for i, c in enumerate(lcodes)})
        rdf = pd.DataFrame({f"k{i}": c.view(np.int64) for i, c in enumerate(rcodes)})
        ldf["_l"] = np.arange(ln)
        rdf["_r"] = np.arange(rn)
        ldf = ldf[~lnull]
        rdf = rdf[~rnull]
        merged = ldf.merge(rdf, on=[f"k{i}" for i in range(len(lcodes))], how="inner")
        lidx = merged["_l"].to_numpy()
        ridx = merged["_r"].to_numpy()

    # extra condition filters matched pairs
    if plan.condition is not None:
        pair_cols = _gather_cols(left, lidx) + _gather_cols(right, ridx)
        pred = plan.condition.eval_cpu(pair_cols, ansi)
        keep = pred.values.astype(np.bool_) & pred.valid
        lidx, ridx = lidx[keep], ridx[keep]

    how = plan.how
    if how in ("inner", "cross"):
        pass
    elif how == "left":
        matched = np.zeros(ln, np.bool_)
        matched[lidx] = True
        extra = np.nonzero(~matched)[0]
        lidx = np.concatenate([lidx, extra])
        ridx = np.concatenate([ridx, np.full(len(extra), -1)])
    elif how == "right":
        matched = np.zeros(rn, np.bool_)
        matched[ridx] = True
        extra = np.nonzero(~matched)[0]
        lidx = np.concatenate([lidx, np.full(len(extra), -1)])
        ridx = np.concatenate([ridx, extra])
    elif how == "full":
        lmatched = np.zeros(ln, np.bool_)
        lmatched[lidx] = True
        rmatched = np.zeros(rn, np.bool_)
        rmatched[ridx] = True
        lex = np.nonzero(~lmatched)[0]
        rex = np.nonzero(~rmatched)[0]
        lidx = np.concatenate([lidx, lex, np.full(len(rex), -1)])
        ridx = np.concatenate([ridx, np.full(len(lex), -1), rex])
    elif how == "left_semi":
        hit = np.zeros(ln, np.bool_)
        hit[lidx] = True
        return _gather_cols(left, np.nonzero(hit)[0])
    elif how == "left_anti":
        hit = np.zeros(ln, np.bool_)
        hit[lidx] = True
        return _gather_cols(left, np.nonzero(~hit)[0])
    return _gather_cols(left, lidx) + _gather_cols(right, ridx)
