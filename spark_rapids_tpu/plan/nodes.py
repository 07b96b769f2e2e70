"""Backend-agnostic plan nodes.

Reference parity: the reference rewrites Spark Catalyst *physical* plans
(GpuOverrides.scala wraps SparkPlan nodes). Standing alone (no live Spark in
this environment), this module plays Catalyst's role: a small physical plan
algebra with schema inference and name binding. The overrides engine
(plan/overrides.py) then walks these exactly like GpuOverrides walks
SparkPlan -- tagging, converting supported subtrees to TPU execs, and
falling back per-operator to the CPU backend.

A thin adapter can later map real Spark physical plans onto these nodes
(the SparkShims seam from SURVEY.md §7.3.7).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import (
    Alias, BoundRef, Col, Expression, Literal, SparkException,
)
from spark_rapids_tpu.expr.aggregates import AggFunction, NamedAgg


class PlanNode:
    children: List["PlanNode"] = []

    @property
    def schema(self) -> T.Schema:
        raise NotImplementedError

    def name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        lines = [f"{pad}{self.describe()}"]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def describe(self) -> str:
        return self.name()

    def estimated_rows(self) -> Optional[int]:
        """Best-effort row-count estimate for physical planning (the
        reference consults Spark statistics; CostBasedOptimizer.scala).
        None = unknown."""
        if isinstance(self, InMemorySource):
            return self.table.num_rows
        if isinstance(self, ParquetScan):
            # the files' row count, whatever the columns read: a narrowed
            # copy asks the scan it was cut from, so a view's footers
            # are walked once, not once a query
            src = self.narrowed_from or self
            if getattr(src, "_est_rows", None) is None:
                try:
                    import pyarrow.parquet as pq
                    src._est_rows = sum(pq.ParquetFile(p).metadata.num_rows
                                        for p in src.paths)
                except Exception:  # noqa: BLE001 - stats are advisory
                    src._est_rows = -1
            return None if src._est_rows < 0 else src._est_rows
        if isinstance(self, Range):
            return max(0, -(-(self.end - self.start) // self.step))
        if isinstance(self, Filter):
            c = self.children[0].estimated_rows()
            return None if c is None else max(c // 2, 1)
        if isinstance(self, Limit):
            c = self.children[0].estimated_rows()
            return self.n if c is None else min(self.n, c)
        if isinstance(self, Union):
            parts = [c.estimated_rows() for c in self.children]
            return None if any(p is None for p in parts) else sum(parts)
        if isinstance(self, Aggregate):
            # grouped-aggregate cardinality is data-dependent: report
            # UNKNOWN so the planner defers the join strategy to runtime
            # (AdaptiveJoinExec measures the real count — the AQE role)
            return 1 if not self.group_exprs else None
        if self.children:
            return self.children[0].estimated_rows()
        return None


def _case_sensitive_now() -> bool:
    from spark_rapids_tpu.config import conf as _active
    from spark_rapids_tpu import config as _C
    return bool(_active().get(_C.CASE_SENSITIVE))


def make_binder(schema: T.Schema, case_sensitive=None):
    def binder(node):
        if isinstance(node, Col):
            cs = _case_sensitive_now() if case_sensitive is None \
                else case_sensitive
            name = node.name
            for i, f in enumerate(schema.fields):
                if f.name == name or (not cs and
                                      f.name.lower() == name.lower()):
                    return BoundRef(i, f.dtype, f.name)
            raise KeyError(f"column {name!r} not found in {schema.names}")
        return node
    return binder


def bind_expr(e: Expression, schema: T.Schema, case_sensitive=None) -> Expression:
    """Resolve Col names to BoundRefs against a child schema
    (case sensitivity from spark.sql.caseSensitive unless forced)."""
    return e.transform(make_binder(schema, case_sensitive))


def expr_name(e: Expression, idx: int) -> str:
    if isinstance(e, Alias):
        return e.name
    if isinstance(e, (Col,)):
        return e.name
    if isinstance(e, BoundRef):
        return e.name or f"c{idx}"
    return f"col{idx}"


class InMemorySource(PlanNode):
    """A pyarrow Table split into partitions (local-mode data source)."""

    def __init__(self, table, num_partitions: Optional[int] = None):
        self.table = table
        #: None = the caller gave no count: one partition, and a cache
        #: under a mesh may split the row range over its devices
        self.explicit_partitions = num_partitions is not None
        self.num_partitions = max(1, num_partitions or 1)
        self.children = []

    @property
    def schema(self) -> T.Schema:
        return T.Schema(tuple(
            T.StructField(f.name, T.from_arrow(f.type)) for f in self.table.schema))

    def describe(self):
        return f"InMemorySource[{self.table.num_rows} rows, {self.num_partitions} parts]"


class TextScan(PlanNode):
    """CSV / JSON-lines / ORC file scan: host-side parse (pyarrow readers
    play the role of the reference's host line-splitting before the cudf
    parse kernels; GpuCSVScan.scala / GpuJsonScan.scala / GpuOrcScan.scala),
    then the standard Arrow-plane device upload."""

    FORMATS = ("csv", "json", "orc", "avro")

    def __init__(self, fmt: str, paths: Sequence[str],
                 schema: Optional[T.Schema] = None,
                 columns: Optional[List[str]] = None,
                 options: Optional[dict] = None):
        assert fmt in self.FORMATS, fmt
        self.fmt = fmt
        self.paths = list(paths)
        self._schema = schema
        self.columns = columns
        self.options = options or {}
        self.children = []

    def read_host(self, path: str):
        """One file -> pyarrow Table (host parse)."""
        import pyarrow as pa
        if self.fmt == "csv":
            import pyarrow.csv as pcsv
            opts = self.options
            read_opts = pcsv.ReadOptions(
                column_names=opts.get("column_names"),
                autogenerate_column_names=not opts.get("header", True)
                and not opts.get("column_names"))
            parse_opts = pcsv.ParseOptions(delimiter=opts.get("sep", ","))
            # pin column types to the PLAN schema (inferred from the first
            # block): full-file re-inference could disagree with what the
            # kernels were planned for
            column_types = None
            if self._schema is not None:
                column_types = {f.name: T.to_arrow(f.dtype)
                                for f in self._schema.fields}
            conv = pcsv.ConvertOptions(include_columns=self.columns or None,
                                       column_types=column_types)
            t = pcsv.read_csv(path, read_options=read_opts,
                              parse_options=parse_opts, convert_options=conv)
        elif self.fmt == "json":
            import pyarrow.json as pjson
            t = pjson.read_json(path)
            if self.columns:
                t = t.select(self.columns)
        elif self.fmt == "avro":
            from spark_rapids_tpu.io.avro import read_avro
            t = read_avro(path)
            if self.columns:
                t = t.select(self.columns)
        else:
            import pyarrow.orc as porc
            t = porc.ORCFile(path).read(columns=self.columns)
        return t

    @property
    def schema(self) -> T.Schema:
        if self._schema is None:
            if not self.paths:
                raise FileNotFoundError("TextScan: no input files")
            if self.fmt == "orc":
                import pyarrow.orc as porc
                pa_schema = porc.ORCFile(self.paths[0]).schema
            elif self.fmt == "csv":
                import pyarrow.csv as pcsv
                opts = self.options
                read_opts = pcsv.ReadOptions(
                    column_names=opts.get("column_names"),
                    autogenerate_column_names=not opts.get("header", True)
                    and not opts.get("column_names"),
                    block_size=1 << 20)  # schema from the first block only
                with pcsv.open_csv(
                        self.paths[0], read_options=read_opts,
                        parse_options=pcsv.ParseOptions(
                            delimiter=opts.get("sep", ","))) as r:
                    pa_schema = r.schema
            else:  # json: no streaming schema API; parse the first file
                pa_schema = self.read_host(self.paths[0]).schema
            fields = [T.StructField(f.name, T.from_arrow(f.type))
                      for f in pa_schema]
            if self.columns:
                # data columns come back in REQUESTED order — the schema
                # must match positionally or names bind to the wrong data
                by_name = {f.name: f for f in fields}
                fields = [by_name[c] for c in self.columns]
            self._schema = T.Schema(tuple(fields))
        return self._schema

    def estimated_rows(self):
        return None

    def describe(self):
        return f"TextScan[{self.fmt}, {len(self.paths)} files]"


class CachedRelation(PlanNode):
    """`df.cache()` analog (reference ParquetCachedBatchSerializer,
    SURVEY.md §2.6 — there df.cache() stores compressed parquet blobs; the
    TPU-first answer keeps the materialized result resident in HBM, where
    repeated queries pay zero upload). The exec node materializes the child
    once and every later collect reuses the device batches."""

    def __init__(self, child: PlanNode):
        self.children = [child]
        self.materialized = None  # List[List[ColumnarBatch]] set by the exec
        #: the devices the partitions were placed over (partition p on
        #: entry p mod n); () for a cache on the default device
        self.placed_on = ()

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    def describe(self):
        state = "hot" if self.materialized is not None else "cold"
        return f"CachedRelation[{state}]"


class ParquetScan(PlanNode):
    """Parquet file scan (reference GpuParquetScan). Filter pushdown happens
    in the overrides pass; `pushed_filters` prune row groups host-side."""

    def __init__(self, paths: Sequence[str], schema: Optional[T.Schema] = None,
                 columns: Optional[List[str]] = None,
                 pushed_filters: Optional[List[Expression]] = None,
                 partition_values: Optional[List[dict]] = None):
        self.paths = list(paths)
        self._schema = schema
        self.columns = columns
        self.pushed_filters = pushed_filters or []
        #: hive-layout partition values per file (k -> str|None), appended
        #: as constant columns (reference: partition-value columns,
        #: BatchWithPartitionData)
        self.partition_values = partition_values
        #: columns to request from the FILES: partition columns never live
        #: in the data files
        self.file_columns = columns
        if columns and partition_values:
            pkeys = {k for v in partition_values for k in v}
            self.file_columns = [c for c in columns if c not in pkeys]
        #: the scan this one was cut from by the planner's column pruning
        #: (`narrowed`); None for a scan as its caller built it
        self.narrowed_from: Optional["ParquetScan"] = None
        self.children = []

    def narrowed(self, keep: Sequence[int]) -> "ParquetScan":
        """A NEW scan of the same files reading only the columns at
        schema positions `keep` (ascending, so the order stays this
        scan's). This node is left as it is: a view's scan is shared by
        every later query, which may name other columns. The schema is
        carried over (no footer is read again) and the constructor
        derives `file_columns` and the partition keys anew."""
        fields = self.schema.fields
        q = ParquetScan(self.paths,
                        schema=T.Schema(tuple(fields[i] for i in keep)),
                        columns=[fields[i].name for i in keep],
                        partition_values=self.partition_values)
        q.narrowed_from = self.narrowed_from or self
        return q

    def partition_fields(self) -> List[T.StructField]:
        if not self.partition_values:
            return []
        keys: List[str] = []
        for vals in self.partition_values:
            for k in vals:
                if k not in keys:
                    keys.append(k)
        if self.columns:
            keys = [k for k in keys if k in self.columns]
        fields = []
        for k in keys:
            non_null = [v.get(k) for v in self.partition_values
                        if v.get(k) is not None]
            dt = T.STRING
            if non_null:
                try:
                    for v in non_null:
                        int(v)
                    dt = T.INT64
                except ValueError:
                    pass
            fields.append(T.StructField(k, dt))
        return fields

    def with_partition_cols(self, table, file_idx: int):
        """Append this file's constant partition-value columns to a host
        table (reference BatchWithPartitionData: lazily materialized
        partition columns)."""
        if not self.partition_values:
            return table
        import pyarrow as pa
        vals = self.partition_values[file_idx]
        for f in self.partition_fields():
            v = vals.get(f.name)
            if v is not None and f.dtype == T.INT64:
                v = int(v)
            arr = pa.array([v] * table.num_rows, type=T.to_arrow(f.dtype))
            table = table.append_column(f.name, arr)
        return table

    @property
    def schema(self) -> T.Schema:
        if self._schema is None:
            import pyarrow.parquet as pq
            s = pq.read_schema(self.paths[0])
            fields = [T.StructField(f.name, T.from_arrow(f.type)) for f in s]
            if self.columns:
                by_name = {f.name: f for f in fields}
                fields = [by_name[c] for c in self.columns if c in by_name]
            fields += self.partition_fields()
            self._schema = T.Schema(tuple(fields))
        return self._schema

    def describe(self):
        if self.narrowed_from is None:
            return f"ParquetScan[{len(self.paths)} files]"
        return (f"ParquetScan[{len(self.paths)} files, "
                f"{len(self.schema.fields)} of "
                f"{len(self.narrowed_from.schema.fields)} columns]")


class Range(PlanNode):
    """spark.range(start, end, step) analog (reference GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int = 1, num_partitions: int = 1):
        self.start = start
        self.end = end
        self.step = step
        self.num_partitions = max(1, num_partitions)
        self.children = []

    @property
    def schema(self):
        return T.Schema.of(("id", T.INT64))

    def describe(self):
        return f"Range[{self.start},{self.end},{self.step}]"


class Project(PlanNode):
    def __init__(self, exprs: List[Expression], child: PlanNode):
        self.children = [child]
        self.raw_exprs = exprs
        self.exprs = [bind_expr(e, child.schema) for e in exprs]
        self.names = [expr_name(e, i) for i, e in enumerate(exprs)]

    @property
    def schema(self):
        return T.Schema(tuple(
            T.StructField(n, e.data_type())
            for n, e in zip(self.names, self.exprs)))

    def describe(self):
        return f"Project[{', '.join(self.names)}]"


class Filter(PlanNode):
    def __init__(self, condition: Expression, child: PlanNode):
        self.children = [child]
        self.condition = bind_expr(condition, child.schema)

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"Filter[{self.condition!r}]"


class Aggregate(PlanNode):
    """Group-by aggregate. group_exprs evaluate per-row keys; aggs are
    NamedAgg(fn, out_name). Empty group_exprs = global aggregation."""

    def __init__(self, group_exprs: List[Expression], aggs: List[NamedAgg],
                 child: PlanNode):
        if any(a.fn.distinct for a in aggs):
            raise SparkException(
                "distinct aggregates are planned by "
                "plan.distinct.plan_aggregate, not as one Aggregate")
        self.children = [child]
        self.raw_group_exprs = group_exprs
        self.group_exprs = [bind_expr(e, child.schema) for e in group_exprs]
        self.group_names = [expr_name(e, i) for i, e in enumerate(group_exprs)]
        self.aggs = [a.transform(lambda n: _bind_leaf(n, child.schema)) for a in aggs]

    @property
    def schema(self):
        fields = [T.StructField(n, e.data_type())
                  for n, e in zip(self.group_names, self.group_exprs)]
        fields += [T.StructField(a.name, a.fn.result_type()) for a in self.aggs]
        return T.Schema(tuple(fields))

    #: what the planner did to this node, shown after its description
    note = ""
    #: the lower level of a distinct aggregate (plan/distinct.py): keys
    #: plus the distinct value
    distinct_level = False

    def describe(self):
        return (f"Aggregate[keys=[{', '.join(self.group_names)}], "
                f"aggs=[{', '.join(a.name for a in self.aggs)}]]{self.note}")


def _bind_leaf(node, schema):
    if isinstance(node, Col):
        for i, f in enumerate(schema.fields):
            if f.name == node.name:
                return BoundRef(i, f.dtype, f.name)
        if not _case_sensitive_now():
            for i, f in enumerate(schema.fields):
                if f.name.lower() == node.name.lower():
                    return BoundRef(i, f.dtype, f.name)
        raise KeyError(f"column {node.name!r} not found in {schema.names}")
    return node


@dataclasses.dataclass
class SortOrder:
    expr: Expression
    ascending: bool = True
    nulls_first: Optional[bool] = None  # Spark default: nulls first iff asc

    def resolved_nulls_first(self) -> bool:
        return self.ascending if self.nulls_first is None else self.nulls_first


class Sort(PlanNode):
    def __init__(self, orders: List[SortOrder], child: PlanNode,
                 global_sort: bool = True):
        self.children = [child]
        self.orders = [SortOrder(bind_expr(o.expr, child.schema), o.ascending,
                                 o.nulls_first) for o in orders]
        self.global_sort = global_sort

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        parts = [f"{o.expr!r} {'ASC' if o.ascending else 'DESC'}" for o in self.orders]
        return f"Sort[{', '.join(parts)}]"


class WindowNode(PlanNode):
    """Window evaluation: appends one output column per WindowExpr to the
    child's schema (reference GpuWindowExec; SURVEY.md §2.4 Window). All
    exprs in one node share the same partition/order spec — the planner
    groups by spec and chains nodes."""

    def __init__(self, window_exprs, names: List[str], child: PlanNode):
        from spark_rapids_tpu.expr.window import WindowExpr, WindowSpec
        self.children = [child]
        self.names = names
        bound = []
        for w in window_exprs:
            spec = w.spec
            if getattr(w.fn, "needs_order", False) and not spec.order_specs:
                # Spark raises AnalysisException for these; silently
                # computing over arbitrary order would be garbage
                raise ValueError(
                    f"{type(w.fn).__name__} requires the window to be "
                    f"ordered (add ORDER BY to the window spec)")
            bspec = WindowSpec(
                [bind_expr(e, child.schema) for e in spec.partition_exprs],
                [SortOrder(bind_expr(o.expr, child.schema), o.ascending,
                           o.nulls_first) for o in spec.order_specs],
                spec.frame)
            bfn = w.fn.transform(make_binder(child.schema))
            bound.append(WindowExpr(bfn, bspec))
        self.window_exprs = bound

    @property
    def schema(self) -> T.Schema:
        fields = list(self.children[0].schema.fields)
        for w, n in zip(self.window_exprs, self.names):
            fields.append(T.StructField(n, w.fn.result_type()))
        return T.Schema(tuple(fields))

    def describe(self):
        return f"Window[{', '.join(self.names)}]"


class Limit(PlanNode):
    def __init__(self, n: int, child: PlanNode):
        self.children = [child]
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"Limit[{self.n}]"


class Repartition(PlanNode):
    """Explicit exchange (DataFrame.repartition): hash-partition by `keys`
    into n_out partitions, or round-robin when no keys are given (Spark's
    repartition(n) / repartition(n, cols) — previously the engine only
    planned exchanges implicitly under aggregates/sorts/windows)."""

    def __init__(self, n_out: int, keys: List[Expression], child: PlanNode):
        self.children = [child]
        self.n_out = max(1, int(n_out))
        self.keys = [bind_expr(e, child.schema) for e in keys]

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        how = f"hash{self.keys!r}" if self.keys else "roundrobin"
        return f"Repartition[{how}, n={self.n_out}]"


class Join(PlanNode):
    """Equi-join with optional extra condition (reference GpuShuffledHashJoin
    / GpuBroadcastHashJoin; the planner picks the physical strategy)."""

    KINDS = ("inner", "left", "right", "full", "left_semi", "left_anti", "cross")

    def __init__(self, left: PlanNode, right: PlanNode,
                 left_keys: List[Expression], right_keys: List[Expression],
                 how: str = "inner", condition: Optional[Expression] = None):
        assert how in self.KINDS, how
        self.children = [left, right]
        self.left_keys = [bind_expr(e, left.schema) for e in left_keys]
        self.right_keys = [bind_expr(e, right.schema) for e in right_keys]
        self.how = how
        self.condition_raw = condition
        # condition binds against the concatenated output schema
        self.condition = (bind_expr(condition, self._concat_schema())
                          if condition is not None else None)

    def _concat_schema(self) -> T.Schema:
        lf = list(self.children[0].schema.fields)
        rf = list(self.children[1].schema.fields)
        return T.Schema(tuple(lf + rf))

    @property
    def schema(self):
        l, r = self.children
        lf = list(l.schema.fields)
        rf = list(r.schema.fields)
        if self.how in ("left_semi", "left_anti"):
            return l.schema
        if self.how in ("right",):
            lf = [T.StructField(f.name, f.dtype, True) for f in lf]
        if self.how in ("left", "full"):
            rf = [T.StructField(f.name, f.dtype, True) for f in rf]
        if self.how == "full":
            lf = [T.StructField(f.name, f.dtype, True) for f in lf]
        return T.Schema(tuple(lf + rf))

    def describe(self):
        keys = ", ".join(f"{l!r}={r!r}" for l, r in zip(self.left_keys, self.right_keys))
        return f"Join[{self.how}, {keys}]"


class Union(PlanNode):
    def __init__(self, children: List[PlanNode]):
        assert children
        first = children[0].schema
        for c in children[1:]:
            assert len(c.schema) == len(first), "UNION arity mismatch"
        self.children = list(children)

    @property
    def schema(self):
        schemas = [c.schema for c in self.children]
        fields = []
        for i, f in enumerate(schemas[0].fields):
            dt = f.dtype
            for s in schemas[1:]:
                dt = T.common_type(dt, s.fields[i].dtype)
            fields.append(T.StructField(f.name, dt))
        return T.Schema(tuple(fields))

    def describe(self):
        return f"Union[{len(self.children)}]"


class ShuffleFileScan(PlanNode):
    """Scan of a cross-process shuffle directory written by
    shuffle.exchange_files.write_exchange (one partition per reduce
    partition; self-describing kudo frames + manifest)."""

    def __init__(self, root: str):
        from spark_rapids_tpu.shuffle.exchange_files import read_manifest
        from spark_rapids_tpu.shuffle.serde import dtype_from_json
        self.children = []
        self.root = root
        m = read_manifest(root)
        self.n_reduce = int(m["n_reduce"])
        self._schema = T.Schema(tuple(
            T.StructField(n, dtype_from_json(t))
            for n, t in zip(m["names"], m["types"])))

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"ShuffleFileScan[{self.root}, n={self.n_reduce}]"


class Generate(PlanNode):
    """One output row per element of a generator over each input row
    (reference GpuGenerateExec.scala: explode/posexplode, incl. _outer).
    Output schema = child columns followed by the generated columns."""

    def __init__(self, generator, gen_names: List[str], child: PlanNode,
                 required: Optional[List[int]] = None):
        from spark_rapids_tpu.expr.complex import Explode
        self.children = [child]
        assert isinstance(generator, Explode), type(generator)
        gen = type(generator)(bind_expr(generator.children[0], child.schema))
        self.generator = gen
        dt = gen.children[0].data_type()
        if not isinstance(dt, (T.ArrayType, T.MapType)):
            from spark_rapids_tpu.expr.core import SparkException
            raise SparkException(
                f"explode() requires an array or map input, got {dt!r}")
        fields = gen.output_fields()
        if gen_names:
            assert len(gen_names) == len(fields), \
                f"generator yields {len(fields)} columns, got names {gen_names}"
            fields = [(n, t) for n, (_, t) in zip(gen_names, fields)]
        self.gen_fields = fields
        #: child column indices carried through (Spark requiredChildOutput);
        #: defaults to all. The exec row-duplicates these — pruning unneeded
        #: ones both saves the gathers and keeps nested siblings (whose
        #: duplicating gather is not supported on device) out of the plan.
        n_child = len(child.schema.fields)
        self.required = list(range(n_child)) if required is None \
            else list(required)

    @property
    def schema(self):
        base = [self.children[0].schema.fields[i] for i in self.required]
        gen = [T.StructField(n, t) for n, t in self.gen_fields]
        return T.Schema(tuple(base + gen))

    def describe(self):
        kind = type(self.generator).__name__
        return f"Generate[{kind}({self.generator.children[0]!r})]"


class Expand(PlanNode):
    """Multiple projections per input row (reference GpuExpandExec; used by
    ROLLUP/CUBE/count-distinct rewrites)."""

    def __init__(self, projections: List[List[Expression]], names: List[str],
                 child: PlanNode):
        self.children = [child]
        self.projections = [[bind_expr(e, child.schema) for e in p]
                            for p in projections]
        self.names = names

    @property
    def schema(self):
        p0 = self.projections[0]
        return T.Schema(tuple(
            T.StructField(n, e.data_type()) for n, e in zip(self.names, p0)))

    def describe(self):
        return f"Expand[{len(self.projections)} projections]"
