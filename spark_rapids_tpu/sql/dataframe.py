"""DataFrame API over the plan algebra (the user surface a Spark user would
recognize; reference: the plugin is transparent to Spark's DataFrame API, so
this module plays PySpark's role in the standalone framework)."""
from __future__ import annotations

from typing import List, Optional, Union as U

from spark_rapids_tpu import config as C
from spark_rapids_tpu.expr import core as E
from spark_rapids_tpu.expr.aggregates import AggFunction, NamedAgg
from spark_rapids_tpu.plan import nodes as P
from spark_rapids_tpu import types as T


def _e(x):
    return x if isinstance(x, E.Expression) else (E.col(x) if isinstance(x, str) else E.lit(x))


class DataFrame:
    def __init__(self, plan: P.PlanNode, session):
        self.plan = plan
        self.session = session

    # -- transformations ---------------------------------------------------
    def _extract_windows(self, exprs):
        """Hoist WindowExprs out of a projection into WindowNode(s) below
        it (reference: Catalyst's ExtractWindowExpressions)."""
        from spark_rapids_tpu.expr import window as WE
        found = []

        def extract(e):
            def repl(node):
                if isinstance(node, WE.WindowExpr):
                    name = f"__w{len(found)}"
                    found.append((node, name))
                    return E.col(name)
                return node
            return e.transform(repl)

        new_exprs = []
        for e in exprs:
            if isinstance(e, WE.WindowExpr):
                name = f"__w{len(found)}"
                found.append((e, name))
                new_exprs.append(E.Alias(E.col(name),
                                         type(e.fn).__name__.lower()))
            else:
                new_exprs.append(extract(e))
        if not found:
            return exprs, self.plan
        # group by spec so each WindowNode sorts once
        plan = self.plan
        groups = {}
        for w, name in found:
            groups.setdefault(w.spec.fingerprint(), []).append((w, name))
        for items in groups.values():
            plan = P.WindowNode([w for w, _ in items],
                                [n for _, n in items], plan)
        return new_exprs, plan

    def select(self, *exprs) -> "DataFrame":
        es = [_e(x) for x in exprs]
        from spark_rapids_tpu.expr import window as WE
        from spark_rapids_tpu.expr import complex as CX

        stacks = [(i, e) for i, e in enumerate(es)
                  if isinstance(e, CX.Stack)
                  or (isinstance(e, E.Alias)
                      and isinstance(e.children[0], CX.Stack))]
        if stacks:
            if len(stacks) > 1:
                raise E.SparkException(
                    "only one generator allowed per select clause")
            i, se = stacks[0]
            alias = se.name if isinstance(se, E.Alias) else None
            st = se.children[0] if isinstance(se, E.Alias) else se
            st = CX.Stack(st.n, *[P.bind_expr(c, self.plan.schema)
                                  for c in st.children])
            names = [n for n, _ in st.output_fields()]
            if alias is not None:
                if len(names) != 1:
                    raise E.SparkException(
                        "stack() alias needs a single output column, "
                        f"got {len(names)}")
                names = [alias]

            def _plain(e):
                if isinstance(e, (WE.WindowExpr, CX.Explode, CX.Stack)):
                    return False
                return all(_plain(c) for c in e.children)

            if all(_plain(e) for e in es[:i] + es[i + 1:]):
                # one-pass lowering onto the Expand node (multiple
                # projections per input row, like the ROLLUP rewrite)
                out_names = ([P.expr_name(e, j)
                              for j, e in enumerate(es[:i])]
                             + names
                             + [P.expr_name(e, i + 1 + j)
                                for j, e in enumerate(es[i + 1:])])
                projections = [es[:i] + row + es[i + 1:]
                               for row in st.row_exprs()]
                return DataFrame(P.Expand(projections, out_names,
                                          self.plan), self.session)
            # other items carry window/explode markers that need their
            # own lowering: fall back to one select per stack row
            out = None
            for row in st.row_exprs():
                es_r = (es[:i]
                        + [E.Alias(c, n) for c, n in zip(row, names)]
                        + es[i + 1:])
                part = self.select(*es_r)
                out = part if out is None else out.union(part)
            return out

        gens = [(i, e) for i, e in enumerate(es)
                if isinstance(e, CX.Explode)
                or (isinstance(e, E.Alias) and isinstance(e.children[0],
                                                          CX.Explode))]
        if gens:
            if len(gens) > 1:
                raise E.SparkException(
                    "only one generator allowed per select clause")
            i, ge = gens[0]
            alias = ge.name if isinstance(ge, E.Alias) else None
            gen = ge.children[0] if isinstance(ge, E.Alias) else ge
            gen = type(gen)(P.bind_expr(gen.children[0], self.plan.schema))
            fields = gen.output_fields(alias)
            names = [n for n, _ in fields]
            new_exprs = es[:i] + [E.col(n) for n in names] + es[i + 1:]
            # requiredChildOutput: only child columns the projection uses
            # ride through the row-duplicating generate
            refs = set()
            for e in new_exprs:
                refs |= {r.lower() for r in e.references()}
            required = [j for j, f in enumerate(self.plan.schema.fields)
                        if f.name.lower() in refs]
            gplan = P.Generate(gen, names, self.plan, required=required)
            return DataFrame(P.Project(new_exprs, gplan), self.session)

        def has_window(e):
            if isinstance(e, WE.WindowExpr):
                return True
            return any(has_window(c) for c in e.children)

        if any(has_window(e) for e in es):
            new_es, plan = self._extract_windows(es)
            return DataFrame(P.Project(new_es, plan), self.session)
        return self._select_plain(*exprs)

    def _select_plain(self, *exprs) -> "DataFrame":
        bound = [_e(x) for x in exprs]
        return DataFrame(P.Project(bound, self.plan), self.session)

    def with_column(self, name: str, expr) -> "DataFrame":
        # case-insensitive replace, like Spark's default resolver
        existing = [E.col(n) for n in self.plan.schema.names
                    if n.lower() != name.lower()]
        return self.select(*existing, _e(expr).alias(name))

    def filter(self, condition) -> "DataFrame":
        return DataFrame(P.Filter(_e(condition), self.plan), self.session)

    where = filter

    def group_by(self, *keys) -> "GroupedData":
        return GroupedData([_e(k) for k in keys], self)

    groupBy = group_by

    def rollup(self, *keys) -> "GroupedData":
        """Hierarchical grouping sets (full, drop-last, ..., grand
        total) lowered onto Expand (reference GpuExpandExec — the
        Catalyst ROLLUP rewrite done in-engine)."""
        ks = [_e(k) for k in keys]
        sets = [tuple(range(i)) for i in range(len(ks), -1, -1)]
        return GroupedData(ks, self, grouping_sets=sets)

    def cube(self, *keys) -> "GroupedData":
        """All 2^n grouping-set combinations, lowered onto Expand."""
        ks = [_e(k) for k in keys]
        n = len(ks)
        sets = [tuple(j for j in range(n) if not (m >> (n - 1 - j)) & 1)
                for m in range(1 << n)]
        return GroupedData(ks, self, grouping_sets=sets)

    def grouping_sets(self, sets, *keys) -> "GroupedData":
        """Explicit GROUPING SETS: `sets` is a list of key-index tuples
        (or key-name/expr lists matched against `keys`)."""
        ks = [_e(k) for k in keys]
        fps = [k.fingerprint() for k in ks]
        norm = []
        for s in sets:
            idx = []
            for item in s:
                if isinstance(item, int):
                    idx.append(item)
                else:
                    fp = _e(item).fingerprint()
                    if fp not in fps:
                        raise E.SparkException(
                            f"GROUPING SETS item {item!r} is not a "
                            "group-by key")
                    idx.append(fps.index(fp))
            norm.append(tuple(idx))
        return GroupedData(ks, self, grouping_sets=norm)

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData([], self).agg(*aggs)

    def order_by(self, *orders) -> "DataFrame":
        os = []
        for o in orders:
            if isinstance(o, P.SortOrder):
                os.append(o)
            else:
                os.append(P.SortOrder(_e(o)))
        return DataFrame(P.Sort(os, self.plan), self.session)

    orderBy = sort = order_by

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(P.Limit(n, self.plan), self.session)

    def repartition(self, n: int, *cols) -> "DataFrame":
        """Explicit exchange: hash-partition by `cols` into n partitions,
        round-robin when no columns are given (Spark's repartition)."""
        keys = [_e(c) for c in cols]
        return DataFrame(P.Repartition(n, keys, self.plan), self.session)

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(P.Union([self.plan, other.plan]), self.session)

    unionAll = union

    def to_device_batches(self):
        """Zero-copy ML handoff: execute the plan and return the raw
        device-resident ColumnarBatches (flat list, partition order;
        reference ColumnarRdd / InternalColumnarRddConverter — the
        XGBoost-style hand-off of device tables without a host round
        trip). The arrays inside are jax Arrays usable directly in
        downstream jax/flax code."""
        from spark_rapids_tpu.ops.kernels import compact_batch
        exec_root, _ = self.session.prepare_execution(self.plan)
        return self.session.run_partitions(exec_root, compact_batch)

    @property
    def write(self):
        """df.write.mode(...).partition_by(...).parquet(path)."""
        from spark_rapids_tpu.io.writer import DataFrameWriter
        return DataFrameWriter(self)

    def cache(self) -> "DataFrame":
        """Pin this DataFrame's result in device HBM; repeated queries over
        it skip the scan + upload entirely. Under a mesh of n devices an
        in-memory source given no partition count is cached as n row
        ranges, one a device (CachedScanExec places partition p on device
        p mod n); an explicit count is respected."""
        plan = self.plan
        if isinstance(plan, P.InMemorySource) \
                and not plan.explicit_partitions:
            from spark_rapids_tpu.parallel.mesh import placement_devices
            n = len(placement_devices(self.session.conf))
            if n > 1:
                plan = P.InMemorySource(plan.table, n)
                plan.explicit_partitions = False
        return DataFrame(P.CachedRelation(plan), self.session)

    persist = cache

    def unpersist(self) -> "DataFrame":
        """Drop a cache()'d result from HBM, every partition's shard on
        its own chip; the next action materializes it again."""
        if isinstance(self.plan, P.CachedRelation):
            from spark_rapids_tpu.exec.tpu_nodes import CachedScanExec
            with CachedScanExec._lock:
                parts, self.plan.materialized = self.plan.materialized, None
                self.plan.placed_on = ()
            for part in parts or ():
                for sb in part:
                    sb.close()
        return self

    def distinct(self) -> "DataFrame":
        keys = [E.col(n) for n in self.plan.schema.names]
        return DataFrame(P.Aggregate(keys, [], self.plan), self.session)

    def drop_duplicates(self, subset: Optional[List[str]] = None
                        ) -> "DataFrame":
        """dropDuplicates: with a subset, keep one arbitrary row per key
        (Spark keeps the partition-order first; both are 'some row')."""
        if not subset:
            return self.distinct()
        # row_number over the key keeps one WHOLE input row per key
        # (a first() per remaining column would stitch cells from
        # different rows when the earliest value is null)
        from spark_rapids_tpu.sql import functions as F
        from spark_rapids_tpu.expr import window as WE
        spec = WE.Window.partition_by(*[E.col(s) for s in subset]) \
            .order_by(E.lit(1))
        marked = self.select(*[E.col(n) for n in self.plan.schema.names],
                             F.row_number().over(spec).alias("__rn"))
        return (marked.filter(E.col("__rn") == E.lit(1))
                .select(*[E.col(n) for n in self.plan.schema.names]))

    dropDuplicates = drop_duplicates

    def dropna(self, how: str = "any", thresh: Optional[int] = None,
               subset: Optional[List[str]] = None) -> "DataFrame":
        """DataFrameNaFunctions.drop: keep rows with enough non-null
        cells (thresh wins over how; how='any' means all cells non-null,
        'all' means at least one — Spark's AtLeastNNonNulls filter)."""
        if how not in ("any", "all"):
            raise ValueError(f"how must be 'any' or 'all', got {how!r}")
        names = subset or list(self.plan.schema.names)
        if thresh is None:
            thresh = len(names) if how == "any" else 1
        # Catalyst's predicate (NaN counts as missing, like Spark)
        return self.filter(E.AtLeastNNonNulls(
            int(thresh), *[E.col(n) for n in names]))

    def fillna(self, value, subset: Optional[List[str]] = None
               ) -> "DataFrame":
        """DataFrameNaFunctions.fill: replace nulls in TYPE-COMPATIBLE
        columns (numeric value fills numeric columns, string fills
        string — Spark's rule), others pass through untouched."""
        names = {s.lower() for s in subset} if subset else None
        out = []
        for f in self.plan.schema.fields:
            compat = (f.dtype.is_numeric
                      if isinstance(value, (int, float))
                      and not isinstance(value, bool)
                      else isinstance(f.dtype, type(E.lit(value).dtype)))
            if (names is None or f.name.lower() in names) and compat:
                # cast the fill to the COLUMN type (Spark truncates
                # 0.5 -> 0 for an int column and keeps the dtype)
                out.append(E.Alias(
                    E.Coalesce(E.col(f.name),
                               E.Cast(E.lit(value), f.dtype)), f.name))
            else:
                out.append(E.col(f.name))
        return self.select(*out)

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition=None) -> "DataFrame":
        """`condition` (with key pairs in `on`): what else a pair of rows
        has to satisfy, bound against the two inputs' columns end to
        end; it holds before an outer join fills its nulls."""
        how = {"leftsemi": "left_semi", "semi": "left_semi",
               "leftanti": "left_anti", "anti": "left_anti",
               "outer": "full", "fullouter": "full", "left_outer": "left",
               "right_outer": "right"}.get(how, how)
        if how == "cross" or on is None:
            return DataFrame(P.Join(self.plan, other.plan, [], [], "cross"),
                             self.session)
        if isinstance(on, E.Expression):
            # non-equi join on an arbitrary condition (binds against the
            # concatenated left+right schema) -> nested-loop join
            return DataFrame(P.Join(self.plan, other.plan, [], [], how,
                                    condition=on), self.session)
        if isinstance(on, str):
            on = [on]
        dedupe_names = None
        if isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            lk = [E.col(k) for k in on]
            rk = [E.col(k) for k in on]
            dedupe_names = list(on)
        elif isinstance(on, (list, tuple)):
            lk, rk = zip(*on)
            lk, rk = list(lk), list(rk)
        else:
            raise TypeError("join on= must be column name(s) or (left, right) pairs")
        joined = DataFrame(P.Join(self.plan, other.plan, lk, rk, how,
                                  condition=condition), self.session)
        if dedupe_names and how not in ("left_semi", "left_anti"):
            # PySpark semantics: a single key column in the output. For right
            # joins the surviving values come from the right side.
            nleft = len(self.plan.schema)
            out = []
            lowered = {n.lower() for n in dedupe_names}
            for i, f in enumerate(joined.plan.schema.fields):
                if i >= nleft and f.name.lower() in lowered:
                    continue  # drop right-side key duplicate
                ref = E.BoundRef(i, f.dtype, f.name)
                if i < nleft and f.name.lower() in lowered and how in ("right", "full"):
                    # take the non-null side for the key
                    ridx = nleft + _index_of(joined.plan.schema.names[nleft:], f.name)
                    rref = E.BoundRef(ridx, joined.plan.schema.fields[ridx].dtype, f.name)
                    out.append(E.Coalesce(ref, rref).alias(f.name))
                else:
                    out.append(ref.alias(f.name))
            joined = DataFrame(P.Project(out, joined.plan), joined.session)
        return joined

    # -- actions -----------------------------------------------------------
    @property
    def schema(self):
        return self.plan.schema

    @property
    def columns(self) -> List[str]:
        return self.plan.schema.names

    # -- pyspark convenience surface ---------------------------------------

    def drop(self, *cols) -> "DataFrame":
        """Drop columns by name (unknown names are ignored, like
        pyspark)."""
        gone = {(c if isinstance(c, str) else c.name).lower()
                for c in cols}
        keep = [E.col(n) for n in self.plan.schema.names
                if n.lower() not in gone]
        if not keep:
            raise E.SparkException("drop() would remove every column")
        return self.select(*keep)

    def with_column_renamed(self, existing: str, new: str) -> "DataFrame":
        out = [E.Alias(E.col(n), new) if n.lower() == existing.lower()
               else E.col(n) for n in self.plan.schema.names]
        return self.select(*out)

    withColumnRenamed = with_column_renamed

    withColumn = with_column

    @property
    def dtypes(self):
        return [(f.name, repr(f.dtype)) for f in self.plan.schema.fields]

    def print_schema(self) -> None:
        print("root")
        for f in self.plan.schema.fields:
            null = "true" if f.nullable else "false"
            print(f" |-- {f.name}: {f.dtype!r} (nullable = {null})")

    printSchema = print_schema

    def show(self, n: int = 20, truncate=True) -> None:
        """Render the first n rows as pyspark's ASCII grid. truncate
        may be a bool (20-char default cut) or an int width."""
        tbl = self.limit(n + 1).collect()
        more = tbl.num_rows > n
        tbl = tbl.slice(0, n)
        names = list(self.plan.schema.names)
        if isinstance(truncate, bool):
            width = 20 if truncate else 0
        else:
            width = int(truncate)

        def cell(v):
            if v is None:
                s = "NULL"
            elif v is True:
                s = "true"
            elif v is False:
                s = "false"
            else:
                s = str(v)
            if width and len(s) > width:
                s = s[: max(width - 3, 0)] + "..."
            return s
        # positional column access: duplicate output names must each
        # show their own values
        cols = [tbl.column(i).to_pylist()
                for i in range(tbl.num_columns)]
        grid = [[cell(cols[i][r]) for i in range(len(names))]
                for r in range(tbl.num_rows)]
        widths = [max(len(c), *(len(g[i]) for g in grid)) if grid
                  else len(c) for i, c in enumerate(names)]
        sep = "+" + "+".join("-" * w for w in widths) + "+"
        print(sep)
        print("|" + "|".join(c.rjust(w)
                             for c, w in zip(names, widths)) + "|")
        print(sep)
        for g in grid:
            print("|" + "|".join(c.rjust(w)
                                 for c, w in zip(g, widths)) + "|")
        print(sep)
        if more:
            print(f"only showing top {n} rows")


    def head(self, n: Optional[int] = None):
        """pyspark surface: head() is one row (or None); head(n) — even
        head(1) — is a list."""
        rows = self.limit(n if n is not None else 1).collect().to_pylist()
        if n is None:
            return rows[0] if rows else None
        return rows

    def take(self, n: int):
        return self.limit(n).collect().to_pylist()

    def first(self):
        return self.head(1)

    def to_pandas(self):
        return self.collect().to_pandas()

    toPandas = to_pandas

    def sample(self, fraction: float, seed: int = 0,
               with_replacement: bool = False) -> "DataFrame":
        """Bernoulli row sample: rand(seed) < fraction per row, Spark's
        without-replacement sampler. With-replacement (Poisson counts)
        is not implemented."""
        if with_replacement:
            raise E.SparkException(
                "sample(withReplacement=True) is not implemented")
        from spark_rapids_tpu.expr.misc import Rand
        return self.filter(Rand(seed) < E.lit(float(fraction)))

    def random_split(self, weights: List[float], seed: int = 0
                     ) -> List["DataFrame"]:
        """Split by disjoint rand(seed) ranges proportional to weights
        (each split re-evaluates the same deterministic rand stream, so
        the splits partition the input exactly)."""
        from spark_rapids_tpu.expr.misc import Rand
        total = float(sum(weights))
        out, lo = [], 0.0
        for i, w in enumerate(weights):
            hi = 1.0 if i == len(weights) - 1 else lo + w / total
            r = Rand(seed)
            out.append(self.filter((r >= E.lit(lo)) & (r < E.lit(hi))))
            lo = hi
        return out

    randomSplit = random_split

    def _null_safe_on(self):
        """EXCEPT/INTERSECT compare NULL as equal to NULL: each column
        becomes an (is-null flag, null-coalesced value) key pair, which
        matches exactly when the null-safe equality would."""
        on = []
        for f in self.plan.schema.fields:
            c = E.col(f.name)
            flag = E.If(E.IsNull(c), E.lit(1), E.lit(0))
            default = E.lit("") if isinstance(f.dtype, T.StringType) \
                else E.Cast(E.lit(0), f.dtype)
            coal = E.Coalesce(c, default)
            on.append((flag, flag))
            on.append((coal, coal))
        return on

    def _align_positional(self, other: "DataFrame") -> "DataFrame":
        """EXCEPT/INTERSECT pair columns by POSITION (Spark): rename
        other's columns to self's names first."""
        mine = self.plan.schema.names
        theirs = other.plan.schema.names
        if len(mine) != len(theirs):
            raise E.SparkException(
                f"set operation needs the same number of columns: "
                f"{len(mine)} vs {len(theirs)}")
        return other.select(*[E.Alias(E.col(t), m)
                              for t, m in zip(theirs, mine)])

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """EXCEPT DISTINCT: distinct rows of self absent from other."""
        return self.distinct().join(self._align_positional(other),
                                    on=self._null_safe_on(),
                                    how="left_anti")

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """INTERSECT DISTINCT."""
        return self.distinct().join(self._align_positional(other),
                                    on=self._null_safe_on(),
                                    how="left_semi")

    def describe(self, *cols) -> "DataFrame":
        """count/mean/stddev/min/max summary rows over numeric columns
        (string rendering like Spark's describe)."""
        from spark_rapids_tpu.sql import functions as F
        import pyarrow as pa
        fields = {f.name: f for f in self.plan.schema.fields}
        names = list(cols) or [f.name for f in self.plan.schema.fields
                               if f.dtype.is_numeric
                               or isinstance(f.dtype, T.StringType)]
        for n in names:
            if n not in fields:
                raise E.SparkException(f"describe: no column {n!r}")
            if n == "summary":
                raise E.SparkException(
                    "describe over a column named 'summary' is not "
                    "supported (it collides with the stat-label column)")
        stats = ["count", "mean", "stddev", "min", "max"]
        if not names:
            return self.session.create_dataframe(
                pa.table({"summary": stats}))
        aggs = []
        for n in names:
            numeric = fields[n].dtype.is_numeric
            aggs += [NamedAgg(F.count(E.col(n)), f"__cnt_{n}"),
                     NamedAgg(F.min(E.col(n)), f"__min_{n}"),
                     NamedAgg(F.max(E.col(n)), f"__max_{n}")]
            if numeric:  # Spark: strings get count/min/max only
                aggs += [NamedAgg(F.avg(E.col(n)), f"__avg_{n}"),
                         NamedAgg(F.stddev(E.col(n)), f"__std_{n}")]
        row = self.agg(*aggs).collect().to_pylist()[0]

        def fmt(v):
            return None if v is None else str(v)
        data = {"summary": stats}
        for n in names:
            data[n] = [fmt(row.get(f"__{k}_{n}"))
                       for k in ("cnt", "avg", "std", "min", "max")]
        return self.session.create_dataframe(pa.table(data))

    def corr(self, c1: str, c2: str) -> float:
        """Pearson correlation (df.stat.corr)."""
        import math
        m = self._moments(c1, c2)
        # E[x^2]-mean^2 can round a hair negative for constant columns
        den = math.sqrt(max(m["vx"], 0.0) * max(m["vy"], 0.0))
        return float("nan") if den == 0 else m["cov"] / den

    def cov(self, c1: str, c2: str) -> float:
        """Sample covariance (df.stat.cov, n-1 denominator)."""
        m = self._moments(c1, c2)
        n = m["n"]
        return 0.0 if n < 2 else m["cov_sum"] / (n - 1)

    def _moments(self, c1: str, c2: str):
        from spark_rapids_tpu.sql import functions as F
        # pairwise-complete rows only (Spark's covar_samp/corr): gate
        # BOTH columns on both being non-null
        both = E.IsNotNull(E.col(c1)) & E.IsNotNull(E.col(c2))
        fx = self.plan.schema.fields[
            [f.name for f in self.plan.schema.fields].index(c1)]
        x = E.If(both, E.col(c1), E.Literal(None, fx.dtype))
        fy = self.plan.schema.fields[
            [f.name for f in self.plan.schema.fields].index(c2)]
        y = E.If(both, E.col(c2), E.Literal(None, fy.dtype))
        row = self.agg(
            NamedAgg(F.count(x), "n"), NamedAgg(F.sum(x), "sx"),
            NamedAgg(F.sum(y), "sy"), NamedAgg(F.sum(x * y), "sxy"),
            NamedAgg(F.sum(x * x), "sxx"),
            NamedAgg(F.sum(y * y), "syy")).collect().to_pylist()[0]
        n = row["n"] or 0
        if n == 0:
            return {"n": 0, "cov": 0.0, "cov_sum": 0.0, "vx": 0.0,
                    "vy": 0.0}
        sx, sy = float(row["sx"]), float(row["sy"])
        cov_sum = float(row["sxy"]) - sx * sy / n
        return {"n": n, "cov_sum": cov_sum, "cov": cov_sum / n,
                "vx": float(row["sxx"]) / n - (sx / n) ** 2,
                "vy": float(row["syy"]) / n - (sy / n) ** 2}

    def crosstab(self, c1: str, c2: str) -> "DataFrame":
        """Pairwise frequency table (df.stat.crosstab): one row per c1
        value, one column per c2 value, 0 for absent combos (Spark's
        crosstab fills 0, unlike pivot+count)."""
        from spark_rapids_tpu.sql import functions as F
        # reserved key name so a c2 VALUE equal to the c1 column name
        # cannot collide with the key column in the pivot output
        key = "__crosstab_key"
        piv = (self.select(E.Alias(E.col(c1), key), E.col(c2))
               .group_by(E.col(key)).pivot(E.col(c2)).agg(F.count()))
        out = []
        for n in piv.plan.schema.names:
            if n == key:
                out.append(E.Alias(E.col(n), f"{c1}_{c2}"))
            else:
                out.append(E.Alias(
                    E.Coalesce(E.col(n), E.lit(0)), n))
        return piv.select(*out)

    def approx_quantile(self, col_name: str, probabilities: List[float],
                        relative_error: float = 1e-4):
        """df.stat.approxQuantile over one column: one engine pass
        collects the non-null values, then every probability reads the
        same sorted array (Spark's rank interpolation; exact, which
        approxQuantile permits for any relative_error)."""
        import numpy as np
        tbl = (self.select(E.col(col_name)).dropna().collect()
               .column(0).to_numpy(zero_copy_only=False))
        if tbl.size == 0:
            return [float("nan")] * len(probabilities)
        return [float(np.quantile(tbl, p)) for p in probabilities]

    approxQuantile = approx_quantile

    def collect(self, timeout_seconds=None):
        """Execute with the TPU engine (per-op CPU fallback as tagged).
        `timeout_seconds` overrides spark.rapids.query.timeoutSeconds
        for THIS action: past the deadline the query's cancel token
        fires and the action raises QueryCancelledError(reason=
        'deadline') at its next cooperative checkpoint."""
        return self.session.collect(self.plan,
                                    timeout_seconds=timeout_seconds)

    def collect_cpu(self):
        """Execute entirely on the CPU reference backend."""
        from spark_rapids_tpu.exec.cpu_backend import execute_cpu
        return execute_cpu(self.plan, ansi=self.session.conf.get(C.ANSI_ENABLED))

    def to_pydict(self):
        return self.collect().to_pydict()

    def count(self) -> int:
        # aggregate ENGINE-side (Spark semantics): collecting the full
        # result to count it would ship every row across the host link
        from spark_rapids_tpu.expr.aggregates import CountAll, NamedAgg
        plan = P.Aggregate([], [NamedAgg(CountAll(), "count")], self.plan)
        out = DataFrame(plan, self.session).collect()
        return int(out.column(0)[0].as_py())

    def explain(self, mode: str = "placement") -> str:
        """'placement' (default): the tagging report — every operator with
        its TPU/CPU placement and fallback reasons. 'stages': the physical
        exec tree after whole-stage vertical fusion, with fusion groups
        annotated `*(N)` the way Spark prints whole-stage-codegen ids.
        'analyze': EXECUTE the query, then print the physical tree
        annotated with the actual rows/batches/dispatches/time each exec
        recorded (Spark's EXPLAIN ANALYZE / the SQL tab's live metric
        annotations) — a slow query is diagnosable from its own run,
        without re-running it under the tracer."""
        if mode == "analyze":
            self.collect()
            s = self.session.explain_analyze()
        elif mode == "stages":
            # build the exec tree WITHOUT convert_plan's action-time side
            # effects (LORE dumper install would overwrite recordings;
            # test-mode fallback assertions would raise instead of print)
            from spark_rapids_tpu.exec.stage_fusion import fuse_stages
            from spark_rapids_tpu.plan.cost import apply_cost_optimizer
            from spark_rapids_tpu.plan.overrides import wrap_and_tag
            from spark_rapids_tpu.plan.prune import prune_plan
            conf = self.session.conf
            meta = wrap_and_tag(prune_plan(self.plan), conf)
            apply_cost_optimizer(meta, conf)
            s = fuse_stages(meta.convert(), conf).tree_string()
        else:
            from spark_rapids_tpu.plan.overrides import explain_plan
            s = explain_plan(self.plan, self.session.conf, all_ops=True)
        print(s)
        return s

    def __repr__(self):
        return f"DataFrame[{self.plan.schema!r}]"


class GroupedData:
    def __init__(self, keys: List[E.Expression], df: DataFrame,
                 grouping_sets=None):
        self.keys = keys
        self.df = df
        #: list of tuples of key indices INCLUDED per grouping set
        self.grouping_sets = grouping_sets

    def agg(self, *aggs) -> DataFrame:
        named: List[NamedAgg] = []
        for i, a in enumerate(aggs):
            if isinstance(a, NamedAgg):
                named.append(a)
            elif isinstance(a, AggFunction):
                named.append(NamedAgg(a, _default_agg_name(a, i)))
            else:
                raise TypeError(f"not an aggregate: {a!r}")
        if self.grouping_sets is not None:
            return self._agg_grouping_sets(named)
        return DataFrame(P.Aggregate(self.keys, named, self.df.plan),
                         self.df.session)

    def _agg_grouping_sets(self, named: List[NamedAgg]) -> DataFrame:
        """ROLLUP/CUBE/GROUPING SETS lowering (the Catalyst Expand
        rewrite, reference GpuExpandExec consumes its output): replicate
        each row once per grouping set with excluded keys nulled and a
        __grouping_id bitmask key, aggregate over keys + id, then
        resolve grouping()/grouping_id() markers to bit reads of the
        id and drop it from the output."""
        from spark_rapids_tpu.expr.aggregates import (Grouping,
                                                      GroupingMarker,
                                                      GroupingID)
        df, keys, sets = self.df, self.keys, self.grouping_sets
        nk = len(keys)
        src = df.columns
        gk = [f"__gkey{j}" for j in range(nk)]
        pre = df.select(*[E.col(n) for n in src],
                        *[E.Alias(k, gk[j]) for j, k in enumerate(keys)])
        ktypes = {f.name: f.dtype for f in pre.schema.fields}
        projections, names = [], src + gk + ["__grouping_id"]
        for s in sets:
            gid = 0
            row: List[E.Expression] = [E.col(n) for n in src]
            for j in range(nk):
                if j in s:
                    row.append(E.col(gk[j]))
                else:
                    # typed null (NOT a cast-from-null: Literal evals
                    # natively on device for every type incl. strings)
                    row.append(E.Literal(None, ktypes[gk[j]]))
                    gid |= 1 << (nk - 1 - j)
            row.append(E.Cast(E.lit(gid), T.INT64))
            projections.append(row)
        expanded = DataFrame(P.Expand(projections, names, pre.plan),
                             df.session)

        key_fps = [k.fingerprint() for k in keys]

        def marker_expr(fn: GroupingMarker) -> E.Expression:
            from spark_rapids_tpu.expr.math import BitwiseAnd, ShiftRight
            if isinstance(fn, GroupingID):
                return E.col("__grouping_id")
            child = fn.children[0]
            fp = child.fingerprint()
            if fp in key_fps:
                j = key_fps.index(fp)
            elif isinstance(child, E.Col) and child.name in gk:
                j = gk.index(child.name)
            else:
                raise E.SparkException(
                    f"grouping() argument {child!r} is not a "
                    "group-by key")
            return E.Cast(BitwiseAnd(
                ShiftRight(E.col("__grouping_id"),
                           E.Cast(E.lit(nk - 1 - j), T.INT32)),
                E.Cast(E.lit(1), T.INT64)), T.INT8)

        real, post = [], []
        for na in named:
            if isinstance(na.fn, GroupingMarker):
                post.append(E.Alias(marker_expr(na.fn), na.name))
            else:
                real.append(na)
                post.append(E.col(na.name))
        grouped = DataFrame(
            P.Aggregate([E.col(n) for n in gk] + [E.col("__grouping_id")],
                        real, expanded.plan), df.session)
        out_keys = [E.Alias(E.col(gk[j]), P.expr_name(keys[j], j))
                    for j in range(nk)]
        return grouped.select(*out_keys, *post)

    def count(self) -> DataFrame:
        from spark_rapids_tpu.expr.aggregates import CountAll
        return self.agg(NamedAgg(CountAll(), "count"))

    def pivot(self, pivot_col, values=None) -> "PivotedData":
        """Spark GroupedData.pivot. The engine lowers a pivot to
        conditional aggregation — one `agg(if(pivot = v, child, null))`
        per value — rather than a row-shuffling pivot kernel (the
        reference lowers to GpuPivotFirst, GpuOverrides.scala expr
        [PivotFirst], which is the same gather-by-value idea on GPU).
        With no explicit values the distinct set is computed eagerly,
        like Spark, capped at 10000."""
        pc = _e(pivot_col)
        if values is None:
            rows = (self.df.select(pc.alias("__pv")).distinct()
                    .limit(10_001).collect().column("__pv").to_pylist())
            if len(rows) > 10_000:
                raise E.SparkException(
                    "pivot: more than 10000 distinct values; pass an "
                    "explicit value list")
            # Spark keeps a NULL pivot value as its own column, sorted
            # first (ascending nulls-first collection order)
            values = sorted(rows, key=lambda v: (v is not None, v))
        return PivotedData(self.keys, self.df, pc, list(values))


class PivotedData:
    def __init__(self, keys, df: DataFrame, pivot_col, values):
        self.keys = keys
        self.df = df
        self.pivot_col = pivot_col
        self.values = values

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu.expr.aggregates import CountAll, Count
        named = []
        for i, a in enumerate(aggs):
            if isinstance(a, NamedAgg):
                named.append((a.fn, a.name if len(aggs) > 1 else None))
            elif isinstance(a, AggFunction):
                named.append((a, _default_agg_name(a, i)
                              if len(aggs) > 1 else None))
            else:
                raise TypeError(f"not an aggregate: {a!r}")
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.expr.aggregates import Max
        schema = self.df.plan.schema
        out = []
        post = {}   # count column -> presence-marker column
        for vi, v in enumerate(self.values):
            pc = P.bind_expr(self.pivot_col, schema)
            # a NULL pivot value needs null-safe matching
            cond = E.IsNull(pc) if v is None else pc == E.lit(v)
            marker = None
            if any(isinstance(a, (CountAll, Count)) for a, _ in named):
                # Spark's pivot leaves counts NULL (not 0) for combos
                # with no matching rows; a presence marker separates
                # "no rows" from "rows whose counted value is null"
                marker = f"__present{vi}"
                out.append(NamedAgg(
                    Max(E.If(cond, E.lit(1), E.Literal(None, T.INT32))),
                    marker))
            for a, suffix in named:
                if isinstance(a, CountAll):
                    cell = Count(E.If(cond, E.lit(1),
                                      E.Literal(None, T.INT32)))
                else:
                    # EVERY child is gated (min_by's ordering column
                    # must not see other pivot cells' rows)
                    import copy
                    gated = []
                    for ch in a.children:
                        ch = P.bind_expr(ch, schema)
                        gated.append(E.If(cond, ch,
                                          E.Literal(None, ch.data_type())))
                    cell = copy.copy(a)  # keeps extra params (e.g. p)
                    cell.children = gated
                vs = "null" if v is None else str(v)
                name = vs if suffix is None else f"{vs}_{suffix}"
                if isinstance(a, (CountAll, Count)):
                    post[name] = marker
                out.append(NamedAgg(cell, name))
        agged = DataFrame(P.Aggregate(self.keys, out, self.df.plan),
                          self.df.session)
        finals = []
        for n in agged.plan.schema.names:
            if n.startswith("__present"):
                continue
            if n in post:
                finals.append(E.Alias(
                    E.If(E.IsNull(E.col(post[n])),
                         E.Literal(None, T.INT64), E.col(n)), n))
            else:
                finals.append(E.col(n))
        return agged.select(*finals) if post else agged


def _index_of(names: List[str], name: str) -> int:
    for i, n in enumerate(names):
        if n.lower() == name.lower():
            return i
    raise KeyError(name)


def _default_agg_name(a: AggFunction, i: int) -> str:
    base = type(a).__name__.lower()
    if a.children and isinstance(a.children[0], E.Col):
        return f"{base}({a.children[0].name})"
    return f"{base}_{i}"
