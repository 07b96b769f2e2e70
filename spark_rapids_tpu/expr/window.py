"""Window expressions.

Reference parity: sql-plugin window/ (GpuWindowExec family,
GpuWindowExpression.scala:198 — rank/dense_rank/row_number/lead/lag and
windowed aggregations over ROWS/RANGE frames; SURVEY.md §2.4 "Window").

Model: a WindowExpr pairs a window function with a WindowSpec
(partition-by, order-by, frame). The planner splits projections containing
WindowExprs into a Window plan node; the exec sorts once per partition
spec and evaluates every window function as segmented scans in ONE fused
kernel (the TPU answer to the reference's batched running/bounded window
iterators).

Frames: (kind, lower, upper) with kind in {"rows", "range"}; None bounds
mean UNBOUNDED, 0 means CURRENT ROW, ints are offsets. Spark defaults:
ordered specs get ("range", None, 0) — running with ties; unordered specs
get ("rows", None, None) — whole partition.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.aggregates import AggFunction
from spark_rapids_tpu.expr.core import Expression


@dataclasses.dataclass(frozen=True)
class Frame:
    kind: str = "range"          # "rows" | "range"
    lower: Optional[int] = None  # None = UNBOUNDED PRECEDING
    upper: Optional[int] = 0     # None = UNBOUNDED FOLLOWING; 0 = CURRENT

    def fingerprint(self) -> str:
        return f"{self.kind}[{self.lower},{self.upper}]"


class WindowSpec:
    """Builder: Window.partition_by(...).order_by(...).rows_between(a, b)."""

    def __init__(self, partition_by=None, order_by=None, frame: Optional[Frame] = None):
        self.partition_exprs: List[Expression] = list(partition_by or [])
        self.order_specs = list(order_by or [])  # list[plan.SortOrder]
        self.frame = frame

    def partition_by(self, *exprs) -> "WindowSpec":
        from spark_rapids_tpu.expr.core import col
        es = [col(e) if isinstance(e, str) else e for e in exprs]
        return WindowSpec(es, self.order_specs, self.frame)

    def order_by(self, *orders) -> "WindowSpec":
        from spark_rapids_tpu.plan.nodes import SortOrder
        from spark_rapids_tpu.expr.core import col
        os = []
        for o in orders:
            if isinstance(o, SortOrder):
                os.append(o)
            else:
                os.append(SortOrder(col(o) if isinstance(o, str) else o))
        return WindowSpec(self.partition_exprs, os, self.frame)

    def rows_between(self, lower, upper) -> "WindowSpec":
        return WindowSpec(self.partition_exprs, self.order_specs,
                          Frame("rows", lower, upper))

    def resolved_frame(self) -> Frame:
        if self.frame is not None:
            return self.frame
        if self.order_specs:
            return Frame("range", None, 0)
        return Frame("rows", None, None)

    def fingerprint(self) -> str:
        ps = ",".join(e.fingerprint() for e in self.partition_exprs)
        os = ",".join(f"{o.expr.fingerprint()}:{o.ascending}:"
                      f"{o.resolved_nulls_first()}" for o in self.order_specs)
        return f"spec({ps}|{os}|{self.resolved_frame().fingerprint()})"


class Window:
    """Entry points mirroring pyspark.sql.Window."""

    #: frame bound sentinels
    unboundedPreceding = None
    unboundedFollowing = None
    currentRow = 0

    @staticmethod
    def partition_by(*exprs) -> WindowSpec:
        return WindowSpec().partition_by(*exprs)

    partitionBy = partition_by

    @staticmethod
    def order_by(*orders) -> WindowSpec:
        return WindowSpec().order_by(*orders)

    orderBy = order_by


class WindowFunction:
    """Base for pure window functions (rank family, lead/lag)."""

    children: List[Expression] = []
    needs_order = True

    def result_type(self) -> T.DataType:
        raise NotImplementedError

    def fingerprint(self) -> str:
        kids = ",".join(c.fingerprint() for c in self.children)
        return f"{type(self).__name__}({kids};{self._params()})"

    def _params(self) -> str:
        return ""

    def transform(self, fn):
        return self

    def over(self, spec: WindowSpec) -> "WindowExpr":
        return WindowExpr(self, spec)


class RowNumber(WindowFunction):
    def result_type(self):
        return T.INT32


class Rank(WindowFunction):
    def result_type(self):
        return T.INT32


class DenseRank(WindowFunction):
    def result_type(self):
        return T.INT32


class NTile(WindowFunction):
    def __init__(self, n: int):
        self.n = n

    def _params(self):
        return str(self.n)

    def result_type(self):
        return T.INT32


class LeadLag(WindowFunction):
    is_lead = True

    def __init__(self, child: Expression, offset: int = 1, default=None):
        self.children = [child]
        self.offset = offset
        self.default = default

    def _params(self):
        return f"{self.offset},{self.default!r}"

    def result_type(self):
        return self.children[0].data_type()

    def transform(self, fn):
        out = type(self)(self.children[0].transform(fn), self.offset, self.default)
        return out


class Lead(LeadLag):
    is_lead = True


class Lag(LeadLag):
    is_lead = False


class PercentRank(WindowFunction):
    """(rank - 1) / (partition rows - 1); 0.0 for single-row partitions."""

    def result_type(self):
        return T.FLOAT64


class CumeDist(WindowFunction):
    """rows ordering <= current (peers included) / partition rows."""

    def result_type(self):
        return T.FLOAT64


class NthValue(WindowFunction):
    """nth_value(col, n): the partition's nth value once the frame has
    reached it, null before (Spark default-frame semantics)."""

    def __init__(self, child: Expression, n: int):
        if n < 1:
            from spark_rapids_tpu.expr.core import SparkException
            raise SparkException("nth_value offset must be >= 1")
        self.children = [child]
        self.n = n

    def _params(self):
        return str(self.n)

    def result_type(self):
        return self.children[0].data_type()

    def transform(self, fn):
        return NthValue(self.children[0].transform(fn), self.n)


class FirstValue(WindowFunction):
    def __init__(self, child: Expression):
        self.children = [child]

    def result_type(self):
        return self.children[0].data_type()

    def transform(self, fn):
        return FirstValue(self.children[0].transform(fn))


class LastValue(WindowFunction):
    """last_value over the FRAME — with Spark's default frame (unbounded
    preceding to current row) this is the current peer group's last row,
    the famously surprising behavior the reference reproduces too."""

    def __init__(self, child: Expression):
        self.children = [child]

    def result_type(self):
        return self.children[0].data_type()

    def transform(self, fn):
        return LastValue(self.children[0].transform(fn))


class WindowAgg(WindowFunction):
    """An aggregate function evaluated over a window frame."""

    needs_order = False

    def __init__(self, fn: AggFunction):
        self.fn = fn
        self.children = list(fn.children)

    def _params(self):
        return type(self.fn).__name__

    def result_type(self):
        return self.fn.result_type()

    def transform(self, tf):
        return WindowAgg(self.fn.transform(lambda e: e.transform(tf)))


class WindowExpr(Expression):
    """function OVER spec — appears in projection lists; the planner hoists
    it into a Window plan node."""

    def __init__(self, fn: WindowFunction, spec: WindowSpec):
        self.fn = fn
        self.spec = spec
        self.children = []

    def data_type(self) -> T.DataType:
        return self.fn.result_type()

    def fingerprint(self) -> str:
        return f"winexpr({self.fn.fingerprint()} over {self.spec.fingerprint()})"

    def transform(self, tf):
        out = tf(self)
        return out if out is not self else self


def over(fn_or_agg, spec: WindowSpec) -> WindowExpr:
    if isinstance(fn_or_agg, AggFunction):
        if fn_or_agg.distinct:
            from spark_rapids_tpu.expr.core import SparkException
            raise SparkException(
                "distinct window functions are not supported")
        fn_or_agg = WindowAgg(fn_or_agg)
    if not isinstance(fn_or_agg, WindowFunction):
        raise TypeError(f"not a window function: {fn_or_agg!r}")
    return WindowExpr(fn_or_agg, spec)
