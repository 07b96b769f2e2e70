"""Expression tree with dual evaluation paths.

Reference parity: the expression library surveyed in SURVEY.md §2.5
(arithmetic.scala, predicates.scala, conditionalExpressions.scala,
nullExpressions.scala, GpuCast.scala) and the `columnarEval` contract of
GpuExpression.

TPU-first difference from the reference: cuDF evaluates one kernel per
expression node over materialized columns; here `eval_tpu` builds jnp ops
inside a trace, so an entire projection/filter stage fuses into ONE jitted
XLA computation (see exec/compiled.py). The CPU path (`eval_cpu`, numpy on
(values, mask) pairs) is an independent implementation used as the
differential-testing baseline, playing the role CPU Spark plays for the
reference's integration tests.

Null semantics follow Spark SQL: null-propagating arithmetic/comparison,
Kleene AND/OR, null-safe equality, CASE/IF lazy-ish branches (both branches
computed, selected by mask -- fine because expressions are pure), non-ANSI
division-by-zero yields null, ANSI mode raises.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnVector
from spark_rapids_tpu.runtime import compile_cache as _cc


class SparkException(Exception):
    """Raised for ANSI-mode arithmetic/cast errors (host-side, after the
    jitted stage reports error flags)."""


class _CpuEvalContext(threading.local):
    """Partition context for the CPU interpreter (spark_partition_id,
    monotonically_increasing_id). CpuFallbackExec collapses its input to a
    single partition, so the defaults describe that execution; a caller
    evaluating per-partition must set these to match the TPU path's
    (pid << 33) + idx layout."""
    partition_id = 0
    row_base = 0


CPU_EVAL_CTX = _CpuEvalContext()


@dataclasses.dataclass
class CpuCol:
    """CPU evaluation currency: numpy values + bool validity (True=valid).
    Strings are object ndarrays of python str."""
    dtype: T.DataType
    values: np.ndarray
    valid: np.ndarray

    @staticmethod
    def of(dtype, values, valid=None):
        values = np.asarray(values) if not isinstance(values, np.ndarray) else values
        if valid is None:
            valid = np.ones(len(values), np.bool_)
        return CpuCol(dtype, values, valid)


class EvalCtx:
    """Context for one traced stage: input columns + row-count scalar.

    num_rows is a traced int32 scalar so changing row counts inside a
    capacity bucket does NOT recompile. `row_mask` gives in-range rows.
    ANSI errors accumulate as (code, bool-plane) pairs checked on the host
    after stage execution.
    """

    def __init__(self, columns: Sequence[ColumnVector], num_rows, capacity: int,
                 ansi: bool = False, live=None, partition_id=0, row_base=0):
        self.columns = list(columns)
        self.num_rows = num_rows
        self.capacity = capacity
        self.ansi = ansi
        self.live = live  # selection mask; dead rows never raise ANSI errors
        #: traced scalars for partition-aware expressions
        #: (spark_partition_id, monotonically_increasing_id)
        self.partition_id = partition_id
        self.row_base = row_base
        self.errors: List[Tuple[str, jax.Array]] = []

    @property
    def row_mask(self) -> jax.Array:
        if self.live is not None:
            return self.live
        return jnp.arange(self.capacity) < self.num_rows

    def add_error(self, code: str, mask: jax.Array) -> None:
        self.errors.append((code, mask & self.row_mask))


class Expression:
    children: List["Expression"] = []

    def data_type(self) -> T.DataType:
        raise NotImplementedError

    @property
    def nullable(self) -> bool:
        return True

    def eval_tpu(self, ctx: EvalCtx) -> ColumnVector:
        raise NotImplementedError

    def eval_cpu(self, cols: Sequence[CpuCol], ansi: bool = False) -> CpuCol:
        raise NotImplementedError

    def fingerprint(self) -> str:
        params = self._params()
        kids = ",".join(c.fingerprint() for c in self.children)
        return f"{type(self).__name__}({params};{kids})"

    def static_range(self):
        """Optional (lo, hi) int bounds of this expression's values,
        derivable from the expression alone (e.g. ``x % 1000``). Lets the
        radix groupby/sort paths skip the per-batch device min/max probe
        (one host sync per batch). None = unknown."""
        return None

    def _params(self) -> str:
        return ""

    def transform(self, fn) -> "Expression":
        """Bottom-up rewrite (used by the analyzer to bind names)."""
        new = self.with_children([c.transform(fn) for c in self.children])
        return fn(new)

    def with_children(self, children: List["Expression"]) -> "Expression":
        if not self.children and not children:
            return self
        clone = dataclasses.replace(self) if dataclasses.is_dataclass(self) else self
        clone.children = children
        return clone

    def references(self) -> set:
        out = set()
        if isinstance(self, Col):
            out.add(self.name)
        for c in self.children:
            out |= c.references()
        return out

    def __repr__(self):
        return self.fingerprint()

    # Operator sugar so tests/DataFrame code read like Spark Column exprs.
    def __add__(self, o): return Add(self, _wrap(o))
    def __radd__(self, o): return Add(_wrap(o), self)
    def __sub__(self, o): return Subtract(self, _wrap(o))
    def __rsub__(self, o): return Subtract(_wrap(o), self)
    def __mul__(self, o): return Multiply(self, _wrap(o))
    def __rmul__(self, o): return Multiply(_wrap(o), self)
    def __truediv__(self, o): return Divide(self, _wrap(o))
    def __mod__(self, o): return Remainder(self, _wrap(o))
    def __neg__(self): return UnaryMinus(self)
    def __eq__(self, o): return EqualTo(self, _wrap(o))  # type: ignore[override]
    def __ne__(self, o): return Not(EqualTo(self, _wrap(o)))  # type: ignore[override]
    def __lt__(self, o): return LessThan(self, _wrap(o))
    def __le__(self, o): return LessThanOrEqual(self, _wrap(o))
    def __gt__(self, o): return GreaterThan(self, _wrap(o))
    def __ge__(self, o): return GreaterThanOrEqual(self, _wrap(o))
    def __and__(self, o): return And(self, _wrap(o))
    def __or__(self, o): return Or(self, _wrap(o))
    def __invert__(self): return Not(self)
    def __hash__(self):
        return hash(self.fingerprint())

    def is_null(self): return IsNull(self)
    def is_not_null(self): return IsNotNull(self)
    def alias(self, name): return Alias(self, name)
    def cast(self, dtype): return Cast(self, dtype)
    def isin(self, *vals): return In(self, [_wrap(v) for v in vals])

    def substr(self, pos, length):
        """pyspark Column.substr (1-based)."""
        from spark_rapids_tpu.expr.strings import Substring
        return Substring(self, pos, length)

    # Complex-type sugar (Spark Column.getItem/getField).
    def get_item(self, key):
        from spark_rapids_tpu.expr import complex as CX
        if isinstance(key, str):
            return CX.GetMapValue(self, _wrap(key))
        return CX.GetArrayItem(self, _wrap(key))

    getItem = get_item

    def get_field(self, name: str):
        from spark_rapids_tpu.expr import complex as CX
        return CX.GetStructField(self, name)

    getField = get_field

    # Sort-order sugar (Spark Column.asc/desc family).
    def _order(self, ascending, nulls_first=None):
        from spark_rapids_tpu.plan.nodes import SortOrder
        return SortOrder(self, ascending, nulls_first)

    def asc(self): return self._order(True)
    def desc(self): return self._order(False)
    def asc_nulls_first(self): return self._order(True, True)
    def asc_nulls_last(self): return self._order(True, False)
    def desc_nulls_first(self): return self._order(False, True)
    def desc_nulls_last(self): return self._order(False, False)


def _wrap(v) -> Expression:
    return v if isinstance(v, Expression) else Literal.infer(v)


def col(name: str) -> "Col":
    return Col(name)


def lit(v) -> "Literal":
    return Literal.infer(v)


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

class Col(Expression):
    """Unresolved attribute; the analyzer rewrites to BoundRef."""

    def __init__(self, name: str):
        self.name = name
        self.children = []

    def data_type(self):
        raise RuntimeError(f"unresolved column {self.name!r}")

    def _params(self):
        return self.name

    def with_children(self, children):
        return self


class BoundRef(Expression):
    def __init__(self, index: int, dtype: T.DataType, name: str = ""):
        self.index = index
        self.dtype = dtype
        self.name = name
        self.children = []

    def data_type(self):
        return self.dtype

    def _params(self):
        return f"{self.index}:{self.dtype!r}"

    def with_children(self, children):
        return self

    def eval_tpu(self, ctx: EvalCtx) -> ColumnVector:
        return ctx.columns[self.index]

    def eval_cpu(self, cols, ansi=False) -> CpuCol:
        return cols[self.index]


class Literal(Expression):
    def __init__(self, value, dtype: T.DataType):
        self.value = value
        self.dtype = dtype
        self.children = []

    @staticmethod
    def infer(v) -> "Literal":
        import datetime
        import decimal
        if v is None:
            return Literal(None, T.NULL)
        if isinstance(v, bool):
            return Literal(v, T.BOOLEAN)
        if isinstance(v, int):
            return Literal(v, T.INT32 if -(2**31) <= v < 2**31 else T.INT64)
        if isinstance(v, float):
            return Literal(v, T.FLOAT64)
        if isinstance(v, str):
            return Literal(v, T.STRING)
        if isinstance(v, decimal.Decimal):
            sign, digits, exp = v.as_tuple()
            scale = max(0, -exp)
            return Literal(v, T.DecimalType(max(len(digits), scale + 1), scale))
        if isinstance(v, datetime.datetime):
            return Literal(v, T.TIMESTAMP)
        if isinstance(v, datetime.date):
            return Literal(v, T.DATE)
        raise TypeError(f"cannot infer literal type for {v!r}")

    def data_type(self):
        return self.dtype

    def static_range(self):
        if isinstance(self.dtype, (T.Int8Type, T.Int16Type, T.Int32Type,
                                   T.Int64Type)) and self.value is not None:
            return (int(self.value), int(self.value))
        return None

    @property
    def nullable(self):
        return self.value is None

    def _params(self):
        return f"{self.value!r}:{self.dtype!r}"

    def with_children(self, children):
        return self

    def _scalar(self):
        import datetime
        v = self.value
        if isinstance(self.dtype, T.DateType) and isinstance(v, datetime.date):
            return (v - datetime.date(1970, 1, 1)).days
        if isinstance(self.dtype, T.TimestampType) and isinstance(v, datetime.datetime):
            epoch = datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc)
            if v.tzinfo is None:
                v = v.replace(tzinfo=datetime.timezone.utc)
            return int((v - epoch).total_seconds() * 1_000_000)
        if isinstance(self.dtype, T.DecimalType):
            import decimal
            return int(decimal.Decimal(v).scaleb(self.dtype.scale).to_integral_value())
        return v

    def _string_rows(self, cap: int) -> ColumnVector:
        """The string value repeated over `cap` rows, flat."""
        from spark_rapids_tpu.columnar.batch import round_capacity
        bs = np.frombuffer(self.value.encode("utf-8"), np.uint8)
        blen = len(bs)
        rep = np.tile(bs, cap) if blen else np.zeros(0, np.uint8)
        buf = np.zeros(round_capacity(max(len(rep), 1)), np.uint8)
        buf[: len(rep)] = rep
        offsets = jnp.asarray((np.arange(cap + 1) * blen).astype(np.int32))
        return ColumnVector(self.dtype, {"offsets": offsets,
                                         "bytes": jnp.asarray(buf)},
                            jnp.ones(cap, jnp.bool_))

    def eval_tpu(self, ctx: EvalCtx) -> ColumnVector:
        cap = ctx.capacity
        if self.value is None:
            dt = self.dtype if self.dtype != T.NULL else T.NULL
            np_dt = dt.np_dtype if dt.np_dtype is not None else np.int8
            if isinstance(dt, T.StringType):
                data = {"offsets": jnp.zeros(cap + 1, jnp.int32),
                        "bytes": jnp.zeros(8, jnp.uint8)}
            else:
                data = jnp.zeros(cap, np_dt)
            return ColumnVector(dt, data, jnp.zeros(cap, jnp.bool_))
        if isinstance(self.dtype, T.StringType):
            return self._string_rows(cap)
        val = self._scalar()
        data = jnp.full(cap, val, self.dtype.np_dtype)
        return ColumnVector(self.dtype, data, jnp.ones(cap, jnp.bool_))

    def eval_cpu(self, cols, ansi=False) -> CpuCol:
        n = len(cols[0].values) if cols else 0
        if self.value is None:
            np_dt = self.dtype.np_dtype if self.dtype.np_dtype is not None else np.int8
            vals = np.zeros(n, object if isinstance(self.dtype, T.StringType) else np_dt)
            return CpuCol(self.dtype, vals, np.zeros(n, np.bool_))
        if isinstance(self.dtype, T.StringType):
            return CpuCol(self.dtype, np.array([self.value] * n, object),
                          np.ones(n, np.bool_))
        return CpuCol(self.dtype, np.full(n, self._scalar(), self.dtype.np_dtype),
                      np.ones(n, np.bool_))


class SparkPartitionID(Expression):
    """spark_partition_id() (reference GpuSparkPartitionID)."""

    def __init__(self):
        self.children = []

    def data_type(self):
        return T.INT32

    def with_children(self, children):
        return self

    def eval_tpu(self, ctx):
        v = jnp.full(ctx.capacity, 0, jnp.int32) + jnp.asarray(
            ctx.partition_id, jnp.int32)
        return ColumnVector(T.INT32, v, None)

    def eval_cpu(self, cols, ansi=False):
        n = len(cols[0].values) if cols else 0
        pid = CPU_EVAL_CTX.partition_id
        return CpuCol(T.INT32, np.full(n, pid, np.int32), np.ones(n, np.bool_))


class MonotonicallyIncreasingID(Expression):
    """monotonically_increasing_id(): (partition_id << 33) + row index
    within the partition (reference GpuMonotonicallyIncreasingID; same
    layout as Spark's)."""

    def __init__(self):
        self.children = []

    def data_type(self):
        return T.INT64

    def with_children(self, children):
        return self

    def eval_tpu(self, ctx):
        base = (jnp.asarray(ctx.partition_id, jnp.int64) << jnp.int64(33)) \
            + jnp.asarray(ctx.row_base, jnp.int64)
        # ids count LIVE rows (dead rows get garbage, masked downstream)
        idx = jnp.cumsum(ctx.row_mask.astype(jnp.int64)) - 1
        return ColumnVector(T.INT64, base + idx, None)

    def eval_cpu(self, cols, ansi=False):
        n = len(cols[0].values) if cols else 0
        base = (np.int64(CPU_EVAL_CTX.partition_id) << np.int64(33)) \
            + np.int64(CPU_EVAL_CTX.row_base)
        return CpuCol(T.INT64, base + np.arange(n, dtype=np.int64),
                      np.ones(n, np.bool_))


class NullOf(Expression):
    """An all-null column with the (post-binding) type of its child — used
    by rewrites like nullif that need a typed null before names resolve."""

    def __init__(self, child: Expression):
        self.children = [child]

    def data_type(self) -> T.DataType:
        return self.children[0].data_type()

    def with_children(self, children):
        return NullOf(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return ColumnVector(c.dtype, c.data, jnp.zeros(ctx.capacity, jnp.bool_))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        return CpuCol(c.dtype, c.values, np.zeros(len(c.values), np.bool_))


class Alias(Expression):
    def __init__(self, child: Expression, name: str):
        self.children = [child]
        self.name = name

    def data_type(self):
        return self.children[0].data_type()

    def static_range(self):
        return self.children[0].static_range()

    @property
    def nullable(self):
        return self.children[0].nullable

    def _params(self):
        return self.name

    def with_children(self, children):
        return Alias(children[0], self.name)

    def eval_tpu(self, ctx):
        return self.children[0].eval_tpu(ctx)

    def eval_cpu(self, cols, ansi=False):
        return self.children[0].eval_cpu(cols, ansi)


# ---------------------------------------------------------------------------
# Helpers for null-propagating binary/unary ops
# ---------------------------------------------------------------------------

def _valid_of(col: ColumnVector, ctx: EvalCtx) -> jax.Array:
    # validity None means "valid wherever the row is live" — the live mask
    # (selection vector) is the floor, NOT arange<num_rows, because masked
    # batches have live rows at arbitrary positions.
    if col.validity is not None:
        return col.validity
    return ctx.row_mask


def _dec_shift(src: T.DataType, out: "T.DecimalType") -> int:
    """Power-of-ten rescale bringing src's unscaled values to out's scale
    (integrals are decimals of scale 0)."""
    src_scale = src.scale if isinstance(src, T.DecimalType) else 0
    return out.scale - src_scale


def _promote(l: ColumnVector, r: ColumnVector, out: T.DataType):
    if isinstance(out, T.DecimalType):
        def conv(c):
            d = c.data.astype(jnp.int64)
            sh = _dec_shift(c.dtype, out)
            return d * (10 ** sh) if sh else d
        return conv(l), conv(r)
    def conv(c):
        d = c.data if c.dtype == out else c.data.astype(out.np_dtype)
        if isinstance(c.dtype, T.DecimalType) and not isinstance(
                out, T.DecimalType):
            # decimal joining a fractional op: promote the VALUE, not the
            # unscaled integer
            d = d / np.float64(10.0 ** c.dtype.scale)
        return d
    return conv(l), conv(r)


def _promote_cpu(l: CpuCol, r: CpuCol, out: T.DataType):
    if isinstance(out, T.DecimalType):
        def conv(c):
            d = c.values.astype(np.int64)
            sh = _dec_shift(c.dtype, out)
            return d * (10 ** sh) if sh else d
        return conv(l), conv(r)
    def conv(c):
        d = c.values.astype(out.np_dtype, copy=False)
        if isinstance(c.dtype, T.DecimalType) and not isinstance(
                out, T.DecimalType):
            d = d / np.float64(10.0 ** c.dtype.scale)
        return d
    return conv(l), conv(r)


class BinaryExpression(Expression):
    def __init__(self, left: Expression, right: Expression):
        self.children = [left, right]

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    def with_children(self, children):
        return type(self)(children[0], children[1])


class BinaryArithmetic(BinaryExpression):
    """Null-propagating arithmetic with Spark type promotion."""

    op_tpu: Callable = None
    op_cpu: Callable = None

    def data_type(self):
        return T.common_type(self.left.data_type(), self.right.data_type())

    def eval_tpu(self, ctx):
        l = self.left.eval_tpu(ctx)
        r = self.right.eval_tpu(ctx)
        out = self.data_type()
        ld, rd = _promote(l, r, out)
        valid = _valid_of(l, ctx) & _valid_of(r, ctx)
        data = type(self).op_tpu(ld, rd)
        return ColumnVector(out, data, valid)

    def eval_cpu(self, cols, ansi=False):
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        out = self.data_type()
        ld, rd = _promote_cpu(l, r, out)
        with np.errstate(all="ignore"):
            data = type(self).op_cpu(ld, rd)
        return CpuCol(out, data.astype(out.np_dtype, copy=False), l.valid & r.valid)


class Add(BinaryArithmetic):
    op_tpu = staticmethod(lambda a, b: a + b)
    op_cpu = staticmethod(lambda a, b: a + b)


class Subtract(BinaryArithmetic):
    op_tpu = staticmethod(lambda a, b: a - b)
    op_cpu = staticmethod(lambda a, b: a - b)


class Multiply(BinaryArithmetic):
    op_tpu = staticmethod(lambda a, b: a * b)
    op_cpu = staticmethod(lambda a, b: a * b)

    def data_type(self):
        lt, rt = self.left.data_type(), self.right.data_type()
        if isinstance(lt, T.DecimalType) or isinstance(rt, T.DecimalType):
            if isinstance(lt, T.DecimalType) and isinstance(rt, T.DecimalType):
                # Spark: precision p1+p2+1, scale s1+s2. Beyond this
                # engine's 18-digit decimal the product computes as DOUBLE
                # (value-correct, reduced precision — documented) instead
                # of silently mis-scaling.
                if lt.scale + rt.scale > 18 \
                        or lt.precision + rt.precision + 1 > 18:
                    return T.FLOAT64
                return T.DecimalType(lt.precision + rt.precision + 1,
                                     lt.scale + rt.scale)
            dec = lt if isinstance(lt, T.DecimalType) else rt
            other = rt if dec is lt else lt
            if other.is_integral:
                # decimal x integral: scale unchanged. Mirror the
                # decimal-x-decimal overflow guard: when the integral
                # operand's digits could push the unscaled product past 18
                # digits (int64 wrap territory), compute as DOUBLE instead
                # of risking a silently wrong wrapped decimal.
                int_prec = {1: 3, 2: 5, 4: 10, 8: 19}.get(
                    np.dtype(other.np_dtype).itemsize, 19)
                if dec.precision + int_prec > 18:
                    return T.FLOAT64
                return T.DecimalType(18, dec.scale)
            return T.FLOAT64
        return T.common_type(lt, rt)

    def eval_tpu(self, ctx):
        out = self.data_type()
        if not isinstance(out, T.DecimalType):
            return super().eval_tpu(ctx)
        # decimal product: unscaled values multiply DIRECTLY (scales add)
        l = self.left.eval_tpu(ctx)
        r = self.right.eval_tpu(ctx)
        data = l.data.astype(jnp.int64) * r.data.astype(jnp.int64)
        return ColumnVector(out, data, _valid_of(l, ctx) & _valid_of(r, ctx))

    def eval_cpu(self, cols, ansi=False):
        out = self.data_type()
        if not isinstance(out, T.DecimalType):
            return super().eval_cpu(cols, ansi)
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        with np.errstate(all="ignore"):
            data = l.values.astype(np.int64) * r.values.astype(np.int64)
        return CpuCol(out, data, l.valid & r.valid)


class Divide(BinaryExpression):
    """Spark `/`: result is double (fractional); div-by-zero -> null
    (non-ANSI) or error (ANSI). Reference: arithmetic.scala GpuDivide."""

    def data_type(self):
        lt, rt = self.left.data_type(), self.right.data_type()
        if isinstance(lt, T.DecimalType) or isinstance(rt, T.DecimalType):
            return T.FLOAT64  # round-1: decimal division via double
        return T.FLOAT64

    def eval_tpu(self, ctx):
        l = self.left.eval_tpu(ctx)
        r = self.right.eval_tpu(ctx)
        ld = l.data.astype(np.float64)
        rd = r.data.astype(np.float64)
        if isinstance(l.dtype, T.DecimalType):
            ld = ld / (10.0 ** l.dtype.scale)
        if isinstance(r.dtype, T.DecimalType):
            rd = rd / (10.0 ** r.dtype.scale)
        zero = rd == 0.0
        valid = _valid_of(l, ctx) & _valid_of(r, ctx)
        if ctx.ansi:
            ctx.add_error("DIVIDE_BY_ZERO", zero & valid)
        data = ld / jnp.where(zero, 1.0, rd)
        return ColumnVector(T.FLOAT64, jnp.where(zero, 0.0, data), valid & ~zero)

    def eval_cpu(self, cols, ansi=False):
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        ld = l.values.astype(np.float64)
        rd = r.values.astype(np.float64)
        if isinstance(l.dtype, T.DecimalType):
            ld = ld / (10.0 ** l.dtype.scale)
        if isinstance(r.dtype, T.DecimalType):
            rd = rd / (10.0 ** r.dtype.scale)
        zero = rd == 0.0
        valid = l.valid & r.valid
        if ansi and bool((zero & valid).any()):
            raise SparkException("[DIVIDE_BY_ZERO] Division by zero")
        with np.errstate(all="ignore"):
            data = np.where(zero, 0.0, ld / np.where(zero, 1.0, rd))
        return CpuCol(T.FLOAT64, data, valid & ~zero)


class IntegralDivide(BinaryExpression):
    """Spark `div`: long division; div-by-zero -> null (non-ANSI)."""

    def data_type(self):
        return T.INT64

    def eval_tpu(self, ctx):
        l = self.left.eval_tpu(ctx)
        r = self.right.eval_tpu(ctx)
        ld = l.data.astype(np.int64)
        rd = r.data.astype(np.int64)
        zero = rd == 0
        valid = _valid_of(l, ctx) & _valid_of(r, ctx)
        if ctx.ansi:
            ctx.add_error("DIVIDE_BY_ZERO", zero & valid)
        q = _java_int_div(ld, jnp.where(zero, 1, rd))
        return ColumnVector(T.INT64, jnp.where(zero, 0, q), valid & ~zero)

    def eval_cpu(self, cols, ansi=False):
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        ld = l.values.astype(np.int64)
        rd = r.values.astype(np.int64)
        zero = rd == 0
        valid = l.valid & r.valid
        if ansi and bool((zero & valid).any()):
            raise SparkException("[DIVIDE_BY_ZERO] Division by zero")
        safe = np.where(zero, 1, rd)
        with np.errstate(all="ignore"):
            q = ld // safe
            rem = ld - q * safe
            # numpy floors; Java truncates toward zero
            q = np.where((rem != 0) & ((ld < 0) != (safe < 0)), q + 1, q)
        return CpuCol(T.INT64, np.where(zero, 0, q), valid & ~zero)


def _java_int_div(a, b):
    """Truncated (toward-zero) integer division, Java semantics."""
    q = a // b
    rem = a - q * b
    fix = (rem != 0) & ((a < 0) != (b < 0))
    return jnp.where(fix, q + 1, q)


class Remainder(BinaryExpression):
    """Spark `%`: sign follows dividend (Java %); zero divisor -> null."""

    def data_type(self):
        return T.common_type(self.left.data_type(), self.right.data_type())

    def static_range(self):
        r = self.right.static_range()
        if r is None or not isinstance(self.data_type(),
                                       (T.Int8Type, T.Int16Type, T.Int32Type,
                                        T.Int64Type)):
            return None
        m = max(abs(r[0]), abs(r[1]))
        if m == 0:
            return None
        lr = self.left.static_range()
        lo = 0 if (lr is not None and lr[0] >= 0) else -(m - 1)
        return (lo, m - 1)

    def eval_tpu(self, ctx):
        l = self.left.eval_tpu(ctx)
        r = self.right.eval_tpu(ctx)
        out = self.data_type()
        ld, rd = _promote(l, r, out)
        valid = _valid_of(l, ctx) & _valid_of(r, ctx)
        if out.is_integral:
            zero = rd == 0
            if ctx.ansi:
                ctx.add_error("DIVIDE_BY_ZERO", zero & valid)
            safe = jnp.where(zero, 1, rd)
            q = _java_int_div(ld, safe)
            rem = ld - q * safe
            return ColumnVector(out, jnp.where(zero, 0, rem), valid & ~zero)
        rem = jnp.where(rd == 0, jnp.nan, ld - rd * lax.div(ld, rd).astype(ld.dtype) if False else jnp.fmod(ld, rd))
        return ColumnVector(out, rem, valid)

    def eval_cpu(self, cols, ansi=False):
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        out = self.data_type()
        ld, rd = _promote_cpu(l, r, out)
        valid = l.valid & r.valid
        with np.errstate(all="ignore"):
            if out.is_integral:
                zero = rd == 0
                if ansi and bool((zero & valid).any()):
                    raise SparkException("[DIVIDE_BY_ZERO] Division by zero")
                rem = np.fmod(ld, np.where(zero, 1, rd))
                return CpuCol(out, np.where(zero, 0, rem), valid & ~zero)
            return CpuCol(out, np.fmod(ld, rd), valid)


class UnaryMinus(Expression):
    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return self.children[0].data_type()

    def with_children(self, children):
        return UnaryMinus(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return ColumnVector(c.dtype, -c.data, _valid_of(c, ctx))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        with np.errstate(all="ignore"):
            return CpuCol(c.dtype, -c.values, c.valid)


class Abs(Expression):
    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return self.children[0].data_type()

    def with_children(self, children):
        return Abs(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return ColumnVector(c.dtype, jnp.abs(c.data), _valid_of(c, ctx))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        with np.errstate(all="ignore"):
            return CpuCol(c.dtype, np.abs(c.values), c.valid)


# ---------------------------------------------------------------------------
# Comparisons
# ---------------------------------------------------------------------------

def _string_eq_tpu(l: ColumnVector, r: ColumnVector) -> jax.Array:
    """Exact per-row string equality: lengths equal AND bytes equal, computed
    with a bounded while_loop over 8-byte strides. Dict-encoded pairs with
    a shared vocab short-circuit to integer code equality."""
    from spark_rapids_tpu.ops.kernels import flatten_dict_column
    if l.is_dict and r.is_dict and \
            l.data["dict_offsets"] is r.data["dict_offsets"] and \
            l.data["dict_bytes"] is r.data["dict_bytes"]:
        return l.data["codes"] == r.data["codes"]
    if l.is_dict:
        l = flatten_dict_column(l, 0)
    if r.is_dict:
        r = flatten_dict_column(r, 0)
    lo, lb = l.data["offsets"], l.data["bytes"]
    ro, rb = r.data["offsets"], r.data["bytes"]
    ll = lo[1:] - lo[:-1]
    rl = ro[1:] - ro[:-1]
    same_len = ll == rl
    maxlen = jnp.maximum(jnp.max(jnp.where(same_len, ll, 0)), 0)

    def body(state):
        i, eq = state
        p = i * 8

        def get8(raw, off):
            vals = []
            for k in range(8):
                idx = jnp.clip(off + p + k, 0, raw.shape[0] - 1)
                vals.append(jnp.where(p + k < ll, raw[idx], 0).astype(jnp.uint64) << jnp.uint64(8 * k))
            out = vals[0]
            for v in vals[1:]:
                out = out | v
            return out
        lw = get8(lb, lo[:-1])
        rw = get8(rb, ro[:-1])
        active = p < ll
        eq = eq & (~active | (lw == rw))
        return i + 1, eq

    def cond(state):
        i, _ = state
        return i * 8 < maxlen

    _, eq = lax.while_loop(cond, body, (jnp.int32(0), same_len))
    return eq


def _flat_view(c: ColumnVector) -> ColumnVector:
    """The vocab of a dict column viewed as a small flat string column."""
    return ColumnVector(T.STRING, {"offsets": c.data["dict_offsets"],
                                   "bytes": c.data["dict_bytes"]}, None,
                        str_width=c.str_width)


def _is_string_value(e: Expression) -> bool:
    return isinstance(e, Literal) and e.value is not None and \
        isinstance(e.dtype, T.StringType)


def _vocab_eq_literal(c: ColumnVector, literal: "Literal") -> jax.Array:
    """Dict-encoded column == string literal: every vocab entry (repeated
    ones too) is compared with the literal once, and each row reads its
    entry's answer by code. O(vocab bytes) byte work plus one gather of
    the batch, where the flatten pays capacity x vocab bytes."""
    vocab = _flat_view(c)
    n = vocab.capacity
    hit = _string_eq_tpu(vocab, literal._string_rows(n))
    codes = c.data["codes"]
    if isinstance(codes, jax.core.Tracer):
        _cc.note_traced("vocab_predicates_traced")
    return hit[jnp.clip(codes, 0, n - 1)]


class BinaryComparison(BinaryExpression):
    op_tpu: Callable = None
    op_cpu: Callable = None

    def data_type(self):
        return T.BOOLEAN

    def _compare_tpu(self, ctx):
        l = self.left.eval_tpu(ctx)
        r = self.right.eval_tpu(ctx)
        if isinstance(l.dtype, T.StringType):
            if type(self) in (EqualTo, EqualNullSafe):
                for c, other in ((l, self.right), (r, self.left)):
                    if c.is_dict and c.dict_size and _is_string_value(other):
                        return l, r, _vocab_eq_literal(c, other)
                return l, r, _string_eq_tpu(l, r)
            raise NotImplementedError("string ordering comparison on device")
        out = T.common_type(l.dtype, r.dtype)
        ld, rd = _promote(l, r, out)
        return l, r, type(self).op_tpu(ld, rd)

    def eval_tpu(self, ctx):
        l, r, cmp = self._compare_tpu(ctx)
        valid = _valid_of(l, ctx) & _valid_of(r, ctx)
        return ColumnVector(T.BOOLEAN, cmp, valid)

    def _compare_cpu(self, l: CpuCol, r: CpuCol):
        if isinstance(l.dtype, T.StringType):
            return type(self).op_cpu(l.values, r.values)
        out = T.common_type(l.dtype, r.dtype)
        ld, rd = _promote_cpu(l, r, out)
        with np.errstate(all="ignore"):
            return type(self).op_cpu(ld, rd)

    def eval_cpu(self, cols, ansi=False):
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        return CpuCol(T.BOOLEAN, self._compare_cpu(l, r), l.valid & r.valid)


class EqualTo(BinaryComparison):
    op_tpu = staticmethod(lambda a, b: a == b)
    op_cpu = staticmethod(lambda a, b: a == b)


class LessThan(BinaryComparison):
    op_tpu = staticmethod(lambda a, b: a < b)
    op_cpu = staticmethod(lambda a, b: a < b)


class LessThanOrEqual(BinaryComparison):
    op_tpu = staticmethod(lambda a, b: a <= b)
    op_cpu = staticmethod(lambda a, b: a <= b)


class GreaterThan(BinaryComparison):
    op_tpu = staticmethod(lambda a, b: a > b)
    op_cpu = staticmethod(lambda a, b: a > b)


class GreaterThanOrEqual(BinaryComparison):
    op_tpu = staticmethod(lambda a, b: a >= b)
    op_cpu = staticmethod(lambda a, b: a >= b)


class EqualNullSafe(BinaryComparison):
    """<=>: null<=>null is true, never returns null."""
    op_tpu = staticmethod(lambda a, b: a == b)
    op_cpu = staticmethod(lambda a, b: a == b)

    def eval_tpu(self, ctx):
        l, r, cmp = self._compare_tpu(ctx)
        lv, rv = _valid_of(l, ctx), _valid_of(r, ctx)
        val = jnp.where(lv & rv, cmp, (~lv) & (~rv))
        return ColumnVector(T.BOOLEAN, val, jnp.ones(ctx.capacity, jnp.bool_))

    def eval_cpu(self, cols, ansi=False):
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        cmp = self._compare_cpu(l, r)
        val = np.where(l.valid & r.valid, cmp, (~l.valid) & (~r.valid))
        return CpuCol(T.BOOLEAN, val, np.ones(len(val), np.bool_))


# ---------------------------------------------------------------------------
# Boolean logic (Kleene three-valued)
# ---------------------------------------------------------------------------

class And(BinaryExpression):
    def data_type(self):
        return T.BOOLEAN

    def eval_tpu(self, ctx):
        l = self.left.eval_tpu(ctx)
        r = self.right.eval_tpu(ctx)
        lv, rv = _valid_of(l, ctx), _valid_of(r, ctx)
        ld = l.data.astype(jnp.bool_)
        rd = r.data.astype(jnp.bool_)
        lfalse = lv & ~ld
        rfalse = rv & ~rd
        value = ld & rd
        valid = (lv & rv) | lfalse | rfalse
        return ColumnVector(T.BOOLEAN, value & lv & rv, valid)

    def eval_cpu(self, cols, ansi=False):
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        ld = l.values.astype(np.bool_)
        rd = r.values.astype(np.bool_)
        lfalse = l.valid & ~ld
        rfalse = r.valid & ~rd
        valid = (l.valid & r.valid) | lfalse | rfalse
        return CpuCol(T.BOOLEAN, ld & rd & l.valid & r.valid, valid)


class Or(BinaryExpression):
    def data_type(self):
        return T.BOOLEAN

    def eval_tpu(self, ctx):
        l = self.left.eval_tpu(ctx)
        r = self.right.eval_tpu(ctx)
        lv, rv = _valid_of(l, ctx), _valid_of(r, ctx)
        ld = l.data.astype(jnp.bool_) & lv
        rd = r.data.astype(jnp.bool_) & rv
        valid = (lv & rv) | ld | rd
        return ColumnVector(T.BOOLEAN, ld | rd, valid)

    def eval_cpu(self, cols, ansi=False):
        l = self.left.eval_cpu(cols, ansi)
        r = self.right.eval_cpu(cols, ansi)
        ld = l.values.astype(np.bool_) & l.valid
        rd = r.values.astype(np.bool_) & r.valid
        valid = (l.valid & r.valid) | ld | rd
        return CpuCol(T.BOOLEAN, ld | rd, valid)


class Not(Expression):
    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.BOOLEAN

    def with_children(self, children):
        return Not(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return ColumnVector(T.BOOLEAN, ~c.data.astype(jnp.bool_), _valid_of(c, ctx))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        return CpuCol(T.BOOLEAN, ~c.values.astype(np.bool_), c.valid)


# ---------------------------------------------------------------------------
# Null predicates / conditionals
# ---------------------------------------------------------------------------

class IsNull(Expression):
    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return IsNull(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return ColumnVector(T.BOOLEAN, ~_valid_of(c, ctx), jnp.ones(ctx.capacity, jnp.bool_))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        return CpuCol(T.BOOLEAN, ~c.valid, np.ones(len(c.valid), np.bool_))


class IsNotNull(Expression):
    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return IsNotNull(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return ColumnVector(T.BOOLEAN, _valid_of(c, ctx), jnp.ones(ctx.capacity, jnp.bool_))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        return CpuCol(T.BOOLEAN, c.valid.copy(), np.ones(len(c.valid), np.bool_))


class IsNaN(Expression):
    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.BOOLEAN

    def with_children(self, children):
        return IsNaN(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return ColumnVector(T.BOOLEAN, jnp.isnan(c.data), _valid_of(c, ctx))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        return CpuCol(T.BOOLEAN, np.isnan(c.values.astype(np.float64)), c.valid)


class In(Expression):
    """IN list of literals (reference GpuInSet)."""

    def __init__(self, child, values: List[Expression]):
        self.children = [child] + list(values)

    def data_type(self):
        return T.BOOLEAN

    def with_children(self, children):
        return In(children[0], children[1:])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        acc = None
        for v in self.children[1:]:
            eq = EqualTo(_RawCol(c), v).eval_tpu(ctx)
            acc = eq if acc is None else Or(_RawCol(acc), _RawCol(eq)).eval_tpu(ctx)
        return acc

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        acc = None
        for v in self.children[1:]:
            eq = EqualTo(_RawCpu(c), v).eval_cpu(cols, ansi)
            acc = eq if acc is None else Or(_RawCpu(acc), _RawCpu(eq)).eval_cpu(cols, ansi)
        return acc


class _RawCol(Expression):
    """Internal: wraps an already-evaluated device column as an expression."""

    def __init__(self, col: ColumnVector):
        self.col = col
        self.children = []

    def data_type(self):
        return self.col.dtype

    def with_children(self, children):
        return self

    def eval_tpu(self, ctx):
        return self.col


class _RawCpu(Expression):
    def __init__(self, col: CpuCol):
        self.col = col
        self.children = []

    def data_type(self):
        return self.col.dtype

    def with_children(self, children):
        return self

    def eval_cpu(self, cols, ansi=False):
        return self.col


class If(Expression):
    def __init__(self, pred, then, otherwise):
        self.children = [pred, then, otherwise]

    def data_type(self):
        return T.common_type(self.children[1].data_type(), self.children[2].data_type())

    def with_children(self, children):
        return If(children[0], children[1], children[2])

    def eval_tpu(self, ctx):
        p = self.children[0].eval_tpu(ctx)
        t = self.children[1].eval_tpu(ctx)
        f = self.children[2].eval_tpu(ctx)
        out = self.data_type()
        take_then = p.data.astype(jnp.bool_) & _valid_of(p, ctx)
        if isinstance(out, T.StringType):
            return _select_strings_tpu(take_then, t, f, _valid_of(t, ctx), _valid_of(f, ctx))
        td, fd = _promote(t, f, out)
        data = jnp.where(take_then, td, fd)
        valid = jnp.where(take_then, _valid_of(t, ctx), _valid_of(f, ctx))
        return ColumnVector(out, data, valid)

    def eval_cpu(self, cols, ansi=False):
        p = self.children[0].eval_cpu(cols, ansi)
        t = self.children[1].eval_cpu(cols, ansi)
        f = self.children[2].eval_cpu(cols, ansi)
        out = self.data_type()
        take_then = p.values.astype(np.bool_) & p.valid
        if isinstance(out, T.StringType):
            vals = np.where(take_then, t.values, f.values)
        else:
            td, fd = _promote_cpu(t, f, out)
            vals = np.where(take_then, td, fd)
        valid = np.where(take_then, t.valid, f.valid)
        return CpuCol(out, vals, valid)


def _select_strings_tpu(mask, t: ColumnVector, f: ColumnVector, tv, fv) -> ColumnVector:
    """Per-row select between two string columns: build new offsets from the
    chosen lengths, then gather bytes from the chosen source."""
    from spark_rapids_tpu.ops.kernels import flatten_dict_column
    if t.is_dict:
        t = flatten_dict_column(t, 0)
    if f.is_dict:
        f = flatten_dict_column(f, 0)
    to_, tb = t.data["offsets"], t.data["bytes"]
    fo, fb = f.data["offsets"], f.data["bytes"]
    tl = to_[1:] - to_[:-1]
    fl = fo[1:] - fo[:-1]
    lens = jnp.where(mask, tl, fl)
    new_off = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(lens).astype(jnp.int32)])
    out_cap = max(tb.shape[0], fb.shape[0])
    b = jnp.arange(out_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_off, b, side="right").astype(jnp.int32) - 1,
                   0, mask.shape[0] - 1)
    off_in_row = b - new_off[row]
    tsrc = jnp.clip(to_[row] + off_in_row, 0, tb.shape[0] - 1)
    fsrc = jnp.clip(fo[row] + off_in_row, 0, fb.shape[0] - 1)
    out_b = jnp.where(mask[row], tb[tsrc], fb[fsrc])
    out_b = jnp.where(b < new_off[-1], out_b, 0).astype(jnp.uint8)
    valid = jnp.where(mask, tv, fv)
    return ColumnVector(T.STRING, {"offsets": new_off, "bytes": out_b}, valid)


class CaseWhen(Expression):
    """CASE WHEN p1 THEN v1 ... ELSE e END, folded as nested If."""

    def __init__(self, branches: List[Tuple[Expression, Expression]],
                 otherwise: Optional[Expression] = None):
        self.branches = branches
        self.otherwise_expr = otherwise or Literal(None, branches[0][1].data_type()
                                                   if _resolved(branches[0][1]) else T.NULL)
        self.children = [e for b in branches for e in b] + [self.otherwise_expr]

    def _fold(self) -> Expression:
        out = self.otherwise_expr
        for p, v in reversed(self.branches):
            out = If(p, v, out)
        return out

    def data_type(self):
        return self._fold().data_type()

    def with_children(self, children):
        nb = len(self.branches)
        branches = [(children[2 * i], children[2 * i + 1]) for i in range(nb)]
        return CaseWhen(branches, children[-1])

    def eval_tpu(self, ctx):
        return self._fold().eval_tpu(ctx)

    def eval_cpu(self, cols, ansi=False):
        return self._fold().eval_cpu(cols, ansi)


def _resolved(e: Expression) -> bool:
    try:
        e.data_type()
        return True
    except Exception:
        return False


class KnownNotNull(Expression):
    """Catalyst's null-introspection wrapper (reference registers it as
    a pass-through): asserts the optimizer proved the child non-null."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return self.children[0].data_type()

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return KnownNotNull(children[0])

    def eval_tpu(self, ctx):
        return self.children[0].eval_tpu(ctx)

    def eval_cpu(self, cols, ansi=False):
        return self.children[0].eval_cpu(cols, ansi)


class KnownFloatingPointNormalized(KnownNotNull):
    """Pass-through marker: the child's NaN/-0.0 are already canonical."""

    @property
    def nullable(self):
        return self.children[0].nullable

    def with_children(self, children):
        return KnownFloatingPointNormalized(children[0])


class NormalizeNaNAndZero(Expression):
    """Canonicalize floats for grouping/join keys: -0.0 -> 0.0 and any
    NaN bit pattern -> the canonical NaN (reference
    normalizeNansAndZeros in GpuOverrides; Catalyst inserts it under
    First/aggregation keys)."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return self.children[0].data_type()

    def with_children(self, children):
        return NormalizeNaNAndZero(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        v = c.data
        # explicit compare: XLA folds v + 0.0 back to v, keeping -0.0
        v = jnp.where(v == 0, jnp.zeros((), v.dtype), v)
        v = jnp.where(jnp.isnan(v), jnp.nan, v)
        return ColumnVector(c.dtype, v, _valid_of(c, ctx))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        v = c.values
        with np.errstate(all="ignore"):
            v = np.where(v == 0, np.zeros((), v.dtype), v)
            v = np.where(np.isnan(v), np.nan, v)
        return CpuCol(c.dtype, v, c.valid)


class AtLeastNNonNulls(Expression):
    """Catalyst's dropna predicate: true when >= n of the children are
    non-null (and, for floats, non-NaN — Spark counts NaN as missing
    here)."""

    def __init__(self, n: int, *children):
        self.n = int(n)
        self.children = list(children)

    def _params(self):
        return str(self.n)

    def data_type(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return AtLeastNNonNulls(self.n, *children)

    def eval_tpu(self, ctx):
        cnt = None
        for c in self.children:
            cc = c.eval_tpu(ctx)
            ok = _valid_of(cc, ctx)
            if isinstance(cc.dtype, (T.Float32Type, T.Float64Type)):
                ok = ok & ~jnp.isnan(cc.data)
            one = ok.astype(jnp.int32)
            cnt = one if cnt is None else cnt + one
        return ColumnVector(T.BOOLEAN, cnt >= self.n,
                            jnp.ones(cnt.shape[0], jnp.bool_))

    def eval_cpu(self, cols, ansi=False):
        cnt = None
        for c in self.children:
            cc = c.eval_cpu(cols, ansi)
            ok = cc.valid
            if isinstance(cc.dtype, (T.Float32Type, T.Float64Type)):
                with np.errstate(all="ignore"):
                    ok = ok & ~np.isnan(cc.values)
            cnt = ok.astype(np.int32) if cnt is None \
                else cnt + ok.astype(np.int32)
        return CpuCol(T.BOOLEAN, cnt >= self.n,
                      np.ones(len(cnt), np.bool_))


class Coalesce(Expression):
    def __init__(self, *exprs):
        self.children = list(exprs)

    def data_type(self):
        dt = self.children[0].data_type()
        for c in self.children[1:]:
            dt = T.common_type(dt, c.data_type())
        return dt

    def with_children(self, children):
        return Coalesce(*children)

    def eval_tpu(self, ctx):
        out = self.data_type()
        acc = self.children[0].eval_tpu(ctx)
        acc_valid = _valid_of(acc, ctx)
        if not isinstance(out, T.StringType) and acc.dtype != out:
            acc = ColumnVector(out, acc.data.astype(out.np_dtype), acc_valid)
        for c in self.children[1:]:
            nxt = c.eval_tpu(ctx)
            nxt_valid = _valid_of(nxt, ctx)
            if isinstance(out, T.StringType):
                acc = _select_strings_tpu(acc_valid, acc, nxt, acc_valid, nxt_valid)
            else:
                nd = nxt.data.astype(out.np_dtype)
                acc = ColumnVector(out, jnp.where(acc_valid, acc.data, nd),
                                   acc_valid | nxt_valid)
            acc_valid = acc.validity
        return acc

    def eval_cpu(self, cols, ansi=False):
        out = self.data_type()
        acc = self.children[0].eval_cpu(cols, ansi)
        vals = acc.values if isinstance(out, T.StringType) else acc.values.astype(out.np_dtype)
        valid = acc.valid.copy()
        for c in self.children[1:]:
            nxt = c.eval_cpu(cols, ansi)
            nvals = nxt.values if isinstance(out, T.StringType) else nxt.values.astype(out.np_dtype)
            vals = np.where(valid, vals, nvals)
            valid = valid | nxt.valid
        return CpuCol(out, vals, valid)


# ---------------------------------------------------------------------------
# Cast (reference GpuCast.scala; numeric matrix for round 1, string casts in
# expr/strings.py where byte-plane rendering lives)
# ---------------------------------------------------------------------------

_INT_BOUNDS = {
    np.dtype(np.int8): (-(2 ** 7), 2 ** 7 - 1),
    np.dtype(np.int16): (-(2 ** 15), 2 ** 15 - 1),
    np.dtype(np.int32): (-(2 ** 31), 2 ** 31 - 1),
    np.dtype(np.int64): (-(2 ** 63), 2 ** 63 - 1),
}


class Cast(Expression):
    def __init__(self, child: Expression, to: T.DataType):
        self.children = [child]
        self.to = to

    def data_type(self):
        return self.to

    def _params(self):
        return repr(self.to)

    def with_children(self, children):
        return Cast(children[0], self.to)

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        src, dst = c.dtype, self.to
        valid = _valid_of(c, ctx)
        if src == dst:
            return c
        if isinstance(dst, T.StringType) or isinstance(src, T.StringType):
            from spark_rapids_tpu.expr import strings as S
            return S.cast_string_tpu(c, dst, ctx)
        if isinstance(src, T.BooleanType):
            data = c.data.astype(dst.np_dtype)
            return ColumnVector(dst, data, valid)
        if isinstance(dst, T.BooleanType):
            return ColumnVector(dst, c.data != 0, valid)
        if isinstance(dst, (T.Float32Type, T.Float64Type)):
            data = c.data.astype(dst.np_dtype)
            if isinstance(src, T.DecimalType):
                data = data / np.float64(10.0 ** src.scale)
            return ColumnVector(dst, data.astype(dst.np_dtype), valid)
        if isinstance(dst, T.DecimalType):
            return self._to_decimal_tpu(c, dst, ctx, valid)
        if isinstance(src, (T.Float32Type, T.Float64Type)) and dst.is_integral:
            lo, hi = _INT_BOUNDS[np.dtype(dst.np_dtype)]
            v = c.data.astype(np.float64)
            if ctx.ansi:
                bad = (jnp.isnan(v) | (v < lo) | (v > hi)) & valid
                ctx.add_error("CAST_OVERFLOW", bad)
            clamped = jnp.clip(jnp.where(jnp.isnan(v), 0.0, v), lo, hi)
            data = jnp.trunc(clamped).astype(dst.np_dtype)
            return ColumnVector(dst, data, valid)
        if isinstance(src, T.DecimalType) and dst.is_integral:
            v = _java_int_div(c.data, jnp.int64(10 ** src.scale))
            return ColumnVector(dst, v.astype(dst.np_dtype), valid)
        # integral/date/timestamp -> integral: Java narrowing (bit truncation)
        data = c.data.astype(np.int64)
        if isinstance(src, T.TimestampType) and isinstance(dst, T.DateType):
            days = _java_floor_div(data, 86_400_000_000)
            return ColumnVector(dst, days.astype(np.int32), valid)
        if isinstance(src, T.DateType) and isinstance(dst, T.TimestampType):
            return ColumnVector(dst, data * 86_400_000_000, valid)
        if isinstance(src, T.TimestampType) and dst.is_integral:
            data = _java_floor_div(data, 1_000_000)  # ts -> seconds
        if isinstance(dst, T.TimestampType) and src.is_integral:
            return ColumnVector(dst, data * 1_000_000, valid)
        if ctx.ansi and dst.is_integral:
            lo, hi = _INT_BOUNDS[np.dtype(dst.np_dtype)]
            ctx.add_error("CAST_OVERFLOW", ((data < lo) | (data > hi)) & valid)
        return ColumnVector(dst, data.astype(dst.np_dtype), valid)

    def _to_decimal_tpu(self, c, dst, ctx, valid):
        if isinstance(c.dtype, T.DecimalType):
            shift = dst.scale - c.dtype.scale
            if shift >= 0:
                data = c.data * (10 ** shift)
            else:
                data = _round_half_up_div(c.data, 10 ** (-shift))
        elif c.dtype.is_integral:
            data = c.data.astype(np.int64) * (10 ** dst.scale)
        else:
            scaled = c.data.astype(np.float64) * (10.0 ** dst.scale)
            data = jnp.round(scaled).astype(np.int64)
        bound = 10 ** min(dst.precision, 18)
        overflow = (data <= -bound) | (data >= bound)
        if ctx.ansi:
            ctx.add_error("CAST_OVERFLOW", overflow & valid)
        return ColumnVector(dst, jnp.where(overflow, 0, data), valid & ~overflow)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        src, dst = c.dtype, self.to
        valid = c.valid
        if src == dst:
            return c
        if isinstance(dst, T.StringType) or isinstance(src, T.StringType):
            from spark_rapids_tpu.expr import strings as S
            return S.cast_string_cpu(c, dst, ansi)
        with np.errstate(all="ignore"):
            if isinstance(src, T.BooleanType):
                return CpuCol(dst, c.values.astype(dst.np_dtype), valid)
            if isinstance(dst, T.BooleanType):
                return CpuCol(dst, c.values != 0, valid)
            if isinstance(dst, (T.Float32Type, T.Float64Type)):
                vals = c.values.astype(np.float64)
                if isinstance(src, T.DecimalType):
                    vals = vals / (10.0 ** src.scale)
                return CpuCol(dst, vals.astype(dst.np_dtype), valid)
            if isinstance(dst, T.DecimalType):
                if isinstance(src, T.DecimalType):
                    shift = dst.scale - src.scale
                    if shift >= 0:
                        vals = c.values * (10 ** shift)
                    else:
                        vals = _round_half_up_div_np(c.values, 10 ** (-shift))
                elif src.is_integral:
                    vals = c.values.astype(np.int64) * (10 ** dst.scale)
                else:
                    vals = np.round(c.values.astype(np.float64) * (10.0 ** dst.scale)).astype(np.int64)
                bound = 10 ** min(dst.precision, 18)
                overflow = (vals <= -bound) | (vals >= bound)
                if ansi and bool((overflow & valid).any()):
                    raise SparkException("[CAST_OVERFLOW]")
                return CpuCol(dst, np.where(overflow, 0, vals), valid & ~overflow)
            if isinstance(src, (T.Float32Type, T.Float64Type)) and dst.is_integral:
                lo, hi = _INT_BOUNDS[np.dtype(dst.np_dtype)]
                v = c.values.astype(np.float64)
                if ansi and bool(((np.isnan(v) | (v < lo) | (v > hi)) & valid).any()):
                    raise SparkException("[CAST_OVERFLOW]")
                clamped = np.clip(np.where(np.isnan(v), 0.0, v), lo, hi)
                return CpuCol(dst, np.trunc(clamped).astype(dst.np_dtype), valid)
            if isinstance(src, T.DecimalType) and dst.is_integral:
                q = (np.abs(c.values) // (10 ** src.scale)) * np.sign(c.values)
                return CpuCol(dst, q.astype(dst.np_dtype), valid)
            data = c.values.astype(np.int64)
            if isinstance(src, T.TimestampType) and isinstance(dst, T.DateType):
                return CpuCol(dst, np.floor_divide(data, 86_400_000_000).astype(np.int32), valid)
            if isinstance(src, T.DateType) and isinstance(dst, T.TimestampType):
                return CpuCol(dst, data * 86_400_000_000, valid)
            if isinstance(src, T.TimestampType) and dst.is_integral:
                data = np.floor_divide(data, 1_000_000)
            if isinstance(dst, T.TimestampType) and src.is_integral:
                return CpuCol(dst, data * 1_000_000, valid)
            if ansi and dst.is_integral:
                lo, hi = _INT_BOUNDS[np.dtype(dst.np_dtype)]
                if bool((((data < lo) | (data > hi)) & valid).any()):
                    raise SparkException("[CAST_OVERFLOW]")
            return CpuCol(dst, data.astype(dst.np_dtype), valid)


def _java_floor_div(a, b):
    return jnp.floor_divide(a, b)


def _round_half_up_div(v, d):
    """Decimal scale-down with HALF_UP rounding (Spark decimal semantics)."""
    sign = jnp.sign(v)
    av = jnp.abs(v)
    return sign * ((av + d // 2) // d)


def _round_half_up_div_np(v, d):
    sign = np.sign(v)
    av = np.abs(v)
    return sign * ((av + d // 2) // d)
