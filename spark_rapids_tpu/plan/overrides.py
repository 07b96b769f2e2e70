"""Plan-rewrite engine: tagging, conversion, fallback, explain.

Reference parity: GpuOverrides.scala (the rule registries + wrapAndTagPlan +
doConvertPlan), RapidsMeta.scala (the wrapper/tagging hierarchy), and
GpuTransitionOverrides (transition insertion -- here, CPU fallback bridging
is handled inside CpuFallbackExec).

Every plan node and expression is wrapped in a Meta, tagged with reasons it
cannot run on TPU (type-signature checks, unregistered expressions, per-op
config disables), and converted bottom-up: supported nodes become TpuExecs,
unsupported ones become CpuFallbackExec over the CPU backend -- per-operator
fallback exactly like the reference. Explain output lists every fallback
with its reasons (spark.rapids.sql.explain=NOT_ON_TPU behaviour).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Type

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.types import Sigs, TypeSig
from spark_rapids_tpu.expr import core as E
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import datetime as DT
from spark_rapids_tpu.expr import math as MA
from spark_rapids_tpu.expr import strings as S
from spark_rapids_tpu.plan import nodes as P


# ---------------------------------------------------------------------------
# Expression rules (reference: the 227 expr[...] registrations)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ExprRule:
    name: str
    input_sig: TypeSig
    result_sig: TypeSig
    doc: str = ""
    extra: Optional[Callable[[E.Expression], Optional[str]]] = None


EXPR_RULES: Dict[Type, ExprRule] = {}


def expr_rule(cls: Type, input_sig: TypeSig = Sigs.COMMON,
              result_sig: TypeSig = Sigs.COMMON, doc: str = "",
              extra=None, name: Optional[str] = None):
    EXPR_RULES[cls] = ExprRule(name or cls.__name__, input_sig, result_sig,
                               doc, extra)


_NUM = Sigs.NUMERIC + TypeSig(["NULL"])
_NUMDT = _NUM + TypeSig(["DATE", "TIMESTAMP", "BOOLEAN"])

expr_rule(E.BoundRef, Sigs.COMMON, Sigs.COMMON, "column reference")
expr_rule(E.Literal, Sigs.COMMON, Sigs.COMMON, "literal value")
expr_rule(E.Alias, Sigs.COMMON, Sigs.COMMON, "named expression")
expr_rule(E.NullOf, Sigs.COMMON, Sigs.COMMON, "typed null")
expr_rule(E.SparkPartitionID, Sigs.COMMON, Sigs.COMMON, "spark_partition_id()")
expr_rule(E.MonotonicallyIncreasingID, Sigs.COMMON, Sigs.COMMON,
          "monotonically_increasing_id()")
expr_rule(E.Add, _NUM, _NUM, "addition")
expr_rule(E.Subtract, _NUM, _NUM, "subtraction")
expr_rule(E.Multiply, _NUM, _NUM, "multiplication")
expr_rule(E.Divide, _NUM, _NUM, "division (double result)")
expr_rule(E.IntegralDivide, _NUM, _NUM, "integral division")
expr_rule(E.Remainder, _NUM, _NUM, "modulo")
expr_rule(E.UnaryMinus, _NUM, _NUM, "negation")
expr_rule(E.Abs, _NUM, _NUM, "absolute value")


def _no_string_order(e: E.Expression) -> Optional[str]:
    for c in e.children:
        if isinstance(c.data_type(), T.StringType):
            return "string ordering comparison not supported on device"
    return None


expr_rule(E.EqualTo, Sigs.COMMON, Sigs.COMMON, "equality")
expr_rule(E.EqualNullSafe, Sigs.COMMON, Sigs.COMMON, "null-safe equality")
expr_rule(E.LessThan, _NUMDT, _NUMDT, "less than", extra=_no_string_order)
expr_rule(E.LessThanOrEqual, _NUMDT, _NUMDT, "<=", extra=_no_string_order)
expr_rule(E.GreaterThan, _NUMDT, _NUMDT, ">", extra=_no_string_order)
expr_rule(E.GreaterThanOrEqual, _NUMDT, _NUMDT, ">=", extra=_no_string_order)
expr_rule(E.And, Sigs.COMMON, Sigs.COMMON, "logical AND (Kleene)")
expr_rule(E.Or, Sigs.COMMON, Sigs.COMMON, "logical OR (Kleene)")
expr_rule(E.Not, Sigs.COMMON, Sigs.COMMON, "logical NOT")
expr_rule(E.IsNull, Sigs.COMMON, Sigs.COMMON, "null test")
expr_rule(E.IsNotNull, Sigs.COMMON, Sigs.COMMON, "not-null test")
expr_rule(E.IsNaN, _NUM, _NUM, "NaN test")
expr_rule(E.In, Sigs.COMMON, Sigs.COMMON, "IN literal list")
expr_rule(E.If, Sigs.COMMON, Sigs.COMMON, "conditional")
expr_rule(E.CaseWhen, Sigs.COMMON, Sigs.COMMON, "CASE WHEN")
expr_rule(E.Coalesce, Sigs.COMMON, Sigs.COMMON, "coalesce")

# Cast: only the device-implemented matrix (reference GpuCast type matrix)
_CASTABLE_FIXED = (T.BooleanType, T.Int8Type, T.Int16Type, T.Int32Type,
                   T.Int64Type, T.Float32Type, T.Float64Type, T.DateType,
                   T.TimestampType, T.DecimalType)


def _cast_check(e: E.Expression) -> Optional[str]:
    src = e.children[0].data_type()
    dst = e.to
    if isinstance(src, T.StringType) and isinstance(dst, T.StringType):
        return None
    if isinstance(src, _CASTABLE_FIXED) and isinstance(dst, _CASTABLE_FIXED):
        return None
    if isinstance(dst, T.StringType):
        if isinstance(src, (T.BooleanType, T.DateType, T.TimestampType)) \
                or src.is_integral:
            return None
        return f"cast {src!r} -> string not supported on device"
    if isinstance(src, T.StringType):
        if dst.is_integral or isinstance(dst, (T.Float32Type, T.Float64Type,
                                               T.DateType, T.TimestampType)):
            return None
        return f"cast string -> {dst!r} not supported on device"
    if isinstance(src, T.NullType):
        return None
    return f"cast {src!r} -> {dst!r} not supported on device"


expr_rule(E.Cast, Sigs.COMMON, Sigs.COMMON, "cast", extra=_cast_check)

# strings
expr_rule(S.StringLength, Sigs.COMMON, Sigs.COMMON, "character length")
expr_rule(S.Upper, Sigs.COMMON, Sigs.COMMON, "uppercase (ASCII)")
expr_rule(S.Lower, Sigs.COMMON, Sigs.COMMON, "lowercase (ASCII)")
expr_rule(S.Substring, Sigs.COMMON, Sigs.COMMON, "substring")
expr_rule(S.ConcatStrings, Sigs.COMMON, Sigs.COMMON, "string concat")
expr_rule(S.StartsWith, Sigs.COMMON, Sigs.COMMON, "prefix match")
expr_rule(S.EndsWith, Sigs.COMMON, Sigs.COMMON, "suffix match")
expr_rule(S.Contains, Sigs.COMMON, Sigs.COMMON, "substring match")


def _like_check(e):
    if not e.supported_on_tpu():
        return (f"LIKE pattern {e.pattern!r} does not transpile to device "
                f"kernels (reference RegexParser reject strategy)")
    return None


expr_rule(S.Like, Sigs.COMMON, Sigs.COMMON, "SQL LIKE", extra=_like_check)
expr_rule(S._StringEquals, Sigs.COMMON, Sigs.COMMON, "string equality")
expr_rule(S._LiteralMatch, Sigs.COMMON, Sigs.COMMON, "literal runs match")


def _rlike_check(e):
    if not e.supported_on_tpu():
        return (f"regex {e.pattern!r} outside the device NFA subset: "
                f"{e._nfa_err} (reference RegexParser reject strategy)")
    return None


expr_rule(S.RLike, Sigs.COMMON, Sigs.COMMON,
          "Java regex match (bit-parallel device NFA)", extra=_rlike_check)
def _extract_check(e):
    if not e.supported_on_tpu():
        return (f"regexp_extract pattern {e.pattern!r} outside the tagged "
                f"device NFA subset: {e._nfa_err} (reference RegexParser "
                f"reject strategy)")
    return None


expr_rule(S.RegexpExtract, Sigs.COMMON, Sigs.COMMON,
          "regex capture extract (tagged device NFA; rejects fall back)",
          extra=_extract_check)
def _replace_check(e):
    if not e.supported_on_tpu():
        return (f"regexp_replace pattern {e.pattern!r} outside the device "
                f"replace subset: {e._nfa_err} (reference RegexParser "
                f"reject strategy)")
    return None


expr_rule(S.RegexpReplace, Sigs.COMMON, Sigs.COMMON,
          "regex replace-all (tagged device NFA span scan + byte "
          "splice; backrefs and rejects fall back)",
          extra=_replace_check)

# complex types (reference complexTypeExtractors.scala / complexTypeCreator /
# collectionOperations / GpuGenerateExec expressions)
from spark_rapids_tpu.expr import complex as CX  # noqa: E402

_NESTED_OK = Sigs.COMMON.nested()

# column refs / aliases / null tests pass nested columns through untouched —
# re-register them with the nested signature (reference: these are
# TypeSig.all in GpuOverrides)
expr_rule(E.BoundRef, _NESTED_OK, _NESTED_OK, "column reference")
expr_rule(E.Alias, _NESTED_OK, _NESTED_OK, "named expression")
expr_rule(E.IsNull, _NESTED_OK, Sigs.COMMON, "null test")
expr_rule(E.IsNotNull, _NESTED_OK, Sigs.COMMON, "not-null test")


def _primitive_elements_only(what: str):
    def check(e: E.Expression) -> Optional[str]:
        dt = e.children[0].data_type()
        inner = dt.element if isinstance(dt, T.ArrayType) else dt.key
        if isinstance(inner, (T.ArrayType, T.StructType, T.MapType)):
            return f"{what} over nested element types runs on CPU"
        return None
    return check


def _create_array_check(e: E.Expression) -> Optional[str]:
    dt = e.data_type().element
    if isinstance(dt, (T.StringType, T.ArrayType, T.StructType, T.MapType,
                       T.NullType)):
        return "array() of non-fixed-width elements runs on CPU"
    return None


expr_rule(CX.Size, _NESTED_OK, Sigs.COMMON, "size(array|map)")
expr_rule(CX.GetArrayItem, _NESTED_OK, _NESTED_OK, "array[ordinal]")
expr_rule(CX.ElementAt, _NESTED_OK, _NESTED_OK, "element_at(array|map, k)",
          extra=lambda e: (_primitive_elements_only("map key lookup")(e)
                           if isinstance(e.children[0].data_type(), T.MapType)
                           else None))
expr_rule(CX.GetMapValue, _NESTED_OK, _NESTED_OK, "map[key]",
          extra=_primitive_elements_only("map key lookup"))
expr_rule(CX.GetStructField, _NESTED_OK, _NESTED_OK, "struct field access")
expr_rule(CX.ArrayContains, _NESTED_OK, Sigs.COMMON, "array_contains",
          extra=_primitive_elements_only("array_contains"))
expr_rule(CX.CreateArray, Sigs.COMMON, _NESTED_OK, "array(...)",
          extra=_create_array_check)
expr_rule(CX.MapKeys, _NESTED_OK, _NESTED_OK, "map_keys")
expr_rule(CX.MapValues, _NESTED_OK, _NESTED_OK, "map_values")

# JSON functions (reference GpuGetJsonObject / GpuJsonToStructs): host
# parse tier with visible fallback
from spark_rapids_tpu.expr import json_functions as JF  # noqa: E402

for _jcls in JF.JSON_FUNCTIONS:
    expr_rule(_jcls, Sigs.COMMON, _NESTED_OK,
              f"{_jcls.name} (host JSON parse)",
              extra=lambda e: f"{e.name} runs on CPU (host JSON parse)")

# misc expressions (reference GpuRandomExpressions / ParseURI / hive hash)
from spark_rapids_tpu.expr import misc as MX  # noqa: E402

expr_rule(MX.Rand, Sigs.COMMON, Sigs.COMMON,
          "rand([seed]) — splitmix64 stream (distribution-equivalent to "
          "Spark's XORShift, stream differs; documented)")
expr_rule(MX.HiveHash, Sigs.COMMON, Sigs.COMMON, "hive hash")

for _mcls in MX.MISC_CPU_FUNCTIONS:
    expr_rule(_mcls, Sigs.COMMON, _NESTED_OK,
              f"{_mcls.name} (CPU tier)",
              extra=lambda e: f"{e.name} runs on CPU (no device kernel yet)")

# CPU-only row functions: registered so tagging gives a clear reason and
# the enclosing exec falls back (reference: ops without GPU impls)
from spark_rapids_tpu.expr import cpu_functions as CF  # noqa: E402

for _cls in CF.ALL_CPU_FUNCTIONS:
    expr_rule(_cls, Sigs.COMMON, Sigs.COMMON,
              f"{_cls.name} (CPU; no device kernel yet)",
              extra=lambda e: f"{e.name} runs on CPU (no device kernel yet)")

# UDFs (reference RapidsUDF SPI / row-based UDF bridge / udf-compiler)
from spark_rapids_tpu.sql import udf as UDF  # noqa: E402

expr_rule(UDF.PythonRowUDF, Sigs.COMMON, Sigs.COMMON,
          "opaque python row UDF (CPU)",
          extra=lambda e: f"python UDF {e.name!r} runs on CPU "
                          f"(use jax_udf for device execution)")
expr_rule(UDF.JaxColumnarUDF, Sigs.COMMON, Sigs.COMMON,
          "columnar jax UDF (fuses into the device stage)")

# math
for _cls in (MA.Sqrt, MA.Exp, MA.Log, MA.Log10, MA.Log2, MA.Sin, MA.Cos,
             MA.Tan, MA.Asin, MA.Acos, MA.Atan, MA.Sinh, MA.Cosh, MA.Tanh,
             MA.Ceil, MA.Floor, MA.Pow, MA.Round, MA.Signum, MA.Atan2,
             MA.Greatest, MA.Least):
    expr_rule(_cls, _NUM, _NUM, _cls.__name__.lower())

# datetime
for _cls in (DT.Year, DT.Month, DT.DayOfMonth, DT.Hour, DT.Minute, DT.Second,
             DT.DayOfWeek, DT.DateAdd, DT.DateSub, DT.DateDiff, DT.LastDay,
             DT.Quarter, DT.DayOfYear, DT.WeekOfYear, DT.AddMonths,
             DT.UnixTimestampFromTs, DT.TimestampSeconds):
    expr_rule(_cls, _NUMDT, _NUMDT, _cls.__name__.lower())


def _trunc_check(e):
    if not e.supported_on_tpu():
        return f"trunc format {e.fmt!r} not supported on device"
    return None


expr_rule(DT.TruncDate, _NUMDT, _NUMDT, "trunc(date, fmt)", extra=_trunc_check)

# bitwise / shifts / hash
for _cls in (MA.BitwiseAnd, MA.BitwiseOr, MA.BitwiseXor, MA.BitwiseNot,
             MA.ShiftLeft, MA.ShiftRight, MA.ShiftRightUnsigned):
    expr_rule(_cls, _NUM, _NUM, _cls.__name__.lower())
expr_rule(MA.Murmur3Hash, Sigs.COMMON, Sigs.COMMON,
          "Spark murmur3 hash (seed 42), bit-parity with CPU Spark")

# string breadth
for _cls in (S.Trim, S.LTrim, S.RTrim, S.InitCap, S.Ascii, S.InStr,
             S.StringRepeat):
    expr_rule(_cls, Sigs.COMMON, Sigs.COMMON, _cls.__name__.lower())


expr_rule(DT.FromUtcTimestamp, Sigs.COMMON, Sigs.COMMON,
          "from_utc_timestamp (IANA transition table on device)",
          extra=lambda e: None if e.supported_on_tpu()
          else f"unknown timezone {e.zone!r}")
expr_rule(DT.ToUtcTimestamp, Sigs.COMMON, Sigs.COMMON,
          "to_utc_timestamp (IANA transition table on device)",
          extra=lambda e: None if e.supported_on_tpu()
          else f"unknown timezone {e.zone!r}")


# higher-order functions (lambdas over arrays/maps) — hof.py
from spark_rapids_tpu.expr import hof as H  # noqa: E402

_ARR = Sigs.COMMON.nested()
expr_rule(H.LambdaVar, Sigs.COMMON, Sigs.COMMON, "lambda parameter")
expr_rule(H.ArrayTransform, _ARR, _ARR, "transform(array, lambda)")
expr_rule(H.ArrayFilter, _ARR, _ARR, "filter(array, lambda)")
expr_rule(H.ArrayExists, _ARR, Sigs.COMMON, "exists(array, lambda)")
expr_rule(H.ArrayForAll, _ARR, Sigs.COMMON, "forall(array, lambda)")
expr_rule(H.TransformKeys, _ARR, _ARR, "transform_keys(map, lambda)")
expr_rule(H.TransformValues, _ARR, _ARR, "transform_values(map, lambda)")
expr_rule(H.MapFilter, _ARR, _ARR, "map_filter(map, lambda)")
expr_rule(H.ZipWith, _ARR, _ARR, "zip_with(a, b, lambda)")
expr_rule(H.ArrayAggregate, _ARR, Sigs.COMMON,
          "aggregate(array, zero, merge[, finish]) — CPU fold",
          extra=lambda e: "aggregate() sequential lambda fold runs on CPU")


# array collection operations — array_ops.py
from spark_rapids_tpu.expr import array_ops as AO  # noqa: E402

expr_rule(AO.ArrayMin, _ARR, Sigs.COMMON, "array_min",
          extra=lambda e: None if e.supported_on_tpu()
          else "array_min over string/nested elements runs on CPU")
expr_rule(AO.ArrayMax, _ARR, Sigs.COMMON, "array_max",
          extra=lambda e: None if e.supported_on_tpu()
          else "array_max over string/nested elements runs on CPU")
expr_rule(AO.ArrayPosition, _ARR, Sigs.COMMON, "array_position")
expr_rule(AO.ArrayRemove, _ARR, _ARR, "array_remove")
expr_rule(AO.Slice, _ARR, _ARR, "slice")
expr_rule(AO.SortArray, _ARR, _ARR, "sort_array",
          extra=lambda e: None if e.supported_on_tpu()
          else "sort_array over string/nested elements runs on CPU")
expr_rule(AO.Flatten, _ARR, _ARR, "flatten")
expr_rule(AO.ArrayDistinct, _ARR, _ARR,
          "array_distinct (string elements dedup by 64-bit hash)")
expr_rule(AO.ArrayUnion, _ARR, _ARR, "array_union")
expr_rule(AO.ArrayIntersect, _ARR, _ARR, "array_intersect")
expr_rule(AO.ArrayExcept, _ARR, _ARR, "array_except")
expr_rule(AO.ArraysOverlap, _ARR, Sigs.COMMON, "arrays_overlap")


# math/string/datetime/collection breadth second tier
from spark_rapids_tpu.expr import cpu_functions as _CPUF  # noqa: E402
from spark_rapids_tpu.expr import misc as _MISC  # noqa: E402

for _cls in (MA.Cbrt, MA.Cot, MA.Sec, MA.Csc, MA.ToDegrees, MA.ToRadians,
             MA.Expm1, MA.Log1p, MA.Rint, MA.Hypot, MA.NaNvl):
    expr_rule(_cls, _NUM, _NUM, _cls.__name__.lower())
expr_rule(MA.Factorial, _NUM, _NUM, "factorial (null outside [0, 20])")
expr_rule(MA.BitwiseCount, _NUM, _NUM, "bit_count")
expr_rule(MA.BitwiseGet, _NUM, _NUM, "getbit")
expr_rule(MA.BRound, _NUM, _NUM, "bround (HALF_EVEN)")

expr_rule(DT.MakeDate, Sigs.COMMON, Sigs.COMMON, "make_date")
expr_rule(DT.NextDay, Sigs.COMMON, Sigs.COMMON, "next_day")
expr_rule(DT.MonthsBetween, Sigs.COMMON, Sigs.COMMON, "months_between")
for _cls in (DT.UnixDate, DT.DateFromUnixDate, DT.UnixMicros,
             DT.UnixMillis, DT.UnixSeconds, DT.TimestampMillis,
             DT.TimestampMicros):
    expr_rule(_cls, Sigs.COMMON, Sigs.COMMON, _cls.__name__.lower())

for _cls in (S.OctetLength, S.BitLength, S.Left, S.Right, S.Chr):
    expr_rule(_cls, Sigs.COMMON, Sigs.COMMON, _cls.__name__.lower())

def _cpu_tier(doc):
    return lambda e: doc

expr_rule(_CPUF.FindInSet, Sigs.COMMON, Sigs.COMMON, "find_in_set",
          extra=_cpu_tier("find_in_set runs on CPU"))
expr_rule(_CPUF.Levenshtein, Sigs.COMMON, Sigs.COMMON, "levenshtein",
          extra=_cpu_tier("levenshtein runs on CPU"))
expr_rule(_CPUF.Base64Encode, Sigs.COMMON, Sigs.COMMON, "base64",
          extra=_cpu_tier("base64 runs on CPU"))
expr_rule(_CPUF.UnBase64, Sigs.COMMON, Sigs.COMMON, "unbase64",
          extra=_cpu_tier("unbase64 runs on CPU"))
expr_rule(_CPUF.FormatString, Sigs.COMMON, Sigs.COMMON, "format_string",
          extra=_cpu_tier("format_string runs on CPU"))
expr_rule(_CPUF.Elt, Sigs.COMMON, Sigs.COMMON, "elt",
          extra=_cpu_tier("elt runs on CPU"))
expr_rule(_CPUF.Soundex, Sigs.COMMON, Sigs.COMMON, "soundex",
          extra=_cpu_tier("soundex runs on CPU"))
expr_rule(_CPUF.JsonTuple, _ARR, _ARR, "json_tuple",
          extra=_cpu_tier("json_tuple runs on CPU"))

for _c, _doc in ((_CPUF.Sha1, "sha1"), (_CPUF.HexStr, "hex"),
                 (_CPUF.Unhex, "unhex"), (_CPUF.Bin, "bin"),
                 (_CPUF.Conv, "conv"), (_CPUF.UrlEncode, "url_encode"),
                 (_CPUF.UrlDecode, "url_decode")):
    expr_rule(_c, Sigs.COMMON, Sigs.COMMON, _doc,
              extra=_cpu_tier(f"{_doc} runs on CPU"))
expr_rule(MA.Logarithm, Sigs.COMMON, Sigs.COMMON, "log(base, expr)")
expr_rule(MA.WidthBucket, Sigs.COMMON, Sigs.COMMON, "width_bucket")
expr_rule(_CPUF.Luhncheck, Sigs.COMMON, Sigs.COMMON, "luhn_check",
          extra=_cpu_tier("luhn_check runs on CPU"))
expr_rule(CX.Stack, Sigs.COMMON, Sigs.COMMON,
          "stack(n, ...) (lowered to a union of projections)")
for _cls in (MA.Acosh, MA.Asinh, MA.Atanh, MA.Pmod, MA.UnaryPositive,
             DT.WeekDay, DT.TruncTimestamp):
    expr_rule(_cls, Sigs.COMMON, Sigs.COMMON, _cls.__name__.lower())
expr_rule(_CPUF.RegexpExtractAll, _ARR, _ARR, "regexp_extract_all",
          extra=_cpu_tier("regexp_extract_all runs on CPU"))
expr_rule(_CPUF.StructsToJson, _ARR, _ARR, "to_json",
          extra=_cpu_tier("to_json runs on CPU"))

for _cls in (E.KnownNotNull, E.KnownFloatingPointNormalized,
             E.NormalizeNaNAndZero, E.AtLeastNNonNulls):
    expr_rule(_cls, Sigs.COMMON, Sigs.COMMON, _cls.__name__)

expr_rule(_MISC.Crc32, Sigs.COMMON, Sigs.COMMON, "crc32")
expr_rule(_MISC.XxHash64, Sigs.COMMON, Sigs.COMMON,
          "xxhash64 (Spark-compatible, seed 42)",
          extra=lambda e: None if e.supported_on_tpu()
          else "xxhash64 over string/nested columns runs on CPU")

expr_rule(AO.ArrayRepeat, _ARR, _ARR, "array_repeat",
          extra=_cpu_tier("array_repeat runs on CPU"))
expr_rule(AO.ArrayJoin, _ARR, Sigs.COMMON, "array_join",
          extra=_cpu_tier("array_join runs on CPU"))
expr_rule(AO.ArraysZip, _ARR, _ARR, "arrays_zip",
          extra=_cpu_tier("arrays_zip runs on CPU"))
expr_rule(AO.MapEntries, _ARR, _ARR, "map_entries")
expr_rule(AO.MapConcat, _ARR, _ARR, "map_concat",
          extra=_cpu_tier("map_concat runs on CPU"))
expr_rule(AO.MapFromArrays, _ARR, _ARR, "map_from_arrays",
          extra=_cpu_tier("map_from_arrays runs on CPU"))
expr_rule(AO.StrToMap, Sigs.COMMON, _ARR, "str_to_map",
          extra=_cpu_tier("str_to_map runs on CPU"))


# Aggregate function rules
AGG_RULES: Dict[Type, ExprRule] = {}


def agg_rule(cls, input_sig=_NUMDT, doc="", extra=None):
    AGG_RULES[cls] = ExprRule(cls.__name__, input_sig, Sigs.COMMON, doc, extra)


def _no_string_input(fn) -> Optional[str]:
    for c in fn.children:
        if isinstance(c.data_type(), T.StringType):
            return f"{type(fn).__name__} over strings not supported on device"
    return None


agg_rule(A.Sum, _NUM, "sum")
agg_rule(A.Count, Sigs.COMMON, "count non-null")
agg_rule(A.CountAll, Sigs.COMMON, "count(*)")
agg_rule(A.Min, _NUMDT, "min", extra=_no_string_input)
agg_rule(A.Max, _NUMDT, "max", extra=_no_string_input)
agg_rule(A.Average, _NUM, "avg")
agg_rule(A.First, _NUMDT, "first", extra=_no_string_input)
agg_rule(A.Last, _NUMDT, "last", extra=_no_string_input)
agg_rule(A.StddevSamp, _NUM, "stddev_samp")
agg_rule(A.StddevPop, _NUM, "stddev_pop")
agg_rule(A.VarianceSamp, _NUM, "var_samp")
agg_rule(A.VariancePop, _NUM, "var_pop")


def _primitive_input_only(what: str):
    def check(fn) -> Optional[str]:
        for c in fn.children:
            if isinstance(c.data_type(), (T.ArrayType, T.StructType,
                                          T.MapType)):
                return f"{what} over nested inputs runs on CPU"
        return None
    return check


agg_rule(A.CollectList, Sigs.COMMON, "collect_list",
         extra=_primitive_input_only("collect_list"))
agg_rule(A.CollectSet, Sigs.COMMON, "collect_set",
         extra=_primitive_input_only("collect_set"))
def _minmax_by_check(what: str):
    def check(fn) -> Optional[str]:
        r = _primitive_input_only(what)(fn)
        if r:
            return r
        if isinstance(fn.children[1].data_type(), T.StringType):
            # device ordering key for strings is an equality hash, not
            # order-faithful — string ordering columns run on CPU
            return f"{what} ordered by a string column runs on CPU"
        return None
    return check


agg_rule(A.MinBy, Sigs.COMMON, "min_by", extra=_minmax_by_check("min_by"))
agg_rule(A.MaxBy, Sigs.COMMON, "max_by", extra=_minmax_by_check("max_by"))
agg_rule(A.Percentile, _NUM, "percentile (exact)")
agg_rule(A.ApproxPercentile, _NUM,
         "approx_percentile (computed exactly on this engine)")


# ---------------------------------------------------------------------------
# Expression tagging
# ---------------------------------------------------------------------------

#: expressions whose evaluation needs the partition context that only the
#: projection kernel threads (reference ExprChecks contexts,
#: RapidsMeta.scala:945-971 — project vs groupby vs window contexts)
PROJECT_ONLY_EXPRS = (E.SparkPartitionID, E.MonotonicallyIncreasingID,
                      MX.Rand)


def _contains_project_only(e: E.Expression) -> bool:
    if isinstance(e, PROJECT_ONLY_EXPRS):
        return True
    return any(_contains_project_only(c) for c in e.children)


_UTC_NAMES = ("UTC", "Etc/UTC", "GMT", "Etc/GMT", "Z", "+00:00")

#: Expressions whose result depends on the session timezone when any input
#: (or output) is a TIMESTAMP. Date-typed inputs are timezone-free.
_TZ_SENSITIVE = ()


def _register_tz_sensitive():
    global _TZ_SENSITIVE
    from spark_rapids_tpu.expr import cpu_functions as CPUF
    _TZ_SENSITIVE = (
        DT.Year, DT.Month, DT.DayOfMonth, DT.Hour, DT.Minute, DT.Second,
        DT.DayOfWeek, DT.LastDay, DT.Quarter, DT.DayOfYear, DT.WeekOfYear,
        DT.AddMonths, DT.TruncDate, DT.UnixTimestampFromTs,
        CPUF.DateFormat, CPUF.ToDateFmt, CPUF.FromUnixtime,
    )


def _check_session_timezone(e: E.Expression, conf, where: str) -> None:
    """Reference discipline (GpuOverrides nonUTC tagging): a non-UTC session
    timezone must never silently produce UTC answers. Zones resolvable
    from the IANA database are handled by the localize_session_tz plan
    rewrite (expressions arriving here are already shifted); anything else
    (unknown zone string) is refused outright — our CPU interpreter is
    also UTC-only, so unlike the reference there is nothing to fall back
    to."""
    tz = conf.get(C.SESSION_TIMEZONE)
    if tz in _UTC_NAMES:
        return
    from spark_rapids_tpu.expr import tzdb
    if tzdb.is_valid_zone(tz):
        return  # localize_session_tz already rewrote the plan
    if not _TZ_SENSITIVE:
        _register_tz_sensitive()
    if not isinstance(e, _TZ_SENSITIVE):
        return
    types = [e.data_type()] + [c.data_type() for c in e.children]
    from spark_rapids_tpu.expr import cpu_functions as CPUF
    always = isinstance(e, (DT.Hour, DT.Minute, DT.Second,
                            CPUF.FromUnixtime, CPUF.ToDateFmt))
    if always or any(isinstance(t, T.TimestampType) for t in types):
        raise E.SparkException(
            f"{where}: {type(e).__name__} with spark.sql.session.timeZone="
            f"{tz!r} is not supported (this engine evaluates timestamps in "
            f"UTC only); set the session timezone to UTC")


def _localize_node_fn(tz: str):
    """Per-node rewrite for timezone localization — suitable for ONE
    bottom-up transform() application over an expression tree. (Applying
    the whole-tree localize_expr at every node would re-wrap already
    localized children and shift timestamps twice.)"""
    if not _TZ_SENSITIVE:
        _register_tz_sensitive()
    from spark_rapids_tpu.expr import cpu_functions as CPUF
    from spark_rapids_tpu.expr.core import Cast

    def is_ts(x):
        try:
            return isinstance(x.data_type(), T.TimestampType)
        except Exception:  # noqa: BLE001 - unresolved stays untouched
            return False

    def wrap_ts_children(node):
        kids = [DT.FromUtcTimestamp(c, tz) if is_ts(c) else c
                for c in node.children]
        return node.with_children(kids)

    def f(node):
        if isinstance(node, _TZ_SENSITIVE) and not isinstance(
                node, DT.UnixTimestampFromTs):
            # field extraction / formatting of a ts happens in local time
            if any(is_ts(c) for c in node.children):
                return wrap_ts_children(node)
            if isinstance(node, CPUF.FromUnixtime):
                # seconds -> formatted local string: shift via ts domain
                sec = node.children[0]
                shifted = DT.UnixTimestampFromTs(
                    DT.FromUtcTimestamp(DT.TimestampSeconds(sec), tz))
                return node.with_children([shifted] + node.children[1:])
            return node
        if isinstance(node, Cast):
            src = None
            try:
                src = node.children[0].data_type()
            except Exception:  # noqa: BLE001
                return node
            dst = node.to
            if isinstance(src, T.TimestampType) and isinstance(
                    dst, (T.DateType, T.StringType)):
                return node.with_children(
                    [DT.FromUtcTimestamp(node.children[0], tz)])
            if isinstance(dst, T.TimestampType) and isinstance(
                    src, (T.DateType, T.StringType)):
                return DT.ToUtcTimestamp(node, tz)
        return node

    return f


def localize_expr(e: E.Expression, tz: str) -> E.Expression:
    """Rewrite a timezone-sensitive expression for a non-UTC session by
    shifting TIMESTAMP operands through the zone's transition table
    (reference: the GpuTimeZoneDB rewrite inside each datetime kernel;
    here it is ONE plan-level rule so every extraction/format expression
    stays a plain UTC kernel). Spark timestamps are instants; the session
    timezone affects field extraction, formatting/parsing, and
    date<->timestamp casts — exactly the places wrapped here."""
    return e.transform(_localize_node_fn(tz))


def localize_plan(plan, conf):
    """Apply localize_expr to every expression in the plan when the
    session timezone is a resolvable non-UTC zone."""
    tz = conf.get(C.SESSION_TIMEZONE)
    if tz in _UTC_NAMES:
        return plan
    from spark_rapids_tpu.expr import tzdb
    if not tzdb.is_valid_zone(tz):
        return plan  # tagging will refuse tz-sensitive expressions
    from spark_rapids_tpu.plan import nodes as P

    node_f = _localize_node_fn(tz)

    def fix(e):
        return e.transform(node_f)

    def walk(n):
        for c in n.children:
            walk(c)
        if isinstance(n, P.Project):
            n.exprs = [fix(e) for e in n.exprs]
        elif isinstance(n, P.Filter):
            n.condition = fix(n.condition)
        elif isinstance(n, P.Aggregate):
            n.group_exprs = [fix(e) for e in n.group_exprs]
            # transform() visits every node once bottom-up; pass the
            # NODE function (the tree-level fix would double-wrap)
            n.aggs = [a.transform(node_f) for a in n.aggs]
        elif isinstance(n, P.Generate):
            n.generator = fix(n.generator)
        elif isinstance(n, P.Expand):
            n.projections = [[fix(e) for e in row]
                             for row in n.projections]
        elif isinstance(n, P.Join):
            n.left_keys = [fix(e) for e in n.left_keys]
            n.right_keys = [fix(e) for e in n.right_keys]
            if n.condition is not None:
                n.condition = fix(n.condition)
        elif isinstance(n, P.Sort):
            for o in n.orders:
                o.expr = fix(o.expr)
        elif isinstance(n, P.WindowNode):
            for we in n.window_exprs:
                we.spec.partition_exprs = [fix(e)
                                           for e in we.spec.partition_exprs]
                for o in we.spec.order_specs:
                    o.expr = fix(o.expr)
                we.fn = fix(we.fn)

    walk(plan)
    return plan


def tag_expression(e: E.Expression, conf, reasons: List[str], where: str) -> None:
    cls = type(e)
    _check_session_timezone(e, conf, where)
    rule = EXPR_RULES.get(cls)
    if rule is None:
        reasons.append(f"{where}: expression {cls.__name__} is not supported on TPU")
        return
    if where != "Project" and isinstance(e, PROJECT_ONLY_EXPRS):
        reasons.append(
            f"{where}: {rule.name} only evaluates in projection context "
            f"(partition id / row base are threaded by ProjectExec)")
    key = f"spark.rapids.sql.expression.{rule.name}"
    if not conf.is_op_enabled(key):
        reasons.append(f"{where}: expression {rule.name} disabled by {key}")
    try:
        dt = e.data_type()
        r = rule.result_sig.reason_not_supported(dt)
        if r:
            reasons.append(f"{where}: {rule.name} output {r}")
    except Exception as ex:  # unresolved
        reasons.append(f"{where}: cannot resolve {rule.name}: {ex}")
        return
    for ch in e.children:
        try:
            cdt = ch.data_type()
            r = rule.input_sig.reason_not_supported(cdt)
            if r:
                reasons.append(f"{where}: {rule.name} input {r}")
        except Exception:  # noqa: BLE001 - unresolvable child type: the
            pass           # recursive tag below records its own reason
    if rule.extra is not None:
        r = rule.extra(e)
        if r:
            reasons.append(f"{where}: {r}")
    for ch in e.children:
        tag_expression(ch, conf, reasons, where)


def tag_agg(fn: A.AggFunction, conf, reasons: List[str], where: str) -> None:
    rule = AGG_RULES.get(type(fn))
    if rule is None:
        reasons.append(f"{where}: aggregate {type(fn).__name__} is not supported on TPU")
        return
    if not conf.get(C.IMPROVED_FLOAT_OPS) and isinstance(
            fn, (A.Sum, A.Average, A.VarianceSamp, A.VariancePop,
                 A.StddevSamp, A.StddevPop)):
        for ch in fn.children:
            if isinstance(ch.data_type(), (T.Float32Type, T.Float64Type)):
                reasons.append(
                    f"{where}: float {rule.name} accumulates in a "
                    f"different order than CPU Spark (ULP-level diffs) — "
                    f"disabled by spark.rapids.sql.improvedFloatOps."
                    f"enabled=false")
    if isinstance(fn, A.CollectSet) and not conf.get(C.INCOMPAT_ENABLED):
        for ch in fn.children:
            if isinstance(ch.data_type(), T.StringType):
                reasons.append(
                    f"{where}: collect_set over strings dedups by 64-bit "
                    f"double-hash on device — disabled by spark.rapids."
                    f"sql.incompatibleOps.enabled=false")
    if rule.extra is not None:
        r = rule.extra(fn)
        if r:
            reasons.append(f"{where}: {r}")
    for ch in fn.children:
        tag_expression(ch, conf, reasons, where)
        r = rule.input_sig.reason_not_supported(ch.data_type())
        if r:
            reasons.append(f"{where}: {rule.name} input {r}")


def _measured_collapse() -> bool:
    """True when the measured cost pass (plan/cost.py measured_hints)
    prescribed collapsing group-key aggregate exchanges to one partition
    for the plan currently converting on this thread — the history said
    the shuffle group was dispatch_overhead-bound."""
    from spark_rapids_tpu.plan import cost as COST
    h = COST.current_hints()
    return h is not None and h.exchange_parts == 1


# ---------------------------------------------------------------------------
# Plan metas
# ---------------------------------------------------------------------------

class SparkPlanMeta:
    """Wrapper with tagging + conversion (reference RapidsMeta:83 /
    SparkPlanMeta:598)."""

    def __init__(self, plan: P.PlanNode, conf, parent: Optional["SparkPlanMeta"] = None):
        self.plan = plan
        self.conf = conf
        self.parent = parent
        self.children = [SparkPlanMeta(c, conf, self) for c in plan.children]
        self.reasons: List[str] = []
        self._tagged = False

    # -- tagging -----------------------------------------------------------
    def tag_for_tpu(self) -> None:
        if self._tagged:
            return
        self._tagged = True
        for c in self.children:
            c.tag_for_tpu()
        name = type(self.plan).__name__
        key = f"spark.rapids.sql.exec.{name}"
        if not self.conf.is_op_enabled(key):
            self.reasons.append(f"{name} disabled by {key}")
        if not self.conf.get(C.SQL_ENABLED):
            self.reasons.append("spark.rapids.sql.enabled is false")
        self._tag_schema()
        self._tag_node()

    #: nodes whose device paths carry nested columns (mask/gather/concat
    #: only — no key normalization): scans, projection, filter, generate,
    #: limit, union, sort payload, cache. Joins/aggregates/exchanges/windows
    #: stay primitive-only until nested key normalization lands.
    NESTED_SCHEMA_NODES = (P.Project, P.Filter, P.Generate, P.InMemorySource,
                           P.ParquetScan, P.TextScan, P.Limit, P.Union,
                           P.Sort, P.CachedRelation, P.ShuffleFileScan,
                           P.Aggregate)

    def _tag_schema(self) -> None:
        sig = (Sigs.COMMON.nested()
               if isinstance(self.plan, self.NESTED_SCHEMA_NODES)
               else Sigs.COMMON)
        for f in self.plan.schema.fields:
            r = sig.reason_not_supported(f.dtype)
            if r:
                self.reasons.append(f"output column {f.name}: {r}")

    def _tag_node(self) -> None:
        p = self.plan
        name = type(p).__name__
        if isinstance(p, P.Project):
            for e in p.exprs:
                tag_expression(e, self.conf, self.reasons, name)
        elif isinstance(p, P.Filter):
            tag_expression(p.condition, self.conf, self.reasons, name)
        elif isinstance(p, P.Aggregate):
            for e in p.group_exprs:
                tag_expression(e, self.conf, self.reasons, name)
                if isinstance(e.data_type(), (T.ArrayType, T.StructType,
                                              T.MapType)):
                    self.reasons.append(
                        f"{name}: grouping by nested type "
                        f"{e.data_type()!r} has no device key normalization")
            for a in p.aggs:
                tag_agg(a.fn, self.conf, self.reasons, name)
        elif isinstance(p, P.Sort):
            # string ORDER BY runs on device via exact 8-byte chunk keys
            # (kernels.string_chunk_keys)
            for o in p.orders:
                tag_expression(o.expr, self.conf, self.reasons, name)
                odt = o.expr.data_type()
                if isinstance(odt, (T.ArrayType, T.StructType, T.MapType)):
                    self.reasons.append(
                        f"{name}: ORDER BY on nested type {odt!r} has no "
                        f"device key normalization (runs on CPU)")
        elif isinstance(p, P.Join):
            for e in p.left_keys + p.right_keys:
                tag_expression(e, self.conf, self.reasons, name)
                if isinstance(e.data_type(), T.StringType) \
                        and not self.conf.get(C.INCOMPAT_ENABLED):
                    self.reasons.append(
                        f"{name}: string join keys compare by 64-bit "
                        f"double-hash on device (collision odds ~2^-64) — "
                        f"disabled by spark.rapids.sql.incompatibleOps."
                        f"enabled=false")
            if p.condition is not None:
                tag_expression(p.condition, self.conf, self.reasons, name)
        elif isinstance(p, P.Repartition):
            for e in p.keys:
                tag_expression(e, self.conf, self.reasons, name)
        elif isinstance(p, P.Expand):
            for proj in p.projections:
                for e in proj:
                    tag_expression(e, self.conf, self.reasons, name)
        elif isinstance(p, P.Generate):
            tag_expression(p.generator.children[0], self.conf, self.reasons,
                           name)
            # the exec row-duplicates required child columns; a duplicating
            # gather of list-like columns would overflow their element
            # planes (kernels._gather_list_like preserves capacity) — fall
            # back. Structs of primitives duplicate fine (row planes only).
            def _has_list_like(dt):
                if isinstance(dt, (T.ArrayType, T.MapType)):
                    return True
                if isinstance(dt, T.StructType):
                    return any(_has_list_like(f.dtype) for f in dt.fields)
                return False
            for i in p.required:
                f = p.children[0].schema.fields[i]
                if _has_list_like(f.dtype):
                    self.reasons.append(
                        f"{name}: carrying array/map column {f.name} through "
                        f"explode needs a sized nested gather (runs on CPU)")
        elif isinstance(p, P.WindowNode):
            self._tag_window(p, name)

    def _tag_window(self, p, name) -> None:
        from spark_rapids_tpu.expr import window as WE
        from spark_rapids_tpu.expr import aggregates as A
        for w in p.window_exprs:
            spec = w.spec
            for e in spec.partition_exprs:
                tag_expression(e, self.conf, self.reasons, name)
            for o in spec.order_specs:
                tag_expression(o.expr, self.conf, self.reasons, name)
                if isinstance(o.expr.data_type(), T.StringType):
                    self.reasons.append(
                        f"{name}: window ORDER BY on strings needs host sort")
            for c in w.fn.children:
                tag_expression(c, self.conf, self.reasons, name)
                if isinstance(c.data_type(), T.StringType):
                    self.reasons.append(
                        f"{name}: string-typed window operands run on CPU "
                        f"(device window kernels are fixed-width planes)")
            fn = w.fn
            if isinstance(fn, (WE.NthValue, WE.FirstValue, WE.LastValue)):
                frame = spec.resolved_frame()
                if frame.lower is not None or frame.upper not in (0, None):
                    self.reasons.append(
                        f"{name}: {type(fn).__name__} supports only "
                        f"unbounded-preceding frames ending at the current "
                        f"row or partition end")
            if isinstance(fn, (WE.RowNumber, WE.Rank, WE.DenseRank, WE.NTile,
                               WE.LeadLag, WE.PercentRank, WE.CumeDist,
                               WE.NthValue, WE.FirstValue, WE.LastValue)):
                pass  # needs_order enforced at plan build (AnalysisException)
            elif isinstance(fn, WE.WindowAgg):
                frame = spec.resolved_frame()
                ok = (A.Sum, A.Count, A.CountAll, A.Min, A.Max, A.Average)
                if not isinstance(fn.fn, ok):
                    self.reasons.append(
                        f"{name}: {type(fn.fn).__name__} not supported in "
                        f"window frames on device")
                bounded_rows = (frame.kind == "rows"
                                and not (frame.lower is None and frame.upper in (0, None)))
                if bounded_rows and isinstance(fn.fn, (A.Min, A.Max)):
                    self.reasons.append(
                        f"{name}: bounded-rows min/max window not yet on "
                        f"device (needs a sliding-extrema kernel)")
            else:
                self.reasons.append(
                    f"{name}: window function {type(fn).__name__} "
                    f"not supported")

    @property
    def can_run_on_tpu(self) -> bool:
        return not self.reasons

    # -- conversion --------------------------------------------------------
    def convert(self):
        from spark_rapids_tpu.exec import tpu_nodes as X
        child_execs = [c.convert() for c in self.children]
        p = self.plan
        conf = self.conf
        if not self.can_run_on_tpu:
            return X.CpuFallbackExec(p, child_execs, conf)
        if isinstance(p, P.InMemorySource):
            return X.InMemoryScanExec(p, [], conf)
        if isinstance(p, P.ParquetScan):
            if conf.get(C.DEVICE_DECODE_ENABLED):
                # device-side decode (cuDF GPU-reader analog): the source
                # coalesces row groups itself up to the reader batch size
                # (no CoalesceBatchesExec — encoded batches are not
                # concatenable, and don't need to be), and the decode
                # exec's stage body fuses with downstream Filter/agg.
                return X.DeviceDecodeScanExec(
                    p, [X.EncodedParquetSourceExec(p, [], conf)], conf)
            # insertCoalesce analog (GpuTransitionOverrides.scala): file
            # scans emit one batch per row group / file split; coalesce to
            # the target size so downstream fused stages see few big
            # batches instead of many small dispatches.
            return X.CoalesceBatchesExec(p, [X.ParquetScanExec(p, [], conf)],
                                         conf)
        if isinstance(p, P.TextScan):
            return X.CoalesceBatchesExec(p, [X.TextScanExec(p, [], conf)],
                                         conf)
        if isinstance(p, P.CachedRelation):
            return X.CachedScanExec(p, child_execs, conf)
        if isinstance(p, P.ShuffleFileScan):
            return X.ShuffleFileScanExec(p, [], conf)
        if isinstance(p, P.Range):
            return X.RangeExec(p, [], conf)
        if isinstance(p, P.Project):
            return X.ProjectExec(p, child_execs, conf)
        if isinstance(p, P.Filter):
            return X.FilterExec(p, child_execs, conf)
        if isinstance(p, P.Limit):
            se = child_execs[0]
            # ORDER BY + LIMIT n -> TopN (reference GpuTopN): threshold
            # selection beats sorting the whole partition; replaces the
            # SortExec (and its range exchange — global order is
            # irrelevant under a global limit) with per-partition TopN +
            # collect + final TopN.
            if isinstance(se, X.SortExec) and p.n <= 100_000:
                inner = se.children[0]
                if isinstance(inner, (X.RangeExchangeExec,
                                      X.CollectExchangeExec)):
                    inner = inner.children[0]
                local = X.TopNExec(p, [inner], conf, se.plan.orders, p.n)
                if inner.num_partitions > 1:
                    coll = X.CollectExchangeExec(p, [local], conf)
                    return X.TopNExec(p, [coll], conf, se.plan.orders, p.n)
                return local
            local = X.LimitExec(p, child_execs, conf)
            if child_execs[0].num_partitions > 1:
                coll = X.CollectExchangeExec(p, [local], conf)
                return X.LimitExec(p, [coll], conf)
            return local
        if isinstance(p, P.Union):
            return X.UnionExec(p, child_execs, conf)
        if isinstance(p, P.Repartition):
            if p.keys:
                return X.ShuffleExchangeExec(p, child_execs, conf, p.keys,
                                             n_out=p.n_out)
            return X.RoundRobinExchangeExec(p, child_execs, conf,
                                            n_out=p.n_out)
        if isinstance(p, P.Expand):
            return X.ExpandExec(p, child_execs, conf)
        if isinstance(p, P.Generate):
            return X.GenerateExec(p, child_execs, conf)
        if isinstance(p, P.Sort):
            child = child_execs[0]
            if child.num_partitions > 1 and p.global_sort:
                # range partition + per-partition sort = global order with
                # no single-partition collapse (GpuRangePartitioner); keys
                # whose device normalization is not order-preserving
                # (strings hash; nested have none) still collect
                rangeable = all(
                    not isinstance(o.expr.data_type(),
                                   (T.StringType, T.ArrayType, T.StructType,
                                    T.MapType))
                    for o in p.orders)
                if rangeable:
                    child = X.RangeExchangeExec(p, [child], conf, p.orders,
                                                n_out=child.num_partitions)
                else:
                    child = X.CollectExchangeExec(p, [child], conf)
            return X.SortExec(p, [child], conf)
        if isinstance(p, P.WindowNode):
            child = child_execs[0]
            if child.num_partitions > 1:
                spec = p.window_exprs[0].spec
                if spec.partition_exprs:
                    child = X.ShuffleExchangeExec(
                        p, [child], conf, spec.partition_exprs,
                        n_out=child.num_partitions)
                else:
                    child = X.CollectExchangeExec(p, [child], conf)
            return X.WindowExec(p, [child], conf)
        if isinstance(p, P.Aggregate):
            return self._convert_aggregate(p, child_execs, conf)
        if isinstance(p, P.Join):
            return self._convert_join(p, child_execs, conf)
        raise NotImplementedError(f"no TPU conversion for {type(p).__name__}")

    def _convert_aggregate(self, p, child_execs, conf):
        from spark_rapids_tpu.exec import tpu_nodes as X
        child = child_execs[0]
        pre_filter = None
        if isinstance(child, X.FilterExec):
            # predicate fusion: the filter disappears into the agg's update
            # kernel (one dispatch for scan-filter-partial-agg)
            pre_filter = child.plan.condition
            child = child.children[0]
        if isinstance(child, X.ExpandExec) and pre_filter is None \
                and child.children[0].num_partitions == 1:
            # GROUP BY ROLLUP: the levels are prefixes of one key list,
            # so one sort serves them all (exec/rollup.py); a batch it
            # cannot take runs through the pair it stands for
            from spark_rapids_tpu.exec.rollup import (RollupAggregateExec,
                                                      rollup_shape)
            shape = rollup_shape(p, child.plan)
            if shape is not None:
                return RollupAggregateExec(p, [child.children[0]], conf,
                                           child.plan, shape)
        if child.num_partitions == 1:
            return X.HashAggregateExec(p, [child], conf, mode="complete",
                                       pre_filter=pre_filter)
        if any(getattr(a.fn, "no_partial", False) for a in p.aggs):
            # custom segmented aggs (collect_*, min_by, percentile) have no
            # mergeable partial state: exchange RAW rows by group key, then
            # aggregate each partition completely (reference: these aggs
            # carry whole-collection buffers between stages; shuffling rows
            # first is the TPU-shaped equivalent)
            if p.group_exprs and not _measured_collapse():
                exch = X.ShuffleExchangeExec(p, [child], conf, p.group_exprs,
                                             n_out=child.num_partitions)
            else:
                exch = X.CollectExchangeExec(p, [child], conf)
            return X.HashAggregateExec(p, [exch], conf, mode="complete",
                                       pre_filter=pre_filter)
        nkeys = len(p.group_exprs)
        import jax as _jax
        single_device = len(_jax.devices()) == 1 \
            and conf.get(C.SHUFFLE_MODE).upper() != "ICI"
        if single_device:
            est = p.children[0].estimated_rows()
            if est is not None and est <= 64_000_000:
                # all partitions share one device and the raw input fits
                # comfortably: one complete pass over the collected input
                # beats partial-per-partition + exchange + final merge
                # (each extra stage costs dispatches and a ~90ms sync)
                coll = X.CollectExchangeExec(p, [child], conf)
                coal = X.CoalesceBatchesExec(p, [coll], conf)
                return X.HashAggregateExec(p, [coal], conf, mode="complete",
                                           pre_filter=pre_filter)
        partial = X.HashAggregateExec(p, [child], conf, mode="partial",
                                      pre_filter=pre_filter)
        if nkeys and not single_device and not _measured_collapse():
            keys = [E.BoundRef(i, e.data_type(), n) for i, (e, n) in
                    enumerate(zip(p.group_exprs, p.group_names))]
            # its sole consumer is the final aggregate below, which asks
            # only that a key's rows share a partition: the one exchange
            # that may pass a few host-resident rows on unexchanged
            exch = X.ShuffleExchangeExec(p, [partial], conf, keys,
                                         n_out=child.num_partitions,
                                         may_bypass=True)
        else:
            # one device: a hash exchange between partial and final states
            # only re-slices arrays that already live together — collect
            # and merge once instead (the single-process analog of AQE's
            # shuffle elimination; multi-chip ICI keeps the real exchange)
            exch = X.CollectExchangeExec(p, [partial], conf)
        return X.HashAggregateExec(p, [exch], conf, mode="final")

    def _convert_join(self, p, child_execs, conf):
        from spark_rapids_tpu.exec import tpu_nodes as X
        left, right = child_execs
        if p.how == "cross":
            return X.CartesianProductExec(p, [left, right], conf)
        if not p.left_keys:
            # non-equi join: broadcast nested loop
            # (GpuBroadcastNestedLoopJoinExecBase)
            if p.how in ("right", "full") and left.num_partitions > 1:
                left = X.CollectExchangeExec(p, [left], conf)
            return X.BroadcastNestedLoopJoinExec(p, [left, right], conf)
        # strategy: broadcast the (right) build side when it is estimated
        # small, else hash-exchange both sides and join per partition
        est = p.children[1].estimated_rows()
        small = est is not None and est <= conf.get(C.BROADCAST_JOIN_ROW_THRESHOLD)
        multi = left.num_partitions > 1
        if multi and est is None and conf.get(C.ADAPTIVE_ENABLED) \
                and p.how not in ("right", "full"):
            # unknown build size: defer broadcast-vs-shuffle to RUNTIME on
            # the measured count (AQE analog)
            lkeys, rkeys = [], []
            for lk, rk in zip(p.left_keys, p.right_keys):
                ct = T.common_type(lk.data_type(), rk.data_type())
                lkeys.append(lk if lk.data_type() == ct else E.Cast(lk, ct))
                rkeys.append(rk if rk.data_type() == ct else E.Cast(rk, ct))
            return X.AdaptiveJoinExec(p, [left, right], conf,
                                      part_keys=(lkeys, rkeys))
        if multi and not small:
            # Hash-partitioning must agree ACROSS sides: Spark murmur3 is
            # width-sensitive (int32 vs int64 hash differently), so keys
            # cast to the common type before the exchange hash.
            lkeys, rkeys = [], []
            for lk, rk in zip(p.left_keys, p.right_keys):
                ct = T.common_type(lk.data_type(), rk.data_type())
                lkeys.append(lk if lk.data_type() == ct else E.Cast(lk, ct))
                rkeys.append(rk if rk.data_type() == ct else E.Cast(rk, ct))
            n_out = left.num_partitions
            if conf.get(C.ADAPTIVE_ENABLED) \
                    and conf.get(C.ADAPTIVE_BROADCAST_BYTES) > 0:
                # planned-as-shuffled, measured at runtime: the build
                # side's exchange materializes first and a small MEASURED
                # result demotes to broadcast before the probe exchange
                # ever dispatches (exec/adaptive.py)
                from spark_rapids_tpu.exec.adaptive import (
                    AdaptiveShuffledHashJoinExec,
                )
                return AdaptiveShuffledHashJoinExec(
                    p, [left, right], conf, part_keys=(lkeys, rkeys))
            left = X.ShuffleExchangeExec(p, [left], conf, lkeys, n_out)
            right = X.ShuffleExchangeExec(p, [right], conf, rkeys, n_out)
            return X.ShuffledHashJoinExec(p, [left, right], conf,
                                          part_keys=(lkeys, rkeys))
        if p.how in ("right", "full") and multi:
            left = X.CollectExchangeExec(p, [left], conf)
        return X.BroadcastHashJoinExec(p, [left, right], conf)

    # -- explain -----------------------------------------------------------
    def explain(self, indent: int = 0, all_ops: bool = False) -> str:
        pad = "  " * indent
        mark = "*" if self.can_run_on_tpu else "!"
        lines = []
        if all_ops or not self.can_run_on_tpu:
            lines.append(f"{pad}{mark} {self.plan.describe()}")
            for r in self.reasons:
                lines.append(f"{pad}    @ cannot run on TPU because: {r}")
        else:
            lines.append(f"{pad}* {self.plan.describe()} [TPU]")
        for c in self.children:
            lines.append(c.explain(indent + 1, all_ops))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Entry points (reference GpuOverrides.apply / ExplainPlan)
# ---------------------------------------------------------------------------

_PUSHABLE_LEAVES = (E.BoundRef, E.Literal)


def _as_pushed(e: E.Expression) -> Optional[E.Expression]:
    """Copy a conjunct into the pushdown-supported shape (comparisons,
    In, IsNull/IsNotNull, And/Or over column refs + literals). None = not
    pushable. Projection renames are applied separately by _rename_refs
    as the pushdown walk descends."""
    if isinstance(e, E.BoundRef):
        return E.BoundRef(e.index, e.data_type(), e.name)
    if isinstance(e, E.Literal):
        return e
    if isinstance(e, E.Not):
        # only null-test negations have a sound pruning rewrite (negating
        # an interval comparison is unsound under three-valued logic)
        c = e.children[0]
        if isinstance(c, E.IsNull):
            return _as_pushed(E.IsNotNull(c.children[0]))
        if isinstance(c, E.IsNotNull):
            return _as_pushed(E.IsNull(c.children[0]))
        return None
    if isinstance(e, (E.And, E.Or, E.EqualTo, E.LessThan, E.LessThanOrEqual,
                      E.GreaterThan, E.GreaterThanOrEqual, E.In,
                      E.IsNull, E.IsNotNull)):
        kids = [_as_pushed(c) for c in e.children]
        if any(k is None for k in kids):
            return None
        return e.with_children(kids)
    return None


def _rename_refs(e: E.Expression, nmap: Dict[str, str]) -> Optional[E.Expression]:
    """Rewrite column refs through a projection's output->input name map;
    None when any ref does not map (computed column)."""
    if isinstance(e, E.BoundRef):
        t = nmap.get(e.name)
        if t is None:
            return None
        return E.BoundRef(e.index, e.data_type(), t)
    if not e.children:
        return e
    kids = [_rename_refs(c, nmap) for c in e.children]
    if any(k is None for k in kids):
        return None
    return e.with_children(kids)


def push_down_scan_filters(plan: P.PlanNode) -> None:
    """Populate ParquetScan.pushed_filters from enclosing Filter nodes
    (reference: ParquetFilters / GpuParquetScan pushedFilters). Filters
    stay in the plan — pruning is a conservative row-group/file skip, the
    exact predicate still runs on device.

    Per-PATH collection: conjuncts accumulate walking top-down through
    Filter/Project chains; a scan object reachable from several branches
    of one plan (union/self-join of differently-filtered views over one
    DataFrame) gets the OR of the branch conjunctions — conjoining them
    would statically refute row groups each branch still needs. A branch
    reaching the scan with no predicate disables pruning entirely.
    Idempotent: pushed lists are reassigned, not extended."""
    from functools import reduce
    from spark_rapids_tpu.io.parquet_pruning import split_conjuncts

    arrivals: Dict[int, List[List[E.Expression]]] = {}
    scans: Dict[int, P.ParquetScan] = {}

    def walk(node: P.PlanNode, conjs: List[E.Expression]) -> None:
        if isinstance(node, P.Filter):
            add = []
            for conj in split_conjuncts(node.condition):
                p = _as_pushed(conj)
                if p is not None:
                    add.append(p)
            walk(node.children[0], conjs + add)
            return
        if isinstance(node, P.Project):
            nmap: Dict[str, str] = {}
            for name, ex in zip(node.names, node.exprs):
                inner = ex.children[0] if isinstance(ex, E.Alias) else ex
                if isinstance(inner, E.BoundRef):
                    nmap[name] = inner.name
            renamed = []
            for c in conjs:
                r = _rename_refs(c, nmap)
                if r is not None:
                    renamed.append(r)
            walk(node.children[0], renamed)
            return
        if isinstance(node, P.ParquetScan):
            arrivals.setdefault(id(node), []).append(conjs)
            scans[id(node)] = node
            return
        for c in node.children:
            walk(c, [])

    walk(plan, [])
    for sid, paths in arrivals.items():
        scan = scans[sid]
        if any(not p for p in paths):
            scan.pushed_filters = []
        elif len(paths) == 1:
            scan.pushed_filters = list(paths[0])
        else:
            ands = [reduce(E.And, p) for p in paths]
            scan.pushed_filters = [reduce(E.Or, ands)]


def wrap_and_tag(plan: P.PlanNode, conf) -> SparkPlanMeta:
    push_down_scan_filters(plan)
    meta = SparkPlanMeta(plan, conf)
    meta.tag_for_tpu()
    return meta


def convert_plan(plan: P.PlanNode, conf):
    """Returns (root_exec, meta). In explainOnly mode no device is required
    by conversion since nothing executes until iteration."""
    from spark_rapids_tpu.plan.prune import prune_plan
    plan = localize_plan(plan, conf)
    plan = prune_plan(plan)
    meta = wrap_and_tag(plan, conf)
    from spark_rapids_tpu.plan.cost import apply_cost_optimizer
    apply_cost_optimizer(meta, conf)
    exec_root = meta.convert()
    # whole-stage vertical fusion: collapse linear chains of narrow execs
    # into one dispatch per batch (spark.rapids.sql.stageFusion.enabled)
    from spark_rapids_tpu.exec.stage_fusion import fuse_stages
    exec_root = fuse_stages(exec_root, conf)
    # multichip sharding: eligible fused stages re-dispatch as ONE SPMD
    # program per batch-wave over the mesh (spark.rapids.sql.multichip.
    # enabled; ineligible stages record their fallback reason)
    from spark_rapids_tpu.exec.sharded import shard_stages
    exec_root = shard_stages(exec_root, conf)
    # pipelined execution: bounded producer/consumer boundaries at
    # scan->compute edges so host decode/upload of batch i+1 overlaps
    # device compute of batch i (spark.rapids.sql.pipeline.enabled)
    from spark_rapids_tpu.runtime.pipeline import insert_pipelines
    exec_root = insert_pipelines(exec_root, conf)
    # plan-invariant verifier (spark.rapids.debug.planVerify.enabled):
    # schema/fusion/pipeline legality of the FINAL tree, after every
    # rewrite pass — a malformed plan must fail here, not on the device
    if conf.get(C.PLAN_VERIFY_ENABLED):
        from spark_rapids_tpu.analysis.plan_verify import verify_plan
        verify_plan(exec_root)
    lore_dir = conf.get(C.LORE_DUMP_DIR)
    if lore_dir:
        from spark_rapids_tpu.runtime.lore import LoreDumper
        LoreDumper(lore_dir).install(exec_root)
    if conf.get(C.TEST_MODE):
        allowed = {s.strip() for s in
                   str(conf.get(C.ALLOW_NON_TPU) or "").split(",") if s.strip()}
        _assert_on_tpu(meta, allowed)
    return exec_root, meta


def _assert_on_tpu(meta: SparkPlanMeta, allowed: set) -> None:
    name = type(meta.plan).__name__
    if not meta.can_run_on_tpu and name not in allowed:
        raise AssertionError(
            f"{name} fell back to CPU in test mode: {meta.reasons}")
    for c in meta.children:
        _assert_on_tpu(c, allowed)


def explain_plan(plan: P.PlanNode, conf, all_ops: bool = False) -> str:
    meta = wrap_and_tag(plan, conf)
    from spark_rapids_tpu.plan.cost import apply_cost_optimizer
    apply_cost_optimizer(meta, conf)  # explain must show cost reversions
    return meta.explain(all_ops=all_ops)
