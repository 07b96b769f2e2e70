"""User-facing expression builders, mirroring pyspark.sql.functions for the
subset the engine implements (reference sql-plugin-api functions.scala df_udf
style surface)."""
from __future__ import annotations

from spark_rapids_tpu.expr import core as E
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import datetime as DT
from spark_rapids_tpu.expr import math as MA
from spark_rapids_tpu.expr import strings as S

col = E.col
lit = E.lit


def _e(x):
    return x if isinstance(x, E.Expression) else (E.col(x) if isinstance(x, str) else E.lit(x))


# aggregates -----------------------------------------------------------------
def sum(c):  # noqa: A001
    return A.Sum(_e(c))


def grouping(c):
    """1 when the key is aggregated away in a ROLLUP/CUBE output row."""
    return A.Grouping(_e(c))


def grouping_id():
    """The grouping-set bitmask over the group-by keys."""
    return A.GroupingID()


def count(c="*"):
    # NB: Expression.__eq__ builds an EqualTo node (truthy), so the
    # "*" probe must be an isinstance check — `c == "*"` on a column
    # silently turned every count(expr) into count(*)
    if isinstance(c, str) and c == "*":
        return A.CountAll()
    return A.Count(_e(c))


def avg(c):
    return A.Average(_e(c))


mean = avg


def count_distinct(c):
    """count(DISTINCT c): planned as Spark's one-distinct aggregation."""
    return A.distinct_of(A.Count(_e(c)))


def sum_distinct(c):
    """sum(DISTINCT c)."""
    return A.distinct_of(A.Sum(_e(c)))


countDistinct, sumDistinct = count_distinct, sum_distinct


def min(c):  # noqa: A001
    return A.Min(_e(c))


def max(c):  # noqa: A001
    return A.Max(_e(c))


def first(c):
    return A.First(_e(c))


def last(c):
    return A.Last(_e(c))


def collect_list(c):
    return A.CollectList(_e(c))


def collect_set(c):
    return A.CollectSet(_e(c))


def min_by(c, ord_c):
    return A.MinBy(_e(c), _e(ord_c))


def max_by(c, ord_c):
    return A.MaxBy(_e(c), _e(ord_c))


def percentile(c, p: float):
    return A.Percentile(_e(c), p)


def approx_percentile(c, p: float, accuracy: int = 10000):
    return A.ApproxPercentile(_e(c), p, accuracy)


percentile_approx = approx_percentile


def stddev(c):
    return A.StddevSamp(_e(c))


stddev_samp = stddev


def stddev_pop(c):
    return A.StddevPop(_e(c))


def variance(c):
    return A.VarianceSamp(_e(c))


var_samp = variance


def var_pop(c):
    return A.VariancePop(_e(c))


# scalar ---------------------------------------------------------------------
def row_number():
    from spark_rapids_tpu.expr.window import RowNumber
    return RowNumber()


def rank():
    from spark_rapids_tpu.expr.window import Rank
    return Rank()


def dense_rank():
    from spark_rapids_tpu.expr.window import DenseRank
    return DenseRank()


def ntile(n: int):
    from spark_rapids_tpu.expr.window import NTile
    return NTile(n)


def percent_rank():
    from spark_rapids_tpu.expr.window import PercentRank
    return PercentRank()


def cume_dist():
    from spark_rapids_tpu.expr.window import CumeDist
    return CumeDist()


def nth_value(c, n: int):
    from spark_rapids_tpu.expr.window import NthValue
    return NthValue(_e(c), n)


def first_value(c):
    from spark_rapids_tpu.expr.window import FirstValue
    return FirstValue(_e(c))


def last_value(c):
    from spark_rapids_tpu.expr.window import LastValue
    return LastValue(_e(c))


def lead(c, offset: int = 1, default=None):
    from spark_rapids_tpu.expr.window import Lead
    return Lead(_e(c), offset, default)


def lag(c, offset: int = 1, default=None):
    from spark_rapids_tpu.expr.window import Lag
    return Lag(_e(c), offset, default)


def trim(c):
    return S.Trim(_e(c))


def ltrim(c):
    return S.LTrim(_e(c))


def rtrim(c):
    return S.RTrim(_e(c))


def initcap(c):
    return S.InitCap(_e(c))


def ascii(c):  # noqa: A001
    return S.Ascii(_e(c))


def instr(c, substr: str):
    return S.InStr(_e(c), substr)


def locate(substr: str, c):
    return S.InStr(_e(c), substr)


def repeat(c, n: int):
    return S.StringRepeat(_e(c), n)


def quarter(c):
    return DT.Quarter(_e(c))


def dayofyear(c):
    return DT.DayOfYear(_e(c))


def weekofyear(c):
    return DT.WeekOfYear(_e(c))


def add_months(c, n):
    return DT.AddMonths(_e(c), _e(n))


def trunc(c, fmt: str):
    return DT.TruncDate(_e(c), fmt)


def unix_timestamp(c):
    return DT.UnixTimestampFromTs(_e(c))


def timestamp_seconds(c):
    return DT.TimestampSeconds(_e(c))


def bitwise_not(c):
    return MA.BitwiseNot(_e(c))


def shiftleft(c, n):
    return MA.ShiftLeft(_e(c), _e(n))


def shiftright(c, n):
    return MA.ShiftRight(_e(c), _e(n))


def shiftrightunsigned(c, n):
    return MA.ShiftRightUnsigned(_e(c), _e(n))


def rand(seed: int = 0):
    from spark_rapids_tpu.expr.misc import Rand
    return Rand(seed)


def sequence(start, stop, step=None):
    from spark_rapids_tpu.expr.misc import Sequence
    args = [_e(start), _e(stop)] + ([_e(step)] if step is not None else [])
    return Sequence(*args)


def parse_url(c, part: str, key: str = None):
    from spark_rapids_tpu.expr.misc import ParseUrl
    params = (part,) if key is None else (part, key)
    return ParseUrl(_e(c), params=params)


def raise_error(c):
    from spark_rapids_tpu.expr.misc import RaiseError
    return RaiseError(_e(c))


def hive_hash(*cs):
    from spark_rapids_tpu.expr.misc import HiveHash
    return HiveHash([_e(c) for c in cs])


def hash(*cs):  # noqa: A001
    return MA.Murmur3Hash(*[_e(c) for c in cs])


def spark_partition_id():
    return E.SparkPartitionID()


def monotonically_increasing_id():
    return E.MonotonicallyIncreasingID()


def reverse(c):
    from spark_rapids_tpu.expr.cpu_functions import Reverse
    return Reverse(_e(c))


def concat_ws(sep, *cs):
    from spark_rapids_tpu.expr.cpu_functions import ConcatWs
    return ConcatWs(*[_e(c) for c in cs], params=(sep,))


def lpad(c, ln, pad=" "):
    from spark_rapids_tpu.expr.cpu_functions import LPad
    return LPad(_e(c), params=(ln, pad))


def rpad(c, ln, pad=" "):
    from spark_rapids_tpu.expr.cpu_functions import RPad
    return RPad(_e(c), params=(ln, pad))


def translate(c, src, dst):
    from spark_rapids_tpu.expr.cpu_functions import Translate
    return Translate(_e(c), params=(src, dst))


def substring_index(c, delim, count):
    from spark_rapids_tpu.expr.cpu_functions import SubstringIndex
    return SubstringIndex(_e(c), params=(delim, count))


def md5(c):
    from spark_rapids_tpu.expr.cpu_functions import Md5
    return Md5(_e(c))


def sha2(c, bits=256):
    from spark_rapids_tpu.expr.cpu_functions import Sha2
    return Sha2(_e(c), params=(bits,))


def date_format(c, fmt):
    from spark_rapids_tpu.expr.cpu_functions import DateFormat
    return DateFormat(_e(c), params=(fmt,))


def to_date(c, fmt="yyyy-MM-dd"):
    from spark_rapids_tpu.expr.cpu_functions import ToDateFmt
    return ToDateFmt(_e(c), params=(fmt,))


def from_unixtime(c, fmt="yyyy-MM-dd HH:mm:ss"):
    from spark_rapids_tpu.expr.cpu_functions import FromUnixtime
    return FromUnixtime(_e(c), params=(fmt,))


def format_number(c, d):
    from spark_rapids_tpu.expr.cpu_functions import FormatNumber
    return FormatNumber(_e(c), params=(d,))


def explode(c):
    from spark_rapids_tpu.expr import complex as CX
    return CX.Explode(_e(c))


def explode_outer(c):
    from spark_rapids_tpu.expr import complex as CX
    return CX.ExplodeOuter(_e(c))


def posexplode(c):
    from spark_rapids_tpu.expr import complex as CX
    return CX.PosExplode(_e(c))


def posexplode_outer(c):
    from spark_rapids_tpu.expr import complex as CX
    return CX.PosExplodeOuter(_e(c))


def size(c):  # noqa: A001
    from spark_rapids_tpu.expr import complex as CX
    return CX.Size(_e(c))


def element_at(c, k):
    from spark_rapids_tpu.expr import complex as CX
    return CX.ElementAt(_e(c), _e(k) if isinstance(k, E.Expression) else E.lit(k))


def array(*cs):
    from spark_rapids_tpu.expr import complex as CX
    return CX.CreateArray([_e(c) for c in cs])


def array_contains(c, v):
    from spark_rapids_tpu.expr import complex as CX
    return CX.ArrayContains(_e(c), _e(v) if isinstance(v, E.Expression) else E.lit(v))


def map_keys(c):
    from spark_rapids_tpu.expr import complex as CX
    return CX.MapKeys(_e(c))


def map_values(c):
    from spark_rapids_tpu.expr import complex as CX
    return CX.MapValues(_e(c))


def get_json_object(c, path: str):
    from spark_rapids_tpu.expr import json_functions as JF
    return JF.GetJsonObject(_e(c), params=(path,))


def from_json(c, schema):
    from spark_rapids_tpu.expr import json_functions as JF
    return JF.JsonToStructs(_e(c), params=(schema,))


def nvl(c, default):
    return coalesce(c, default)


def nullif(a, b):
    from spark_rapids_tpu.expr.core import EqualTo, If, NullOf
    ea, eb = _e(a), _e(b)
    return If(EqualTo(ea, eb), NullOf(ea), ea)


def rlike(c, pattern: str):
    from spark_rapids_tpu.expr.strings import RLike
    return RLike(_e(c), pattern)


def regexp_extract(c, pattern: str, group: int = 1):
    from spark_rapids_tpu.expr.strings import RegexpExtract
    return RegexpExtract(_e(c), pattern, group)


def regexp_replace(c, pattern: str, replacement: str):
    from spark_rapids_tpu.expr.strings import RegexpReplace
    return RegexpReplace(_e(c), pattern, replacement)


def coalesce(*cs):
    return E.Coalesce(*[_e(c) for c in cs])


def when(cond, value):
    return _WhenBuilder([(cond, _e(value))])


class _WhenBuilder(E.Expression):
    def __init__(self, branches):
        self._branches = branches
        self.children = []

    def when(self, cond, value):
        return _WhenBuilder(self._branches + [(cond, _e(value))])

    def otherwise(self, value):
        return E.CaseWhen(self._branches, _e(value))

    def _as_case(self):
        return E.CaseWhen(self._branches)

    def data_type(self):
        return self._as_case().data_type()

    def transform(self, fn):
        return E.CaseWhen([(p.transform(fn), v.transform(fn))
                           for p, v in self._branches]).transform(fn)

    def eval_tpu(self, ctx):
        return self._as_case().eval_tpu(ctx)

    def eval_cpu(self, cols, ansi=False):
        return self._as_case().eval_cpu(cols, ansi)

    def fingerprint(self):
        return self._as_case().fingerprint()


def isnull(c):
    return E.IsNull(_e(c))


def isnan(c):
    return E.IsNaN(_e(c))


def abs(c):  # noqa: A001
    return E.Abs(_e(c))


def sqrt(c):
    return MA.Sqrt(_e(c))


def exp(c):
    return MA.Exp(_e(c))


def log(arg1, arg2=None):
    """log(col) is the natural log; log(base, col) is Logarithm."""
    if arg2 is None:
        return MA.Log(_e(arg1))
    return MA.Logarithm(_e(arg1), _e(arg2))


def log10(c):
    return MA.Log10(_e(c))


def log2(c):
    return MA.Log2(_e(c))


def sin(c):
    return MA.Sin(_e(c))


def cos(c):
    return MA.Cos(_e(c))


def tan(c):
    return MA.Tan(_e(c))


def ceil(c):
    return MA.Ceil(_e(c))


def floor(c):
    return MA.Floor(_e(c))


def pow(a, b):  # noqa: A001
    return MA.Pow(_e(a), _e(b))


def round(c, scale=0):  # noqa: A001
    return MA.Round(_e(c), scale)


def signum(c):
    return MA.Signum(_e(c))


def atan2(a, b):
    return MA.Atan2(_e(a), _e(b))


def greatest(*cs):
    return MA.Greatest(*[_e(c) for c in cs])


def least(*cs):
    return MA.Least(*[_e(c) for c in cs])


# strings --------------------------------------------------------------------
def length(c):
    return S.StringLength(_e(c))


def upper(c):
    return S.Upper(_e(c))


def lower(c):
    return S.Lower(_e(c))


def substring(c, pos, length_):
    return S.Substring(_e(c), pos, length_)


def concat(*cs):
    return S.ConcatStrings(*[_e(c) for c in cs])


def startswith(c, prefix):
    return S.StartsWith(_e(c), prefix)


def endswith(c, suffix):
    return S.EndsWith(_e(c), suffix)


def contains(c, s):
    return S.Contains(_e(c), s)


def like(c, pattern):
    return S.Like(_e(c), pattern)


# datetime -------------------------------------------------------------------
def year(c):
    return DT.Year(_e(c))


def month(c):
    return DT.Month(_e(c))


def dayofmonth(c):
    return DT.DayOfMonth(_e(c))


def hour(c):
    return DT.Hour(_e(c))


def minute(c):
    return DT.Minute(_e(c))


def second(c):
    return DT.Second(_e(c))


def dayofweek(c):
    return DT.DayOfWeek(_e(c))


def date_add(c, n):
    return DT.DateAdd(_e(c), _e(n))


def date_sub(c, n):
    return DT.DateSub(_e(c), _e(n))


def datediff(end, start):
    return DT.DateDiff(_e(end), _e(start))


def last_day(c):
    return DT.LastDay(_e(c))


# ---------------------------------------------------------------------------
# Higher-order functions (lambda expressions over arrays/maps)
# Reference: sql-plugin higherOrderFunctions.scala
# ---------------------------------------------------------------------------

def _lambda(fn, n_args, names):
    from spark_rapids_tpu.expr import hof as H
    from spark_rapids_tpu import types as T
    import inspect
    try:
        arity = len(inspect.signature(fn).parameters)
    except (TypeError, ValueError):
        arity = n_args
    import builtins
    arity = builtins.min(builtins.max(arity, 1), n_args)
    return H.make_lambda(fn, [T.NULL] * arity, names[:arity])


def transform(c, fn):
    """transform(array, x -> expr) or transform(array, (x, i) -> expr)."""
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(fn, 2, ["x", "i"])
    return H.ArrayTransform(_e(c), body, vs)


def filter(c, fn):  # noqa: A001 - Spark's F.filter
    """filter(array, x -> bool) / filter(array, (x, i) -> bool)."""
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(fn, 2, ["x", "i"])
    return H.ArrayFilter(_e(c), body, vs)


def exists(c, fn):
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(fn, 1, ["x"])
    return H.ArrayExists(_e(c), body, vs)


def forall(c, fn):
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(fn, 1, ["x"])
    return H.ArrayForAll(_e(c), body, vs)


def aggregate(c, zero, merge, finish=None):
    """aggregate(array, zero, (acc, x) -> new_acc[, acc -> out])."""
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(merge, 2, ["acc", "x"])
    fb = fvs = None
    if finish is not None:
        fb, fvs = _lambda(finish, 1, ["acc"])
    return H.ArrayAggregate(_e(c), _e(zero), body, vs, fb, fvs)


reduce = aggregate  # Spark 3.4+ alias


def zip_with(a, b, fn):
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(fn, 2, ["x", "y"])
    return H.ZipWith(_e(a), _e(b), body, vs)


def transform_keys(c, fn):
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(fn, 2, ["k", "v"])
    return H.TransformKeys(_e(c), body, vs)


def transform_values(c, fn):
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(fn, 2, ["k", "v"])
    return H.TransformValues(_e(c), body, vs)


def map_filter(c, fn):
    from spark_rapids_tpu.expr import hof as H
    body, vs = _lambda(fn, 2, ["k", "v"])
    return H.MapFilter(_e(c), body, vs)


# ---------------------------------------------------------------------------
# Array collection operations (reference collectionOperations.scala)
# ---------------------------------------------------------------------------

def array_min(c):
    from spark_rapids_tpu.expr.array_ops import ArrayMin
    return ArrayMin(_e(c))


def array_max(c):
    from spark_rapids_tpu.expr.array_ops import ArrayMax
    return ArrayMax(_e(c))


def array_position(c, v):
    from spark_rapids_tpu.expr.array_ops import ArrayPosition
    return ArrayPosition(_e(c), _e(v))


def array_remove(c, v):
    from spark_rapids_tpu.expr.array_ops import ArrayRemove
    return ArrayRemove(_e(c), _e(v))


def slice(c, start, length):  # noqa: A001 - Spark's F.slice
    from spark_rapids_tpu.expr.array_ops import Slice
    return Slice(_e(c), _e(start), _e(length))


def sort_array(c, asc=True):
    from spark_rapids_tpu.expr.array_ops import SortArray
    return SortArray(_e(c), asc)


def flatten(c):
    from spark_rapids_tpu.expr.array_ops import Flatten
    return Flatten(_e(c))


def array_distinct(c):
    from spark_rapids_tpu.expr.array_ops import ArrayDistinct
    return ArrayDistinct(_e(c))


def array_union(a, b):
    from spark_rapids_tpu.expr.array_ops import ArrayUnion
    return ArrayUnion(_e(a), _e(b))


def array_intersect(a, b):
    from spark_rapids_tpu.expr.array_ops import ArrayIntersect
    return ArrayIntersect(_e(a), _e(b))


def array_except(a, b):
    from spark_rapids_tpu.expr.array_ops import ArrayExcept
    return ArrayExcept(_e(a), _e(b))


def arrays_overlap(a, b):
    from spark_rapids_tpu.expr.array_ops import ArraysOverlap
    return ArraysOverlap(_e(a), _e(b))


def from_utc_timestamp(ts, tz):
    from spark_rapids_tpu.expr.datetime import FromUtcTimestamp
    from spark_rapids_tpu.expr.core import Literal
    z = tz.value if isinstance(tz, Literal) else tz
    return FromUtcTimestamp(_e(ts), z)


def to_utc_timestamp(ts, tz):
    from spark_rapids_tpu.expr.datetime import ToUtcTimestamp
    from spark_rapids_tpu.expr.core import Literal
    z = tz.value if isinstance(tz, Literal) else tz
    return ToUtcTimestamp(_e(ts), z)


# ---------------------------------------------------------------------------
# Math / string / datetime / collection breadth second tier
# ---------------------------------------------------------------------------

def _math1(name):
    def f(c):
        from spark_rapids_tpu.expr import math as MA
        return getattr(MA, name)(_e(c))
    f.__name__ = name.lower()
    return f


cbrt = _math1("Cbrt")
cot = _math1("Cot")
sec = _math1("Sec")
csc = _math1("Csc")
degrees = _math1("ToDegrees")
radians = _math1("ToRadians")
expm1 = _math1("Expm1")
log1p = _math1("Log1p")
rint = _math1("Rint")
factorial = _math1("Factorial")
bit_count = _math1("BitwiseCount")


def hypot(a, b):
    from spark_rapids_tpu.expr.math import Hypot
    return Hypot(_e(a), _e(b))


def nanvl(a, b):
    from spark_rapids_tpu.expr.math import NaNvl
    return NaNvl(_e(a), _e(b))


def getbit(c, pos):
    from spark_rapids_tpu.expr.math import BitwiseGet
    return BitwiseGet(_e(c), _e(pos))


bit_get = getbit


def bround(c, scale=0):
    from spark_rapids_tpu.expr.math import BRound
    return BRound(_e(c), scale)


def make_date(y, m, d):
    from spark_rapids_tpu.expr.datetime import MakeDate
    return MakeDate(_e(y), _e(m), _e(d))


def next_day(c, day):
    from spark_rapids_tpu.expr.datetime import NextDay
    return NextDay(_e(c), day)


def months_between(end, start, roundOff=True):
    from spark_rapids_tpu.expr.datetime import MonthsBetween
    return MonthsBetween(_e(end), _e(start), roundOff)


def _dt1(name):
    def f(c):
        from spark_rapids_tpu.expr import datetime as DTm
        return getattr(DTm, name)(_e(c))
    f.__name__ = name.lower()
    return f


unix_date = _dt1("UnixDate")
date_from_unix_date = _dt1("DateFromUnixDate")
unix_micros = _dt1("UnixMicros")
unix_millis = _dt1("UnixMillis")
unix_seconds = _dt1("UnixSeconds")
timestamp_millis = _dt1("TimestampMillis")
timestamp_micros = _dt1("TimestampMicros")


def octet_length(c):
    from spark_rapids_tpu.expr.strings import OctetLength
    return OctetLength(_e(c))


def bit_length(c):
    from spark_rapids_tpu.expr.strings import BitLength
    return BitLength(_e(c))


def left(c, n):
    from spark_rapids_tpu.expr.strings import Left
    from spark_rapids_tpu.expr.core import Literal
    return Left(_e(c), n.value if isinstance(n, Literal) else n)


def right(c, n):
    from spark_rapids_tpu.expr.strings import Right
    from spark_rapids_tpu.expr.core import Literal
    return Right(_e(c), n.value if isinstance(n, Literal) else n)


def chr_(c):
    from spark_rapids_tpu.expr.strings import Chr
    return Chr(_e(c))


char = chr_


def find_in_set(s, csv):
    from spark_rapids_tpu.expr.cpu_functions import FindInSet
    return FindInSet(_e(s), _e(csv))


def levenshtein(a, b):
    from spark_rapids_tpu.expr.cpu_functions import Levenshtein
    return Levenshtein(_e(a), _e(b))


def base64(c):
    from spark_rapids_tpu.expr.cpu_functions import Base64Encode
    return Base64Encode(_e(c))


def unbase64(c):
    from spark_rapids_tpu.expr.cpu_functions import UnBase64
    return UnBase64(_e(c))


def format_string(fmt, *cols):
    from spark_rapids_tpu.expr.cpu_functions import FormatString
    return FormatString(*[_e(c) for c in cols], params=(fmt,))


def elt(n, *cols):
    from spark_rapids_tpu.expr.cpu_functions import Elt
    return Elt(_e(n), *[_e(c) for c in cols])


def soundex(c):
    from spark_rapids_tpu.expr.cpu_functions import Soundex
    return Soundex(_e(c))


def json_tuple(c, *fields):
    from spark_rapids_tpu.expr.cpu_functions import JsonTuple
    return JsonTuple(_e(c), params=tuple(fields))


def crc32(c):
    from spark_rapids_tpu.expr.misc import Crc32
    return Crc32(_e(c))


def xxhash64(*cols):
    from spark_rapids_tpu.expr.misc import XxHash64
    return XxHash64([_e(c) for c in cols])


def array_repeat(v, n):
    from spark_rapids_tpu.expr.array_ops import ArrayRepeat
    return ArrayRepeat(_e(v), _e(n))


def array_join(c, sep, null_replacement=None):
    from spark_rapids_tpu.expr.array_ops import ArrayJoin
    return ArrayJoin(_e(c), sep, null_replacement)


def arrays_zip(*cols):
    from spark_rapids_tpu.expr.array_ops import ArraysZip
    return ArraysZip([_e(c) for c in cols])


def map_entries(c):
    from spark_rapids_tpu.expr.array_ops import MapEntries
    return MapEntries(_e(c))


def map_concat(*cols):
    from spark_rapids_tpu.expr.array_ops import MapConcat
    return MapConcat([_e(c) for c in cols])


def map_from_arrays(k, v):
    from spark_rapids_tpu.expr.array_ops import MapFromArrays
    return MapFromArrays(_e(k), _e(v))


def str_to_map(c, pair_delim=",", kv_delim=":"):
    from spark_rapids_tpu.expr.array_ops import StrToMap
    return StrToMap(_e(c), pair_delim, kv_delim)


def sha1(c):
    from spark_rapids_tpu.expr.cpu_functions import Sha1
    return Sha1(_e(c))


def hex(c):  # noqa: A001 - Spark name
    from spark_rapids_tpu.expr.cpu_functions import HexStr
    return HexStr(_e(c))


def unhex(c):
    from spark_rapids_tpu.expr.cpu_functions import Unhex
    return Unhex(_e(c))


def bin(c):  # noqa: A001 - Spark name
    from spark_rapids_tpu.expr.cpu_functions import Bin
    return Bin(_e(c))


def conv(c, from_base, to_base):
    from spark_rapids_tpu.expr.cpu_functions import Conv
    return Conv(_e(c), params=(int(from_base), int(to_base)))


def url_encode(c):
    from spark_rapids_tpu.expr.cpu_functions import UrlEncode
    return UrlEncode(_e(c))


def url_decode(c):
    from spark_rapids_tpu.expr.cpu_functions import UrlDecode
    return UrlDecode(_e(c))


def stack(n, *cols):
    from spark_rapids_tpu.expr.complex import Stack
    return Stack(n, *[_e(c) for c in cols])


def acosh(c):
    return MA.Acosh(_e(c))


def asinh(c):
    return MA.Asinh(_e(c))


def atanh(c):
    return MA.Atanh(_e(c))


def pmod(a, b):
    return MA.Pmod(_e(a), _e(b))


def positive(c):
    return MA.UnaryPositive(_e(c))


def weekday(c):
    return DT.WeekDay(_e(c))


def date_trunc(fmt, c):
    return DT.TruncTimestamp(_e(c), fmt)


def regexp_extract_all(c, pattern, idx=1):
    from spark_rapids_tpu.expr.cpu_functions import RegexpExtractAll
    return RegexpExtractAll(_e(c), params=(pattern, idx))


def to_json(c):
    from spark_rapids_tpu.expr.cpu_functions import StructsToJson
    return StructsToJson(_e(c))


def width_bucket(v, lo, hi, nb):
    return MA.WidthBucket(_e(v), _e(lo), _e(hi), _e(nb))


def luhn_check(c):
    from spark_rapids_tpu.expr.cpu_functions import Luhncheck
    return Luhncheck(_e(c))
