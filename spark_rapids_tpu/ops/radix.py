"""Range-compressed radix keys + sorted segmented reductions.

The round-3 performance backbone (reference parity: the cudf hash/radix
groupby + sort kernel library, SURVEY.md §2.9.1/§7.3.1 — re-designed for
what this TPU actually measures, not translated):

Found on a v5e in round 3 (none re-measured on today's installation):
a single-plane argsort is fast and compiles in seconds, while the
general multi-operand u64 ``lax.sort`` takes MINUTES to compile, and
64-bit scatter reductions (``segment_sum`` on f64/i64) and 64-bit
``searchsorted`` are an order of magnitude slower than their i32 forms
(the device emulates 64-bit lanes).  The fast primitives are:
single-key sorts, 32-bit scatters, and (exact, integer) cumsums — so
the groupby backbone is built from exactly those:

1. **Pack** all group keys into ONE int64 plane by runtime range
   compression: per key, ``code = value - min`` occupies
   ``ceil_log2(span+2)`` bits (slot 0 encodes NULL, so null groups work).
   Bit widths are static per compiled kernel (rounded up to multiples of
   4 to bound recompiles); the per-key minima ride in as traced scalars.
2. **Sort once** by the packed plane (stable argsort; dead rows get an
   above-range sentinel and sink to the tail).
3. **Segmented reductions over the sorted order** without any 64-bit
   scatter:
   - counts/any/all: i32 cumsum + boundary diff,
   - int64/decimal sums: ONE i64 cumsum (exact mod 2^64 — matching Java
     long overflow semantics bit-for-bit) + boundary diff,
   - f64 sums: TWO i64 "limb" cumsums of a fixed-point decomposition
     scaled to the batch maximum — error <= 1 ulp of the largest element
     regardless of group size (better than sequential summation),
   - min/max on 64-bit types: two chained i32 scatter reductions
     (high word, then low word among high-word winners),
   - first/last: i32 scatter-min/max of valid sorted positions.

Group keys are reconstructed arithmetically from the packed plane at the
segment boundaries — no gather of the original key columns at all.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnVector

#: packed planes are int64 with a dead-row sentinel above all live codes
MAX_PACK_BITS = 62
_SENTINEL = jnp.int64(1) << jnp.int64(MAX_PACK_BITS)

#: key kinds (static part of a pack spec)
KIND_INT = "int"      # needs runtime (min, span) — int-family/date/timestamp
KIND_DICT = "dict"    # dictionary codes, static span = vocab size
KIND_BOOL = "bool"    # static span = 2


@dataclass(frozen=True)
class PackSpec:
    """Static layout of a packed key plane: per-key (kind, bits). bits
    includes the +1 null slot and is rounded up to a multiple of 4 so the
    jit cache doesn't fragment across batches with slightly different
    spans."""
    kinds: Tuple[str, ...]
    bits: Tuple[int, ...]

    @property
    def total_bits(self) -> int:
        return sum(self.bits)

    @property
    def key(self):
        return (self.kinds, self.bits)


_INT_KINDS = (T.Int8Type, T.Int16Type, T.Int32Type, T.Int64Type,
              T.DateType, T.TimestampType)


def packable_dtype(c: ColumnVector) -> Optional[str]:
    if c.is_dict:
        return KIND_DICT
    d = c.dtype
    if isinstance(d, T.BooleanType):
        return KIND_BOOL
    if isinstance(d, _INT_KINDS):
        return KIND_INT
    if isinstance(d, T.DecimalType):
        return KIND_INT  # unscaled int64 representation
    return None


def static_kinds(key_cols: Sequence[ColumnVector]) -> Optional[List[str]]:
    kinds = []
    for c in key_cols:
        k = packable_dtype(c)
        if k is None:
            return None
        kinds.append(k)
    return kinds


def needs_range_probe(kinds: Sequence[str]) -> bool:
    return any(k == KIND_INT for k in kinds)


def probe_ranges(key_cols: Sequence[ColumnVector], live: jax.Array
                 ) -> jax.Array:
    """Traced: stacked [min_0, max_0, min_1, max_1, ...] (i64) for the
    KIND_INT keys (dict/bool keys contribute placeholder zeros to keep the
    layout positional). Null/dead rows are excluded."""
    out = []
    for c in key_cols:
        kind = packable_dtype(c)
        if kind != KIND_INT:
            out.extend([jnp.int64(0), jnp.int64(0)])
            continue
        v = c.data.astype(jnp.int64)
        valid = live if c.validity is None else (live & c.validity)
        lo = jnp.min(jnp.where(valid, v, jnp.int64(2**62)))
        hi = jnp.max(jnp.where(valid, v, -jnp.int64(2**62)))
        # all-null column: collapse to span 0
        lo = jnp.minimum(lo, hi)
        out.extend([lo, hi])
    return jnp.stack(out)


def _round_bits(b: int) -> int:
    # multiples of 2 bound jit-cache fragmentation across batches whose
    # spans drift, without pushing small keys past the BUCKET_BITS gate
    return max(2, -(-b // 2) * 2)


def _key_bits(key_cols: Sequence[ColumnVector], kinds: Sequence[str],
              ranges_host: Optional[np.ndarray]) -> List[int]:
    """Bits each key's code takes in a packed plane (slot 0 is NULL)."""
    bits = []
    for i, (c, kind) in enumerate(zip(key_cols, kinds)):
        if kind == KIND_DICT:
            span = max(int(c.dict_size) - 1, 0)
        elif kind == KIND_BOOL:
            span = 1
        else:
            lo = int(ranges_host[2 * i])
            hi = int(ranges_host[2 * i + 1])
            span = hi - lo
            if span < 0:
                span = 0
        # codes occupy [0, span+1]; slot 0 is NULL
        bits.append(_round_bits(int(span + 2).bit_length()))
    return bits


def plan_packing(key_cols: Sequence[ColumnVector],
                 ranges_host: Optional[np.ndarray]) -> Optional[PackSpec]:
    """Host-side: decide the static bit layout. ranges_host is the fetched
    probe_ranges vector (None when no KIND_INT keys)."""
    kinds = static_kinds(key_cols)
    if kinds is None:
        return None
    spec = PackSpec(tuple(kinds), tuple(_key_bits(key_cols, kinds,
                                                  ranges_host)))
    if spec.total_bits > MAX_PACK_BITS:
        return None
    return spec


#: planes plan_packing_planes lays keys over at most (the rollup's sort
#: pays a pass of the shared argsort a 32-bit digit of each)
MAX_PACK_PLANES = 2


def plan_packing_planes(key_cols: Sequence[ColumnVector],
                        ranges_host: Optional[np.ndarray]
                        ) -> Optional[List[Tuple[PackSpec, int, int]]]:
    """plan_packing for keys too wide for one plane: [(spec, first key,
    one past the last key)] a plane, the first key in the first plane, a
    key never split over two; None when the keys do not pack or need more
    than MAX_PACK_PLANES. Sorting by the planes in order, the first the
    most significant, is the lexicographic order of the keys' codes."""
    kinds = static_kinds(key_cols)
    if kinds is None:
        return None
    bits = _key_bits(key_cols, kinds, ranges_host)
    planes, first, used = [], 0, 0
    for i, b in enumerate(bits):
        if b > MAX_PACK_BITS:
            return None
        if used + b > MAX_PACK_BITS:
            planes.append((first, i))
            first, used = i, 0
        used += b
    planes.append((first, len(bits)))
    if len(planes) > MAX_PACK_PLANES:
        return None
    return [(PackSpec(tuple(kinds[a:b]), tuple(bits[a:b])), a, b)
            for a, b in planes]


def pack_keys(spec: PackSpec, key_cols: Sequence[ColumnVector],
              mins: jax.Array, live: jax.Array) -> jax.Array:
    """Traced: ONE int64 plane with the range-compressed key codes.
    mins = the probe_ranges vector (device; only KIND_INT entries used).
    Dead rows get the above-range sentinel so they sort to the tail."""
    from spark_rapids_tpu.ops import kernels as K
    cap = live.shape[0]
    packed = jnp.zeros(cap, jnp.int64)
    for i, (c, kind, b) in enumerate(zip(key_cols, spec.kinds, spec.bits)):
        if kind == KIND_DICT:
            # codes stand for the strings: equal where the strings are (a
            # gathered flat column's are not until made so)
            code = K.canonical_dict_codes(c).data["codes"].astype(jnp.int64)
        elif kind == KIND_BOOL:
            code = c.data.astype(jnp.int64)
        else:
            code = c.data.astype(jnp.int64) - mins[2 * i]
        code = code + 1  # slot 0 = NULL
        if c.validity is not None:
            code = jnp.where(c.validity, code, jnp.int64(0))
        packed = (packed << jnp.int64(b)) | jnp.clip(
            code, 0, (jnp.int64(1) << jnp.int64(b)) - 1)
    return jnp.where(live, packed, _SENTINEL)


def pack_keys_sort(spec: PackSpec, key_cols: Sequence[ColumnVector],
                   mins: jax.Array, live: jax.Array,
                   flags: Sequence[Tuple[bool, bool]]) -> jax.Array:
    """Order-faithful variant of pack_keys: per key, (ascending,
    nulls_first) decides the field encoding so an ascending sort of the
    packed plane IS the requested lexicographic order. KIND_INT/BOOL
    only for order-significant keys (dict codes are not value-ordered;
    callers place dict keys only in grouping positions with (True, True)
    where any consistent order suffices)."""
    from spark_rapids_tpu.ops import kernels as K
    cap = live.shape[0]
    packed = jnp.zeros(cap, jnp.int64)
    for i, (c, kind, b, (asc, nf)) in enumerate(
            zip(key_cols, spec.kinds, spec.bits, flags)):
        if kind == KIND_DICT:
            v = K.canonical_dict_codes(c).data["codes"].astype(jnp.int64)
            lo = jnp.int64(0)
            hi = jnp.int64(max(int(c.dict_size) - 1, 0))
        elif kind == KIND_BOOL:
            v = c.data.astype(jnp.int64)
            lo, hi = jnp.int64(0), jnp.int64(1)
        else:
            v = c.data.astype(jnp.int64)
            lo, hi = mins[2 * i], mins[2 * i + 1]
        code = (v - lo) if asc else (hi - v)
        span_max = (jnp.int64(1) << jnp.int64(b)) - jnp.int64(2)
        code = jnp.clip(code, 0, span_max)
        if nf:
            code = code + 1
            null_code = jnp.int64(0)
        else:
            null_code = span_max + 1
        if c.validity is not None:
            code = jnp.where(c.validity, code, null_code)
        packed = (packed << jnp.int64(b)) | code
    return jnp.where(live, packed, _SENTINEL)


def unpack_keys(spec: PackSpec, group_packed: jax.Array,
                mins: jax.Array, key_cols: Sequence[ColumnVector]
                ) -> List[ColumnVector]:
    """Traced: rebuild representative key columns from packed group values
    (arithmetic only — no gather of the source key planes). key_cols
    supply dtype + (for dict) the shared vocab planes."""
    out = []
    rem = group_packed
    fields = []
    for b in reversed(spec.bits):
        fields.append(rem & ((jnp.int64(1) << jnp.int64(b)) - 1))
        rem = rem >> jnp.int64(b)
    fields.reverse()
    for i, (c, kind, code) in enumerate(zip(key_cols, spec.kinds, fields)):
        valid = code != 0
        v = code - 1
        if kind == KIND_DICT:
            data = {"codes": v.astype(jnp.int32),
                    "dict_offsets": c.data["dict_offsets"],
                    "dict_bytes": c.data["dict_bytes"]}
            out.append(ColumnVector(c.dtype, data, valid,
                                    dict_unique=c.dict_unique))
            continue
        if kind == KIND_BOOL:
            out.append(ColumnVector(c.dtype, v.astype(jnp.bool_), valid))
            continue
        v = v + mins[2 * i]
        out.append(ColumnVector(c.dtype, v.astype(c.data.dtype), valid))
    return out


# ---------------------------------------------------------------------------
# Sorted segment layout
# ---------------------------------------------------------------------------

@dataclass
class GroupLayout:
    """Everything downstream reductions need, all traced arrays.
    Positions are in SORTED row order; group g lives at slot g in
    [0, n_groups)."""
    perm: jax.Array          # i32[cap] stable sort permutation
    sorted_packed: jax.Array  # i64[cap]
    boundary: jax.Array      # bool[cap] first sorted row of each group
    gid: jax.Array           # i32[cap] dense group id per sorted row
    safe_gid: jax.Array      # gid with dead rows routed to slot `cap`
    starts: jax.Array        # i32[cap] sorted position of group g's first row (-1 pad)
    ends: jax.Array          # i32[cap] sorted position of group g's last row (-1 pad)
    n_live: jax.Array        # i32 scalar
    n_groups: jax.Array      # i32 scalar
    cap: int


def group_layout(packed: jax.Array, live: jax.Array) -> GroupLayout:
    cap = packed.shape[0]
    n_live = jnp.sum(live.astype(jnp.int32))
    perm = jnp.argsort(packed, stable=True).astype(jnp.int32)
    sp = packed[perm]
    pos = jnp.arange(cap, dtype=jnp.int32)
    in_range = pos < n_live
    boundary = jnp.concatenate([jnp.ones(1, jnp.bool_), sp[1:] != sp[:-1]])
    boundary = boundary & in_range
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    n_groups = jnp.sum(boundary.astype(jnp.int32))
    safe_gid = jnp.where(in_range, gid, cap)
    # compacted boundary positions -> per-group start index
    bpos = jnp.where(boundary, gid, cap)
    starts = jnp.full(cap + 1, -1, jnp.int32).at[bpos].set(pos, mode="drop")[:cap]
    nxt = jnp.concatenate([starts[1:], jnp.full(1, -1, jnp.int32)])
    ends = jnp.where(nxt >= 0, nxt - 1, n_live - 1)
    ends = jnp.where(starts >= 0, ends, -1)
    return GroupLayout(perm, sp, boundary, gid, safe_gid, starts, ends,
                       n_live, n_groups, cap)


def _seg_diff(csum: jax.Array, x0: jax.Array, lay: GroupLayout) -> jax.Array:
    """Per-group total from an inclusive cumsum over sorted rows:
    total[g] = csum[end_g] - csum[start_g] + x[start_g]."""
    s = jnp.clip(lay.starts, 0, lay.cap - 1)
    e = jnp.clip(lay.ends, 0, lay.cap - 1)
    return csum[e] - csum[s] + x0[s]


def seg_count(valid_sorted: jax.Array, lay: GroupLayout) -> jax.Array:
    v = valid_sorted.astype(jnp.int32)
    return _seg_diff(jnp.cumsum(v), v, lay).astype(jnp.int64)


def seg_count_all(lay: GroupLayout) -> jax.Array:
    return (lay.ends - lay.starts + 1).astype(jnp.int64)


def seg_sum_int(vals_sorted: jax.Array, valid_sorted: jax.Array,
                lay: GroupLayout) -> jax.Array:
    """Exact mod-2^64 segmented integer sum (wraparound matches Java)."""
    v = jnp.where(valid_sorted, vals_sorted.astype(jnp.int64),
                  jnp.int64(0))
    return _seg_diff(jnp.cumsum(v), v, lay)


def _exponent_scale(m: jax.Array) -> jax.Array:
    """2^(36 - floor(log2(m))) for a positive scalar m, via compare-and-
    multiply (no 64-bit bitcasts — see kernels._frexp_arith). m == 0 maps
    to scale 1 (all-zero plane, sums are exactly 0 anyway)."""
    x = jnp.where(m > 0, m, jnp.float64(1.0))
    scale = jnp.float64(2.0) ** 36
    for k in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        up = np.float64(2.0) ** k
        c = x >= up
        x = jnp.where(c, x * np.float64(2.0) ** (-k), x)
        scale = jnp.where(c, scale * np.float64(2.0) ** (-k), scale)
        c2 = x * up < 2.0
        x = jnp.where(c2, x * up, x)
        scale = jnp.where(c2, scale * up, scale)
    return scale


def f64_sum_planes(v: jax.Array, valid: jax.Array
                   ) -> Tuple[List[jax.Array], jax.Array]:
    """The integer planes whose per-group sums ARE a float sum: two int64
    limbs of a fixed-point decomposition scaled to the largest |value|
    (every addend exact, so a sum of them is the same whatever the order
    or the grouping), and the counts of NaN / +inf (one int64 plane,
    nan<<31 | pinf) and of -inf (int32). Returns (planes, scale)."""
    v = v.astype(jnp.float64)
    nan = jnp.isnan(v) & valid
    pinf = (v == jnp.inf) & valid
    ninf = (v == -jnp.inf) & valid
    finite = valid & ~nan & ~pinf & ~ninf
    clean = jnp.where(finite, v, jnp.float64(0.0))

    m = jnp.max(jnp.abs(clean))
    scale = _exponent_scale(m)  # 2^(36-E): |clean|*scale < 2^37
    scaled = clean * scale
    hi = jnp.floor(scaled)
    lo = jnp.round((scaled - hi) * np.float64(2.0) ** 36)
    spec = (nan.astype(jnp.int64) << jnp.int64(31)) | pinf.astype(jnp.int64)
    return [hi.astype(jnp.int64), lo.astype(jnp.int64), spec,
            ninf.astype(jnp.int32)], scale


def f64_sum_finish(shi: jax.Array, slo: jax.Array, sspec: jax.Array,
                   n_ninf: jax.Array, scale: jax.Array) -> jax.Array:
    """Per-group sums of f64_sum_planes' planes back to the float sum,
    NaN/Inf with Spark's semantics."""
    total = (shi.astype(jnp.float64)
             + slo.astype(jnp.float64) * np.float64(2.0) ** -36) / scale
    n_nan = sspec >> jnp.int64(31)
    n_pinf = sspec & ((jnp.int64(1) << jnp.int64(31)) - 1)
    is_nan = (n_nan > 0) | ((n_pinf > 0) & (n_ninf > 0))
    out = jnp.where(n_pinf > 0, jnp.float64(np.inf), total)
    out = jnp.where(n_ninf > 0, jnp.float64(-np.inf), out)
    out = jnp.where(is_nan, jnp.float64(np.nan), out)
    return out


def seg_sum_f64(vals_sorted: jax.Array, valid_sorted: jax.Array,
                lay: GroupLayout) -> jax.Array:
    """Segmented float sum via two exact int64 limb cumsums. Finite part
    is summed with error <= 1 ulp of the largest |value| in the batch;
    NaN/Inf propagate with Spark semantics (counted per segment through
    the same cumsum-diff machinery — no 64-bit scatter anywhere)."""
    planes, scale = f64_sum_planes(vals_sorted, valid_sorted)
    return f64_sum_finish(*(_seg_diff(jnp.cumsum(x), x, lay)
                            for x in planes), scale)


def _scatter_red(op: str, vals: jax.Array, gid: jax.Array, cap: int
                 ) -> jax.Array:
    red = jax.ops.segment_min if op == "min" else jax.ops.segment_max
    return red(vals, gid, num_segments=cap + 1)[:cap]


def seg_minmax_i32(op: str, vals_sorted: jax.Array, valid_sorted: jax.Array,
                   lay: GroupLayout, init) -> jax.Array:
    v = jnp.where(valid_sorted, vals_sorted.astype(jnp.int32),
                  jnp.full_like(vals_sorted, init, dtype=jnp.int32))
    return _scatter_red(op, v, lay.safe_gid, lay.cap)


def seg_minmax_i64(op: str, vals_sorted: jax.Array, valid_sorted: jax.Array,
                   lay: GroupLayout) -> jax.Array:
    """64-bit segmented min/max as two chained i32 scatter reductions:
    first the high words; then, among rows whose high word equals the
    group winner, the (order-adjusted) low words."""
    init64 = np.iinfo(np.int64).max if op == "min" else np.iinfo(np.int64).min
    v = jnp.where(valid_sorted, vals_sorted.astype(jnp.int64),
                  jnp.int64(init64))
    hi = (v >> jnp.int64(32)).astype(jnp.int32)
    # low word: unsigned order -> shift into signed i32 range for compare
    lo = v & jnp.int64(0xFFFFFFFF)
    lo32 = (lo - jnp.int64(2**31)).astype(jnp.int32)
    whi = _scatter_red(op, hi, lay.safe_gid, lay.cap)
    cand = hi == whi[jnp.clip(lay.safe_gid, 0, lay.cap - 1)]
    init32 = np.iinfo(np.int32).max if op == "min" else np.iinfo(np.int32).min
    lo_m = jnp.where(cand & valid_sorted, lo32, jnp.int32(init32))
    wlo = _scatter_red(op, lo_m, lay.safe_gid, lay.cap)
    return (whi.astype(jnp.int64) << jnp.int64(32)) | \
        (wlo.astype(jnp.int64) + jnp.int64(2**31)).astype(jnp.uint32).astype(jnp.int64)


def seg_first_last(op: str, vals_sorted: jax.Array, valid_sorted: jax.Array,
                   lay: GroupLayout) -> Tuple[jax.Array, jax.Array]:
    """Sorted position of the first/last VALID row per group (stable sort
    keeps original row order within a group), then gather."""
    cap = lay.cap
    pos = jnp.arange(cap, dtype=jnp.int32)
    if op == "first":
        p = jnp.where(valid_sorted, pos, cap)
        sel = _scatter_red("min", p, lay.safe_gid, cap)
        has = sel < cap
    else:
        p = jnp.where(valid_sorted, pos, -1)
        sel = _scatter_red("max", p, lay.safe_gid, cap)
        has = sel >= 0
    selc = jnp.clip(sel, 0, cap - 1)
    return vals_sorted[selc], has


# ---------------------------------------------------------------------------
# Sort-free scatter-bucket aggregation (small packed key spaces)
#
# When the packed key fits BUCKET_BITS (<= 2^23 buckets), skip the sort
# entirely: every reduction is a direct i32 scatter into the bucket space.
# Measured on v5e: one i32 segment_sum of 8M rows into 3M buckets is
# ~95 ms, while the sorted pipeline pays ~150 ms PER GATHER (random
# gathers run at ~0.4 GB/s on this hardware) — so three balanced-digit
# limb scatters beat sort+gather+cumsum by ~4x and need no host sync.
# ---------------------------------------------------------------------------

#: max total packed bits for the scatter-bucket path (8M-slot targets)
BUCKET_BITS = 23
#: per-bucket row-count bound for the 16-bit-digit f64 sum: |digit| can
#: reach 2^16 at the top of the max binade (|s| < 2^48), so counts up to
#: 2^14 keep the i32 accumulator under 2^30
_LIMB_COUNT_LIMIT = 1 << 14
#: int sums keep the original 2^15 bound: their 16-bit balanced digits
#: are strictly |d| <= 2^15 (unlike the f64 path's rounded 2^16 corner)
_INT_LIMB_COUNT_LIMIT = 1 << 15


class BucketLayout:
    __slots__ = ("bucket", "nb", "counts", "occupied", "n_groups",
                 "max_cnt", "live")

    def __init__(self, bucket, nb, counts, occupied, n_groups, max_cnt,
                 live):
        self.bucket = bucket
        self.nb = nb
        self.counts = counts
        self.occupied = occupied
        self.n_groups = n_groups
        self.max_cnt = max_cnt
        self.live = live


def bucket_layout(spec: PackSpec, key_cols, mins, live) -> BucketLayout:
    """i32 bucket id per row (dead rows -> overflow slot nb) + occupancy."""
    nb = 1 << spec.total_bits
    packed = pack_keys(spec, key_cols, mins, live)
    bucket = jnp.where(live, packed, jnp.int64(nb)).astype(jnp.int32)
    counts = jax.ops.segment_sum(jnp.ones(bucket.shape[0], jnp.int32),
                                 bucket, num_segments=nb + 1)[:nb]
    occupied = counts > 0
    n_groups = jnp.sum(occupied.astype(jnp.int32))
    max_cnt = jnp.max(counts)
    return BucketLayout(bucket, nb, counts, occupied, n_groups, max_cnt,
                        live)


def bucket_unpack_keys(spec: PackSpec, mins, key_cols) -> List[ColumnVector]:
    """Group keys for the whole bucket space, decoded from the bucket
    INDEX itself — pure arithmetic over arange, zero data movement."""
    nb = 1 << spec.total_bits
    return unpack_keys(spec, jnp.arange(nb, dtype=jnp.int64), mins, key_cols)


def _safe_bucket(lay: BucketLayout, valid) -> jax.Array:
    return jnp.where(valid, lay.bucket, jnp.int32(lay.nb))


def bucket_count(lay: BucketLayout, valid) -> jax.Array:
    return jax.ops.segment_sum(
        jnp.where(valid, 1, 0).astype(jnp.int32), lay.bucket,
        num_segments=lay.nb + 1)[:lay.nb].astype(jnp.int64)


def bucket_sum_int(lay: BucketLayout, vals, valid) -> jax.Array:
    """Exact mod-2^64 integer sum per bucket from balanced i32 limb
    scatters. Limb width adapts to bucket depth (scatters are ~a full
    batch pass each on this hardware): counts <= 2^9 take three 22-bit
    limbs, counts <= 2^15 four 16-bit limbs (|digit| <= 2^15, so
    2^15 * 2^15 = 2^30 fits i32), pathological skew one slow i64
    scatter. Picked at runtime by lax.cond — no sync."""
    v = jnp.where(valid, vals.astype(jnp.int64), jnp.int64(0))
    sb = _safe_bucket(lay, valid)

    def limb_path(width: int, nlimbs: int):
        half = jnp.int64(1 << (width - 1))
        mask = jnp.int64((1 << width) - 1)

        def go(_):
            x = v
            acc = jnp.zeros(lay.nb, jnp.int64)
            for i in range(nlimbs):
                d = ((x + half) & mask) - half
                if i < nlimbs - 1:
                    x = (x - d) >> jnp.int64(width)
                # else: top limb truncates; wraparound keeps mod-2^64
                s = jax.ops.segment_sum(d.astype(jnp.int32), sb,
                                        num_segments=lay.nb + 1)[:lay.nb]
                acc = acc + (s.astype(jnp.int64) << jnp.int64(width * i))
            return acc
        return go

    def slow_path(_):
        return jax.ops.segment_sum(v, sb, num_segments=lay.nb + 1)[:lay.nb]

    return lax.cond(
        lay.max_cnt <= (1 << 9), limb_path(22, 3),
        lambda _: lax.cond(lay.max_cnt <= _INT_LIMB_COUNT_LIMIT,
                           limb_path(16, 4), slow_path, None),
        None)


#: shallow-bucket bound for the 2-digit f64 sum: |digit| = round(s/2^24)
#: can reach 2^24 at the top of the max binade (|s| < 2^48), so counts up
#: to 64 keep the i32 accumulator under 2^31
_LIMB2_COUNT_LIMIT = 1 << 6


def bucket_sum_f64(lay: BucketLayout, vals, valid) -> jax.Array:
    """Float sum per bucket via balanced fixed-point digit scatters of a
    47-bit representation below the batch max exponent — error <= ~1 ulp
    of the device's own f32-pair f64. Scatters are the dominant cost of
    the bucket path on this hardware (~each a full pass over the batch),
    so the digit count adapts to bucket depth: shallow buckets (the
    high-cardinality-groupby shape) take TWO base-2^24 digits, deeper
    ones three base-2^16 digits, pathological skew one slow f64 scatter.
    The NaN/Inf flag scatters only execute when the batch actually
    contains a special (one cheap any() reduce gates them)."""
    v = vals.astype(jnp.float64)
    nan = jnp.isnan(v) & valid
    pinf = (v == jnp.inf) & valid
    ninf = (v == -jnp.inf) & valid
    finite = valid & ~nan & ~pinf & ~ninf
    clean = jnp.where(finite, v, jnp.float64(0.0))
    sb = _safe_bucket(lay, valid)

    m = jnp.max(jnp.abs(clean))
    scale = _exponent_scale(m) * np.float64(2.0 ** 11)  # 47 bits below E
    s = clean * scale

    def digits_path(widths):
        def go(_):
            tot = jnp.zeros(lay.nb, jnp.float64)
            rem = s
            shift = sum(widths)
            for w in widths:
                shift -= w
                d = jnp.round(rem / np.float64(2.0 ** shift)) if shift \
                    else jnp.round(rem)
                if shift:
                    rem = rem - d * np.float64(2.0 ** shift)
                acc = jax.ops.segment_sum(d.astype(jnp.int32), sb,
                                          num_segments=lay.nb + 1)[:lay.nb]
                tot = tot + acc.astype(jnp.float64) * np.float64(2.0 ** shift)
            return tot / scale
        return go

    def slow_path(_):
        return jax.ops.segment_sum(clean, sb,
                                   num_segments=lay.nb + 1)[:lay.nb]

    total = lax.cond(
        lay.max_cnt <= _LIMB2_COUNT_LIMIT, digits_path((24, 24)),
        lambda _: lax.cond(lay.max_cnt <= _LIMB_COUNT_LIMIT,
                           digits_path((16, 16, 16)), slow_path, None),
        None)

    any_special = nan | pinf | ninf

    def exact_flags(_):
        has_nan = jax.ops.segment_max(
            jnp.where(nan, 1, 0).astype(jnp.int32), sb,
            num_segments=lay.nb + 1)[:lay.nb] > 0
        has_pinf = jax.ops.segment_max(
            jnp.where(pinf, 1, 0).astype(jnp.int32), sb,
            num_segments=lay.nb + 1)[:lay.nb] > 0
        has_ninf = jax.ops.segment_max(
            jnp.where(ninf, 1, 0).astype(jnp.int32), sb,
            num_segments=lay.nb + 1)[:lay.nb] > 0
        return has_nan, has_pinf, has_ninf

    def no_flags(_):
        f = jnp.zeros(lay.nb, jnp.bool_)
        return f, f, f

    has_nan, has_pinf, has_ninf = lax.cond(jnp.any(any_special), exact_flags,
                                           no_flags, None)
    out = jnp.where(has_pinf, jnp.float64(np.inf), total)
    out = jnp.where(has_ninf, jnp.float64(-np.inf), out)
    out = jnp.where(has_nan | (has_pinf & has_ninf), jnp.float64(np.nan), out)
    return out


def bucket_minmax_i32(op, lay: BucketLayout, vals, valid, init) -> jax.Array:
    v = jnp.where(valid, vals.astype(jnp.int32),
                  jnp.full(vals.shape, init, jnp.int32))
    red = jax.ops.segment_min if op == "min" else jax.ops.segment_max
    return red(v, _safe_bucket(lay, valid), num_segments=lay.nb + 1)[:lay.nb]


def bucket_minmax_i64(op, lay: BucketLayout, vals, valid) -> jax.Array:
    init64 = np.iinfo(np.int64).max if op == "min" else np.iinfo(np.int64).min
    v = jnp.where(valid, vals.astype(jnp.int64), jnp.int64(init64))
    sb = _safe_bucket(lay, valid)
    red = jax.ops.segment_min if op == "min" else jax.ops.segment_max
    hi = (v >> jnp.int64(32)).astype(jnp.int32)
    lo = ((v & jnp.int64(0xFFFFFFFF)) - jnp.int64(2 ** 31)).astype(jnp.int32)
    whi = red(hi, sb, num_segments=lay.nb + 1)[:lay.nb]
    cand = valid & (hi == whi[jnp.clip(lay.bucket, 0, lay.nb - 1)])
    init32 = np.iinfo(np.int32).max if op == "min" else np.iinfo(np.int32).min
    lom = jnp.where(cand, lo, jnp.int32(init32))
    wlo = red(lom, _safe_bucket(lay, cand), num_segments=lay.nb + 1)[:lay.nb]
    return (whi.astype(jnp.int64) << jnp.int64(32)) | \
        (wlo.astype(jnp.int64) + jnp.int64(2 ** 31)).astype(jnp.uint32).astype(jnp.int64)


def bucket_minmax_f64(op, lay: BucketLayout, vals, valid) -> jax.Array:
    o = _f64_order_i64(vals.astype(jnp.float64))
    init = np.iinfo(np.int64).max if op == "min" else np.iinfo(np.int64).min
    o = jnp.where(valid, o, jnp.int64(init))
    w = bucket_minmax_i64(op, lay, o, jnp.ones_like(valid))
    return _i64_order_f64(w)


def bucket_minmax_f32(op, lay: BucketLayout, vals, valid) -> jax.Array:
    min32 = jnp.int32(np.int32(-2 ** 31))
    v = vals.astype(jnp.float32)
    x = jnp.where(jnp.isnan(v), jnp.float32(np.nan), v)
    x = jnp.where(x == 0.0, jnp.zeros_like(x), x)
    bits = lax.bitcast_convert_type(x, jnp.int32)
    o = jnp.where(bits < 0, ~bits ^ min32, bits)
    init = np.iinfo(np.int32).max if op == "min" else np.iinfo(np.int32).min
    w = bucket_minmax_i32(op, lay, o, valid, init)
    back = jnp.where(w < 0, ~(w ^ min32), w)
    return lax.bitcast_convert_type(back, jnp.float32)


def bucket_first_last(op, lay: BucketLayout, vals, valid
                      ) -> Tuple[jax.Array, jax.Array]:
    n = vals.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    if op == "first":
        p = jnp.where(valid, pos, n)
        sel = jax.ops.segment_min(p, _safe_bucket(lay, valid),
                                  num_segments=lay.nb + 1)[:lay.nb]
        has = sel < n
    else:
        p = jnp.where(valid, pos, -1)
        sel = jax.ops.segment_max(p, _safe_bucket(lay, valid),
                                  num_segments=lay.nb + 1)[:lay.nb]
        has = sel >= 0
    return vals[jnp.clip(sel, 0, n - 1)], has


def _f64_order_i64(v: jax.Array) -> jax.Array:
    """f64 -> order-preserving int64 (Spark total order: NaN above +inf,
    -0.0 == 0.0), via the arithmetic bitcast (no 64-bit bitcast-convert
    on TPU)."""
    from spark_rapids_tpu.ops import kernels as K
    x = jnp.where(jnp.isnan(v), jnp.float64(np.nan), v)
    x = jnp.where(x == 0.0, jnp.zeros_like(x), x)
    bits = K._bitcast_f64_u64(x)
    neg = (bits >> jnp.uint64(63)) != 0
    u = jnp.where(neg, ~bits, bits | (jnp.uint64(1) << jnp.uint64(63)))
    return (u.astype(jnp.int64) ^ jnp.int64(np.int64(-2**63)))


def _i64_order_f64(o: jax.Array) -> jax.Array:
    from spark_rapids_tpu.ops import groupby as G
    u = (o ^ jnp.int64(np.int64(-2**63))).astype(jnp.uint64)
    return G._invert_float_bits(u, 64, np.float64)


def seg_minmax_f64(op: str, vals_sorted: jax.Array, valid_sorted: jax.Array,
                   lay: GroupLayout) -> jax.Array:
    """Segmented f64 min/max through the order-preserving i64 transform +
    the two-pass i32 scatter reduction."""
    o = _f64_order_i64(vals_sorted.astype(jnp.float64))
    init = np.iinfo(np.int64).max if op == "min" else np.iinfo(np.int64).min
    o = jnp.where(valid_sorted, o, jnp.int64(init))
    w = seg_minmax_i64(op, o, valid_sorted | True, lay)
    return _i64_order_f64(w)


def seg_minmax_f32(op: str, vals_sorted: jax.Array, valid_sorted: jax.Array,
                   lay: GroupLayout) -> jax.Array:
    """f32 min/max via the signed-i32 order transform + one i32 scatter.
    forward: o = bits < 0 ? ~bits ^ MIN32 : bits; inverse mirrors it."""
    min32 = jnp.int32(np.int32(-2**31))
    v = vals_sorted.astype(jnp.float32)
    x = jnp.where(jnp.isnan(v), jnp.float32(np.nan), v)
    x = jnp.where(x == 0.0, jnp.zeros_like(x), x)
    bits = lax.bitcast_convert_type(x, jnp.int32)
    o = jnp.where(bits < 0, ~bits ^ min32, bits)
    init = np.iinfo(np.int32).max if op == "min" else np.iinfo(np.int32).min
    w = seg_minmax_i32(op, o, valid_sorted, lay, init)
    back = jnp.where(w < 0, ~(w ^ min32), w)
    return lax.bitcast_convert_type(back, jnp.float32)
