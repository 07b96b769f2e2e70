"""Core device kernels: hashing, normalization, sort, compaction, gather,
concat, and join candidate expansion.

Reference parity: the libcudf Table algebra surface enumerated in SURVEY.md
§2.9.1 (join gather-maps, groupby agg, sort/OrderByArg, filter, gather,
concat, slice) and jni.Hash (Spark-compatible murmur3/xxhash64).

TPU-first design: everything here is shape-static and branch-free so XLA can
tile it onto the VPU/MXU. Dynamic-result ops (filter, join) follow the
count-then-gather discipline: a jitted counting pass, a host readback of one
scalar, then a jitted gather pass compiled per output-capacity bucket
(the JoinGatherer analog from SURVEY.md §7.3.1).
"""
from __future__ import annotations

import dataclasses

from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnVector, ColumnarBatch, LazyRowCount, carry_host_stats, host_int,
    host_planes, materialize_counts, max_entry_len, round_capacity,
    traced_rows,
)
from spark_rapids_tpu.ops.pallas_decode import _cumsum, _per_row
from spark_rapids_tpu.runtime import compile_cache as _cc

# ---------------------------------------------------------------------------
# Spark-compatible Murmur3 (x86_32, seed 42) -- reference jni.Hash murmur3.
# Matching Spark's hash exactly means a future live-Spark adapter places rows
# exactly where CPU Spark would for hash partitioning.
# ---------------------------------------------------------------------------

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
SPARK_MURMUR3_SEED = 42


def _rotl32(x, r):
    return (x << r) | (x >> (32 - r))


def _mm3_mix_k1(k1):
    k1 = k1 * _C1
    k1 = _rotl32(k1, 15)
    return k1 * _C2


def _mm3_mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(h1, 13)
    return h1 * np.uint32(5) + np.uint32(0xE6546B64)


def _mm3_fmix(h1, length):
    h1 = h1 ^ length.astype(jnp.uint32) if hasattr(length, "astype") else h1 ^ np.uint32(length)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * np.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def murmur3_int32(values: jax.Array, seed: jax.Array) -> jax.Array:
    """Murmur3 of an int32 plane (Spark hashInt). Block-aligned planes
    take the hand-tiled Pallas kernel (ops/pallas_kernels.py); the lax
    chain below is the reference twin and the small-plane path."""
    from spark_rapids_tpu.ops import pallas_kernels as PK
    if PK.enabled() and PK.pallas_supported(values.shape[0]) \
            and getattr(seed, "ndim", 1) == 0:
        return PK.murmur3_int32_pallas(values, seed)
    k1 = _mm3_mix_k1(values.astype(jnp.uint32))
    h1 = _mm3_mix_h1(seed.astype(jnp.uint32), k1)
    return _mm3_fmix(h1, 4)


def murmur3_int64(values: jax.Array, seed: jax.Array) -> jax.Array:
    """Murmur3 of an int64 plane (Spark hashLong: low word then high word)."""
    v = values.astype(jnp.uint64)
    low = (v & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    high = (v >> jnp.uint64(32)).astype(jnp.uint32)
    h1 = seed.astype(jnp.uint32)
    h1 = _mm3_mix_h1(h1, _mm3_mix_k1(low))
    h1 = _mm3_mix_h1(h1, _mm3_mix_k1(high))
    return _mm3_fmix(h1, 8)


def murmur3_bytes(offsets: jax.Array, raw: jax.Array, seed: jax.Array) -> jax.Array:
    """Per-row Murmur3 over variable-length byte slices (Spark
    hashUnsafeBytes over UTF8 payloads): 4-byte little-endian words for the
    aligned prefix, then each trailing byte mixed individually as a
    sign-extended int. Variable trip count handled with a lax.while_loop over
    the batch max length; shorter rows mask out (branch-free)."""
    cap = offsets.shape[0] - 1
    starts = offsets[:-1].astype(jnp.int32)
    lens = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    nbytes = raw.shape[0]

    def byte_at(pos):
        idx = jnp.clip(pos, 0, nbytes - 1)
        return raw[idx]

    def word_body(state):
        i, h1 = state
        pos = starts + 4 * i
        b0 = byte_at(pos).astype(jnp.uint32)
        b1 = byte_at(pos + 1).astype(jnp.uint32)
        b2 = byte_at(pos + 2).astype(jnp.uint32)
        b3 = byte_at(pos + 3).astype(jnp.uint32)
        k1 = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
        mixed = _mm3_mix_h1(h1, _mm3_mix_k1(k1))
        active = (i + 1) * 4 <= lens
        return i + 1, jnp.where(active, mixed, h1)

    def word_cond(state):
        i, _ = state
        return (i + 1) * 4 <= jnp.max(lens)

    h0 = jnp.broadcast_to(seed.astype(jnp.uint32), (cap,))
    _, h1 = lax.while_loop(word_cond, word_body, (jnp.int32(0), h0))

    aligned = lens - (lens % 4)
    for j in range(3):
        pos = starts + aligned + j
        active = aligned + j < lens
        b = byte_at(pos).astype(jnp.int8).astype(jnp.int32).astype(jnp.uint32)
        mixed = _mm3_mix_h1(h1, _mm3_mix_k1(b))
        h1 = jnp.where(active, mixed, h1)
    return _mm3_fmix(h1, lens)


def spark_hash_column(col: ColumnVector, num_rows: int, seed: jax.Array,
                      live=None) -> jax.Array:
    """Spark Murmur3Hash semantics per type: null fields pass the running
    seed through unchanged."""
    d = col.dtype
    if col.is_dict:
        # hash the (small) vocab once, then gather by code; per-row seeds
        # force the general path (vocab hash is seed-independent only for
        # scalar seeds)
        if seed.ndim == 0:
            vh = murmur3_bytes(col.data["dict_offsets"], col.data["dict_bytes"], seed)
            h = vh[col.data["codes"]]
        else:
            flat = flatten_dict_column(col, num_rows)
            h = murmur3_bytes(flat.data["offsets"], flat.data["bytes"], seed)
    elif isinstance(d, T.StringType):
        h = murmur3_bytes(col.data["offsets"], col.data["bytes"], seed)
    elif isinstance(d, T.BooleanType):
        h = murmur3_int32(col.data.astype(jnp.int32), seed)
    elif isinstance(d, (T.Int8Type, T.Int16Type, T.Int32Type, T.DateType)):
        h = murmur3_int32(col.data.astype(jnp.int32), seed)
    elif isinstance(d, T.Float32Type):
        v = jnp.where(col.data == 0.0, jnp.zeros_like(col.data), col.data)  # -0.0 -> +0.0
        h = murmur3_int32(lax.bitcast_convert_type(v, jnp.int32), seed)
    elif isinstance(d, T.Float64Type):
        v = jnp.where(col.data == 0.0, jnp.zeros_like(col.data), col.data)
        h = murmur3_int64(_bitcast_f64_u64(v).astype(jnp.int64), seed)
    else:  # int64, timestamp, decimal64
        h = murmur3_int64(col.data.astype(jnp.int64), seed)
    if live is not None:
        valid = live if col.validity is None else (col.validity & live)
    else:
        valid = col.validity_or_default(num_rows)
    if seed.ndim == 0:
        seed = jnp.broadcast_to(seed, h.shape)
    return jnp.where(valid, h, seed.astype(jnp.uint32))


def spark_murmur3_batch(cols: Sequence[ColumnVector], num_rows: int,
                        seed: int = SPARK_MURMUR3_SEED, live=None) -> jax.Array:
    """Chained per-row hash over columns = Spark Murmur3Hash(cols, 42).
    The seed stays SCALAR until the first column hashes it into a
    vector, so a leading dict-string column takes the vocab-lift path
    instead of flattening."""
    h = jnp.uint32(seed)
    for c in cols:
        h = spark_hash_column(c, num_rows, h, live=live)
    if h.ndim == 0:
        h = jnp.full((cols[0].capacity,), h)
    return h.astype(jnp.int32)


def partition_hash_batch(cols: Sequence[ColumnVector], num_rows: int,
                         seed: int = SPARK_MURMUR3_SEED,
                         live=None) -> jax.Array:
    """Exchange/bucket partitioning hash. Spark murmur3 EXCEPT that a
    dict-string column in a non-leading position mixes its vocab-lifted
    entry hash as an int32 instead of flattening the whole column
    (which is bound-limited inside a trace). NOT Spark-hash-compatible
    for that one case — use only where the hash picks a partition and
    is never user-visible (the reference has the same freedom in its
    internal GpuHashPartitioning)."""
    h = jnp.uint32(seed)
    for c in cols:
        if c.is_dict and h.ndim != 0:
            vh = murmur3_bytes(c.data["dict_offsets"], c.data["dict_bytes"],
                               jnp.uint32(SPARK_MURMUR3_SEED))
            lifted = ColumnVector(
                T.INT32, vh[c.data["codes"]].astype(jnp.int32), c.validity)
            h = spark_hash_column(lifted, num_rows, h, live=live)
        else:
            h = spark_hash_column(c, num_rows, h, live=live)
    if h.ndim == 0:
        h = jnp.full((cols[0].capacity,), h)
    return h.astype(jnp.int32)


# -- xxhash64 (reference jni.Hash.xxhash64) ---------------------------------

_XXP1 = np.uint64(0x9E3779B185EBCA87)
_XXP2 = np.uint64(0xC2B2AE3D27D4EB4F)
_XXP3 = np.uint64(0x165667B19E3779F9)
_XXP5 = np.uint64(0x27D4EB2F165667C5)


def _rotl64(x, r):
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


_XXP4 = np.uint64(0x85EBCA77C2B2AE63)


def _xx_avalanche(h):
    h = (h ^ (h >> np.uint64(33))) * _XXP2
    h = (h ^ (h >> np.uint64(29))) * _XXP3
    return h ^ (h >> np.uint64(32))


def xxhash64_int64(values: jax.Array, seed=42) -> jax.Array:
    """XXH64.hashLong: seed may be a scalar or a per-row uint64 vector
    (Spark chains column hashes through the seed)."""
    v = values.astype(jnp.uint64)
    seed = seed.astype(jnp.uint64) if hasattr(seed, "astype")         else np.uint64(seed)
    h = seed + _XXP5 + np.uint64(8)
    k1 = _rotl64(v * _XXP2, 31) * _XXP1
    h = h ^ k1
    h = _rotl64(h, 27) * _XXP1 + _XXP4
    return _xx_avalanche(h).astype(jnp.int64)


def xxhash64_int32(values: jax.Array, seed=42) -> jax.Array:
    """XXH64.hashInt (Spark uses it for <= 4-byte fixed types)."""
    v = values.astype(jnp.int32).astype(jnp.uint32).astype(jnp.uint64)
    seed = seed.astype(jnp.uint64) if hasattr(seed, "astype")         else np.uint64(seed)
    h = seed + _XXP5 + np.uint64(4)
    h = h ^ (v * _XXP1)
    h = _rotl64(h, 23) * _XXP2 + _XXP3
    return _xx_avalanche(h).astype(jnp.int64)


# ---------------------------------------------------------------------------
# Key normalization: map a column to an order-preserving uint64 plane so
# sorts/joins/groupbys work on uniform fixed-width lanes.
# ---------------------------------------------------------------------------

_SIGN64 = np.uint64(0x8000000000000000)


def normalize_key(col: ColumnVector, num_rows: int,
                  for_order: bool = False, live=None) -> Tuple[jax.Array, jax.Array]:
    """Returns (key_u64, null_flags). Key order matches value order for all
    fixed-width types. Strings get a 64-bit double-hash of the bytes:
    equality-faithful up to astronomically-unlikely collisions, NOT
    order-faithful (string ORDER BY uses string_chunk_keys)."""
    d = col.dtype
    if live is not None:
        valid = live if col.validity is None else (col.validity & live)
    else:
        valid = col.validity_or_default(num_rows)
    if col.is_dict:
        if for_order:
            raise NotImplementedError("device string ordering; use host sort")
        vh1 = murmur3_bytes(col.data["dict_offsets"], col.data["dict_bytes"],
                            jnp.uint32(0x12345671))
        vh2 = murmur3_bytes(col.data["dict_offsets"], col.data["dict_bytes"],
                            jnp.uint32(0x89ABCDE3))
        vkey = (vh1.astype(jnp.uint64) << jnp.uint64(32)) | vh2.astype(jnp.uint64)
        key = vkey[col.data["codes"]]
    elif isinstance(d, T.StringType):
        if for_order:
            raise NotImplementedError("device string ordering; use host sort")
        h1 = murmur3_bytes(col.data["offsets"], col.data["bytes"], jnp.uint32(0x12345671))
        h2 = murmur3_bytes(col.data["offsets"], col.data["bytes"], jnp.uint32(0x89ABCDE3))
        key = (h1.astype(jnp.uint64) << jnp.uint64(32)) | h2.astype(jnp.uint64)
    elif isinstance(d, T.BooleanType):
        key = col.data.astype(jnp.uint64)
    elif isinstance(d, T.Float32Type):
        v = jnp.where(jnp.isnan(col.data), jnp.float32(np.nan), col.data)
        v = jnp.where(v == 0.0, jnp.zeros_like(v), v)
        key = _order_float_bits(lax.bitcast_convert_type(v, jnp.int32).astype(jnp.int64), 32)
    elif isinstance(d, T.Float64Type):
        v = jnp.where(jnp.isnan(col.data), jnp.float64(np.nan), col.data)
        v = jnp.where(v == 0.0, jnp.zeros_like(v), v)
        key = _order_float_bits(_bitcast_f64_u64(v), 64)
    else:
        key = col.data.astype(jnp.int64).astype(jnp.uint64) ^ _SIGN64
    key = jnp.where(valid, key, jnp.uint64(0))
    return key, ~valid


def _chunks_for(nbytes: int) -> int:
    """8-byte chunks covering `nbytes`, rounded up to a power of two to
    bound kernel variants."""
    return round_capacity(max(1, -(-nbytes // 8)), minimum=1)


def static_string_chunks(col: ColumnVector) -> Optional[int]:
    """The chunk count of a string key from what the HOST already knows,
    or None. First the width stamped on the column where the host built
    its planes (ColumnVector.str_width: exact at upload, conservative
    after); then the static shape of a dictionary's byte plane, which
    bounds its longest entry, taken only where it cannot be loose: a
    vocabulary of at most 8 bytes in all needs the one chunk every
    string key has. A wider width than the longest string only adds
    all-zero planes that tie: the order is the same."""
    if col.str_width is not None:
        return _chunks_for(col.str_width)
    if col.is_dict and int(col.data["dict_bytes"].shape[0]) <= 8:
        return 1
    return None


@_cc.jit
def _max_entry_len(off: jax.Array) -> jax.Array:
    return jnp.max(off[1:] - off[:-1])


def string_chunk_count(col: ColumnVector) -> int:
    """Number of 8-byte chunks covering the longest string in the column
    (the longest vocabulary entry of a dict column), rounded up to a
    power of two. The width of a string sort key is static in a trace, so
    it is settled on the host before the program: from
    static_string_chunks where the host knows it (no device access), and
    only otherwise from ONE device scalar read-back (one program, one
    `host_int`): a computed string key, or a column whose stamp was lost
    across an operator that does not carry it. Never call inside jit."""
    n = static_string_chunks(col)
    if n is not None:
        return n
    off = col.data["dict_offsets"] if col.is_dict else col.data["offsets"]
    return _chunks_for(host_int(_max_entry_len(off), "width"))


def string_chunk_keys(col: ColumnVector, num_rows: int, n_chunks: int,
                      live=None) -> List[Tuple[jax.Array, jax.Array]]:
    """EXACT device string ordering: per row, n_chunks u64 keys holding the
    UTF-8 bytes big-endian (zero padded), most-significant chunk first —
    unsigned lexsort over them IS lexicographic byte order (= Spark's
    binary string ordering). Replaces the host string sort; embedded NUL
    bytes tie with end-of-string (documented, vanishingly rare in UTF-8).
    Dict columns build chunk planes over the (small) vocab once and gather
    by code."""
    if live is not None:
        valid = live if col.validity is None else (col.validity & live)
    else:
        valid = col.validity_or_default(num_rows)
    nulls = ~valid
    if col.is_dict:
        off, raw = col.data["dict_offsets"], col.data["dict_bytes"]
    else:
        off, raw = col.data["offsets"], col.data["bytes"]
    starts = off[:-1].astype(jnp.int32)
    ends = off[1:].astype(jnp.int32)
    nbytes = raw.shape[0]
    out = []
    for j in range(n_chunks):
        pos = starts[:, None] + 8 * j + jnp.arange(8, dtype=jnp.int32)[None, :]
        b = jnp.where(pos < ends[:, None],
                      raw[jnp.clip(pos, 0, nbytes - 1)], 0).astype(jnp.uint64)
        shifts = jnp.uint64(8) * (jnp.uint64(7) - jnp.arange(8, dtype=jnp.uint64))
        key = jnp.sum(b << shifts[None, :], axis=1)
        if col.is_dict:
            key = key[col.data["codes"]]
        out.append((key, nulls))
    return out


def _frexp_arith(a: jax.Array):
    """(m, e) with a = m * 2^e, m in [1, 2), for positive normal a —
    computed with comparisons and exact power-of-two multiplies only.
    jnp.frexp internally does a 64-bit bitcast-convert, which the TPU x64
    rewriter cannot lower; this binary-search normalization avoids it.
    Zero/inf/NaN inputs produce garbage m/e that callers mask out."""
    x = a
    e = jnp.zeros(a.shape, jnp.int32)
    for k in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        up = np.float64(2.0) ** k
        c = x >= up
        x = jnp.where(c, x * np.float64(2.0) ** (-k), x)
        e = e + jnp.where(c, k, 0)
    for k in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        up = np.float64(2.0) ** k
        c = (x < 1.0) & (x * up < 2.0)
        x = jnp.where(c, x * up, x)
        e = e - jnp.where(c, k, 0)
    return x, e


def _bitcast_f64_u64(v: jax.Array) -> jax.Array:
    """IEEE-754 f64 bit pattern as u64, ARITHMETICALLY — the TPU x64
    rewriter cannot lower any 64-bit bitcast-convert, so the bits are
    reconstructed by exponent normalization. On backends with true IEEE
    f64 (the CPU simulator) this is bit-exact and matches
    java.lang.Double.doubleToLongBits (canonical NaN), which Spark's
    murmur3 hashes. On TPUs whose x64 mode emulates f64 with f32 pairs
    (~48-bit mantissa, f32 exponent range — upload of |v|>~3.4e38 is
    already inf), exactness vs host f64 is unattainable by ANY function;
    the contract is instead consistency with DEVICE f64 semantics, which
    this construction satisfies: verified on v5e over random samples +
    specials that key order and key equality agree exactly with the
    device's own f64 comparisons (see docs/compatibility.md)."""
    nan = jnp.isnan(v)
    pinf = v == jnp.inf
    ninf = v == -jnp.inf
    zero = v == 0.0
    # sign via compare, not jnp.signbit (which bitcasts internally); -0.0
    # is normalized to +0.0 by callers (Spark normalizes it before hashing)
    sign = jnp.where(v < 0.0, jnp.uint64(1) << jnp.uint64(63), jnp.uint64(0))
    a = jnp.abs(v)
    m, e = _frexp_arith(a)  # a = m * 2^e, m in [1, 2)
    biased = (e + 1023).astype(jnp.int64)
    normal = biased > 0
    mant = (m * np.float64(2.0 ** 52)).astype(jnp.uint64)  # [2^52, 2^53)
    norm_bits = (jnp.where(normal, biased, 0).astype(jnp.uint64)
                 << jnp.uint64(52)) | (mant & ((jnp.uint64(1) << jnp.uint64(52)) - jnp.uint64(1)))
    # Subnormals: XLA flushes them to zero in f64 arithmetic on both the
    # TPU emulation and the CPU backend (FTZ), so they hash/compare as
    # +/-0 here — consistent with every other op in the engine, divergent
    # from Spark CPU only for exact-subnormal inputs (documented incompat,
    # reference keeps a similar float incompat list).
    mag = jnp.where(normal, norm_bits, jnp.uint64(0))
    mag = jnp.where(zero, jnp.uint64(0), mag)
    mag = jnp.where(pinf | ninf, jnp.uint64(0x7FF0000000000000), mag)
    mag = jnp.where(nan, jnp.uint64(0x7FF8000000000000), mag)
    return sign | mag


def _order_float_bits(bits: jax.Array, width: int) -> jax.Array:
    """IEEE total-order transform: negatives flip all bits, positives flip
    the sign bit. NaN (canonicalized, positive payload) sorts above +inf,
    matching Spark's NaN ordering."""
    u = bits.astype(jnp.uint64)
    if width == 32:
        mask = jnp.uint64(0xFFFFFFFF)
        sign = jnp.uint64(0x80000000)
        u = u & mask
        neg = (u & sign) != 0
        return jnp.where(neg, (~u) & mask, u | sign)
    neg = (u & _SIGN64) != 0
    return jnp.where(neg, ~u, u | _SIGN64)


# ---------------------------------------------------------------------------
# Sort / argsort (reference cudf OrderByArg sort)
# ---------------------------------------------------------------------------

#: a lexsort of more operand planes than this runs as one single-key
#: stable sort a plane, last plane first, inside one loop (one sort for the
#: TPU compiler, which takes half a minute over a 39-operand sort of 2,048
#: rows and under a second over the loop), as long as the stacked planes
#: stay under _LEXSORT_LOOP_BYTES: a wide ORDER BY over the few rows a
#: top-N or a ranked window leaves
_LEXSORT_LOOP_PLANES = 8
_LEXSORT_LOOP_BYTES = 1 << 28


def lexsort_indices(keys: List[Tuple[jax.Array, jax.Array, bool, bool]],
                    num_rows: int, live=None) -> jax.Array:
    """Stable lexicographic argsort. keys = [(key_u64, null_flags, ascending,
    nulls_first)]. Dead rows (mask False / >= num_rows) sort to the very
    end. Returns an int32 permutation of the full capacity."""
    cap = keys[0][0].shape[0]
    operands: List[jax.Array] = []
    in_range = live if live is not None else (jnp.arange(cap) < num_rows)
    operands.append(jnp.where(in_range, 0, 1).astype(jnp.uint8))
    for key, nulls, asc, nulls_first in keys:
        # null-ordering plane: 0 sorts before 1
        null_rank = jnp.uint8(0) if nulls_first else jnp.uint8(1)
        val_rank = jnp.uint8(1) if nulls_first else jnp.uint8(0)
        operands.append(jnp.where(nulls, null_rank, val_rank))
        operands.append(key if asc else ~key)
    iota = jnp.arange(cap, dtype=jnp.int32)
    n = len(operands)
    if n > _LEXSORT_LOOP_PLANES and n * cap * 8 <= _LEXSORT_LOOP_BYTES:
        planes = jnp.stack([o.astype(jnp.uint64) for o in operands])

        def by_plane(i, perm):
            return perm[jnp.argsort(planes[n - 1 - i][perm],
                                    stable=True).astype(jnp.int32)]
        return lax.fori_loop(0, n, by_plane, iota)
    out = lax.sort(tuple(operands) + (iota,), num_keys=n, is_stable=True)
    return out[-1]


# ---------------------------------------------------------------------------
# Gather (reference GatherMap + OutOfBoundsPolicy.NULLIFY)
# ---------------------------------------------------------------------------

def gather_column(col: ColumnVector, indices: jax.Array, src_rows: int,
                  src_live=None) -> ColumnVector:
    """Row gather of one column. indices: int32[out_cap]; -1 emits null.
    src_live: liveness plane of the source batch (selection mask); dead
    source rows gather as null."""
    oob = indices < 0
    safe = jnp.clip(indices, 0, col.capacity - 1)
    if src_live is not None:
        src_valid = src_live if col.validity is None else (col.validity & src_live)
    else:
        src_valid = col.validity_or_default(src_rows)
    valid = src_valid[safe] & ~oob
    if col.is_string and not col.is_dict:
        # Flat strings gather as an identity-coded dictionary (zero-copy
        # reinterpretation: vocab = the source planes themselves). A
        # byte-plane gather cannot duplicate rows without growing past the
        # static byte capacity — code gather sidesteps that entirely. The
        # code of row i is i: the gathered codes are the indices.
        col = flat_string_as_dict(col)
        data = {"codes": safe.astype(jnp.int32),
                "dict_offsets": col.data["dict_offsets"],
                "dict_bytes": col.data["dict_bytes"]}
        return ColumnVector(col.dtype, data, valid, dict_unique=col.dict_unique,
                            str_width=col.str_width)
    if col.is_dict:
        # dict strings gather as integer codes; the vocab is shared.
        data = {"codes": col.data["codes"][safe],
                "dict_offsets": col.data["dict_offsets"],
                "dict_bytes": col.data["dict_bytes"]}
        return ColumnVector(col.dtype, data, valid, dict_unique=col.dict_unique,
                            str_width=col.str_width)
    if isinstance(col.dtype, T.StructType):
        kids = [gather_column(ch, indices, src_rows, src_live=src_live)
                for ch in col.data["children"]]
        return ColumnVector(col.dtype, {"children": kids}, valid)
    if isinstance(col.dtype, (T.ArrayType, T.MapType)):
        return _gather_list_like(col, safe, valid)
    data = col.data[safe]
    return ColumnVector(col.dtype, data, valid, bounds=col.bounds)


def _gather_list_like(col: ColumnVector, safe: jax.Array, valid: jax.Array
                      ) -> ColumnVector:
    """Gather an array/map column: rebuild offsets from gathered lengths,
    then map each output element back to its source element and gather the
    child planes. Child capacity is preserved — PERMUTING gathers (sort,
    filter compaction, explode passthrough) never grow the element count;
    row-DUPLICATING gathers of nested columns (join payload) are excluded
    by TypeSig until a sized nested gather lands."""
    off = col.data["offsets"]
    out_cap = safe.shape[0]
    lens = jnp.where(valid, (off[1:] - off[:-1])[safe], 0)
    new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
    children = ([("child", col.data["child"])] if "child" in col.data
                else [("keys", col.data["keys"]), ("values", col.data["values"])])
    child_cap = children[0][1].capacity
    e = jnp.arange(child_cap, dtype=jnp.int32)
    orow = jnp.clip(jnp.searchsorted(new_off, e, side="right").astype(jnp.int32) - 1,
                    0, out_cap - 1)
    src_e = off[safe[orow]] + (e - new_off[orow])
    in_range = e < new_off[-1]
    child_idx = jnp.where(in_range, jnp.clip(src_e, 0, child_cap - 1), -1)
    data = {"offsets": new_off}
    for name, ch in children:
        data[name] = gather_column(ch, child_idx, child_cap)
    return ColumnVector(col.dtype, data, valid)


def flat_string_as_dict(col: ColumnVector) -> ColumnVector:
    """Reinterpret a flat offsets+bytes string column as a dictionary
    column with identity codes. Zero-copy: the vocab IS the source planes.
    Source rows may repeat values, so dict_unique is False unless the host
    that uploaded the column saw its strings distinct (flat_distinct: the
    entries a null row points at repeat "", and no valid row's code is
    one of them). The vocab keeps the
    full source byte plane alive regardless of how few codes survive
    downstream — acceptable: gather outputs share source lifetime anyway."""
    if col.is_dict or not col.is_string:
        return col
    cap = col.capacity
    data = {"codes": jnp.arange(cap, dtype=jnp.int32),
            "dict_offsets": col.data["offsets"],
            "dict_bytes": col.data["bytes"]}
    return ColumnVector(col.dtype, data, col.validity,
                        dict_unique=col.flat_distinct,
                        str_width=col.str_width)


def canonical_dict_codes(col: ColumnVector) -> ColumnVector:
    """A dict-string column whose codes are equal exactly where its
    strings are: every code becomes the smallest code of an entry with the
    same bytes. A vocabulary that came from an upload is unique already
    (`dict_unique`) and the column comes back as it is; one that is a flat
    column seen as its own dictionary (flat_string_as_dict, what a gather
    leaves) repeats an entry wherever the strings repeat. Entries are
    compared by normalize_key's 64-bit double hash, as every grouping of
    strings in the engine is. The vocabulary is untouched."""
    if not col.is_dict or col.dict_unique:
        return col
    off, raw = col.data["dict_offsets"], col.data["dict_bytes"]
    h = (murmur3_bytes(off, raw, jnp.uint32(0x12345671)).astype(jnp.uint64)
         << jnp.uint64(32)) | murmur3_bytes(
        off, raw, jnp.uint32(0x89ABCDE3)).astype(jnp.uint64)
    n = h.shape[0]
    order = jnp.argsort(h, stable=True).astype(jnp.int32)
    hs = h[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), hs[1:] != hs[:-1]])
    smallest = order[lax.cummax(jnp.where(first, idx, 0))]
    canon = jnp.zeros(n, jnp.int32).at[order].set(smallest)
    codes = canon[jnp.clip(col.data["codes"], 0, n - 1)]
    return ColumnVector(col.dtype, {"codes": codes, "dict_offsets": off,
                                    "dict_bytes": raw}, col.validity,
                        dict_unique=False, str_width=col.str_width)


class LazyGatheredCols:
    """A column list view that gathers source columns by a shared index
    plane ON FIRST ACCESS (memoized). Lambda bodies (expr/hof) and window
    functions (exec/tpu_nodes) evaluate over reindexed row spaces where
    most columns are never read — a 16M-row gather costs ~200ms, so
    laziness is worth real wall-clock, and XLA CSEs the duplicate index
    arithmetic for the columns that ARE read."""

    def __init__(self, cols, indices, num_rows):
        self._cols = cols
        self._idx = indices
        self._rows = num_rows
        self._cache = {}

    def __len__(self):
        return len(self._cols)

    def __getitem__(self, i):
        out = self._cache.get(i)
        if out is None:
            out = gather_column(self._cols[i], self._idx, self._rows)
            self._cache[i] = out
        return out

    def __iter__(self):
        return (self[i] for i in range(len(self._cols)))


#: an integer column rides in a packed word when its values' span takes
#: at most this many bits
_PACKED_FIELD_BITS = 40


def gather_plan(cols: Sequence[ColumnVector]) -> tuple:
    """What a gather of these columns can pack, from what the HOST knows
    of them (a plan is static in a trace and part of its key): per column
    (lo, bits) where its values are small non-negative codes once `lo` is
    taken off (dictionary codes, booleans, integers and dates with column
    stats), else None. A random gather costs the chip 9 to 16 ms a
    million rows a plane, data and validity alike and more from a large
    source, whatever the plane's width up to 8 bytes: gather_columns packs
    the small columns and every column's validity bit into a few words
    and gathers those."""
    plan = []
    for c in cols:
        if c.is_dict:
            plan.append((0, max(int(c.dict_size) - 1, 1).bit_length()))
        elif c.is_string or c.is_nested:
            plan.append(None)   # a flat string's codes are the indices
        elif isinstance(c.dtype, T.BooleanType):
            plan.append((0, 1))
        elif c.bounds is not None and np.dtype(c.dtype.np_dtype).kind == "i" \
                and (int(c.bounds[1]) - int(c.bounds[0])).bit_length() \
                <= _PACKED_FIELD_BITS:
            plan.append((int(c.bounds[0]), max(
                (int(c.bounds[1]) - int(c.bounds[0])).bit_length(), 1)))
        else:
            plan.append(None)
    return tuple(plan)


def gather_columns(cols: Sequence[ColumnVector], indices: jax.Array,
                   src_rows, src_live=None, plan: Optional[tuple] = None
                   ) -> List[ColumnVector]:
    """gather_column of every column, the same rows of each. With a
    `plan` (gather_plan) the columns it names and the validity of all of
    them travel in packed words: one gather a word, and one more a column
    the plan could not pack."""
    if plan is None or not cols:
        return [gather_column(c, indices, src_rows, src_live=src_live)
                for c in cols]
    cap = cols[0].capacity
    oob = indices < 0
    safe = jnp.clip(indices, 0, cap - 1)
    # fields: a column's code (if planned) then its validity bit, laid into
    # words of at most 64 bits, first fit
    words: List[List] = []   # [(column, shift, bits, is_validity)]
    used: List[int] = []
    for i, (c, p) in enumerate(zip(cols, plan)):
        if c.is_nested:
            continue
        need = (p[1] if p is not None else 0) + 1
        w = next((k for k, u in enumerate(used) if u + need <= 64), None)
        if w is None:
            words.append([])
            used.append(0)
            w = len(words) - 1
        if p is not None:
            words[w].append((i, used[w], p[1], False))
        words[w].append((i, used[w] + need - 1, 1, True))
        used[w] += need
    got = {}
    for fields, total in zip(words, used):
        wide = total > 32
        dt = jnp.uint64 if wide else jnp.uint32
        word = jnp.zeros(cap, dt)
        for i, shift, bits, is_valid in fields:
            c = cols[i]
            if is_valid:
                if src_live is not None:
                    v = src_live if c.validity is None \
                        else (c.validity & src_live)
                else:
                    v = c.validity_or_default(src_rows)
                code = v.astype(dt)
            else:
                raw = c.data["codes"] if c.is_dict else c.data
                code = (raw.astype(jnp.int64) - plan[i][0]).astype(dt) \
                    & dt((1 << bits) - 1)
            word = word | (code << dt(shift))
        word = word[safe]
        for i, shift, bits, is_valid in fields:
            code = (word >> dt(shift)) & dt((1 << bits) - 1)
            got[(i, is_valid)] = code
    out = []
    for i, (c, p) in enumerate(zip(cols, plan)):
        if c.is_nested:
            out.append(gather_column(c, indices, src_rows, src_live=src_live))
            continue
        valid = got[(i, True)].astype(jnp.bool_) & ~oob
        if p is None:
            plain = gather_column(c, indices, src_rows, src_live=src_live)
            out.append(dataclasses.replace(plain, validity=valid))
            continue
        code = got[(i, False)]
        if c.is_dict:
            data = {"codes": code.astype(jnp.int32),
                    "dict_offsets": c.data["dict_offsets"],
                    "dict_bytes": c.data["dict_bytes"]}
            out.append(ColumnVector(c.dtype, data, valid,
                                    dict_unique=c.dict_unique,
                                    str_width=c.str_width))
        else:
            data = (code.astype(jnp.int64) + p[0]).astype(c.data.dtype)
            out.append(ColumnVector(c.dtype, data, valid, bounds=c.bounds))
    return out


def gather_batch(batch: ColumnarBatch, indices: jax.Array, out_rows: int,
                 plan: Optional[tuple] = None) -> ColumnarBatch:
    live = batch.live_mask() if batch.row_mask is not None else None
    return ColumnarBatch(gather_columns(batch.columns, indices,
                                        batch.num_rows, src_live=live,
                                        plan=plan), out_rows)


# ---------------------------------------------------------------------------
# Filter: count-then-gather compaction
# ---------------------------------------------------------------------------

@_cc.jit
def _count_true(mask: jax.Array, num_rows) -> jax.Array:
    cap = mask.shape[0]
    return jnp.sum((mask & (jnp.arange(cap) < num_rows)).astype(jnp.int32))


@_cc.jit(static_argnums=(2,))
def _compact_indices(mask: jax.Array, num_rows, out_cap: int) -> jax.Array:
    """The positions of the set entries among the first `num_rows` of
    `mask`, in order, -1 behind them. Found 32 rows at a time: the mask
    packs into u32 words, each word's first output slot is the prefix sum
    of the words' popcounts, one scatter of a word's (index, start, bits)
    spreads it over its slots (`_per_row`), and slot j takes the
    (j - start)-th set bit of its word. A scatter of every row costs some
    5 ns an update on a v5e; this scatters cap/32. The prefix sums are
    the two-level ones: the TPU compiler takes a minute over a flat one
    of millions of rows."""
    _cc.note_traced("compact_words_traced")
    nw = -(-mask.shape[0] // 32)
    # the barrier keeps the word-shaped relayout on the one-byte mask:
    # XLA would widen it to u32 first and write four times the bytes
    bits = lax.optimization_barrier(
        jnp.pad(mask, (0, nw * 32 - mask.shape[0])).reshape(nw, 32))
    bits = bits & (jnp.arange(nw * 32).reshape(nw, 32) < num_rows)
    words = jnp.sum(bits.astype(jnp.uint32)
                    << jnp.arange(32, dtype=jnp.uint32), axis=1,
                    dtype=jnp.uint32)
    pc = lax.population_count(words).astype(jnp.int32)
    start = _cumsum(pc) - pc
    total = jnp.sum(pc)
    # one scatter a plane: on a v5e one scatter of [3, n] rows cost 40 ns
    # an update, three of [n] 18 ns each; the bits plane rides the int32
    # prefix sum, exact modulo 2**32
    word, first, b = (_per_row(start, t, out_cap) for t in (
        jnp.arange(nw, dtype=jnp.int32), start,
        lax.bitcast_convert_type(words, jnp.int32)))
    b = lax.bitcast_convert_type(b, jnp.uint32)
    j = jnp.arange(out_cap, dtype=jnp.int32)
    r = (j - first).astype(jnp.uint32)
    bit = jnp.zeros(out_cap, jnp.int32)
    for width in (16, 8, 4, 2, 1):
        low = b & jnp.uint32((1 << width) - 1)
        c = lax.population_count(low)
        high = r >= c
        r = jnp.where(high, r - c, r)
        b = jnp.where(high, b >> width, low)
        bit = bit + jnp.where(high, width, 0)
    return jnp.where(j < total, word * 32 + bit, -1)


def filter_indices(mask: jax.Array, num_rows: int) -> Tuple[jax.Array, int]:
    """mask: bool[capacity]. One device->host scalar readback for the count
    (the price of a dynamic result size; paid per batch, not per element)."""
    count = host_int(_count_true(mask, num_rows), "size")
    out_cap = round_capacity(max(count, 1))
    return _compact_indices(mask, num_rows, out_cap), count


def filter_batch(batch: ColumnarBatch, mask: jax.Array) -> ColumnarBatch:
    idx, count = filter_indices(mask, batch.num_rows)
    return gather_batch(batch, idx, count)


def mask_filter_batch(batch: ColumnarBatch, pred_mask: jax.Array) -> ColumnarBatch:
    """The hot-path filter: NO gather, NO host sync. Survivors are marked in
    a selection mask (row_mask); the count stays on device as a
    LazyRowCount. The reference's GpuFilterExec compacts eagerly with a
    cudf kernel and a stream sync — on TPU a full-size gather costs more
    than every downstream op combined, while a mask fuses into them."""
    live = batch.live_mask() & pred_mask
    count = jnp.sum(live.astype(jnp.int32))
    return ColumnarBatch(batch.columns, LazyRowCount(count), live)


@_cc.jit(static_argnums=(1, 2))
def _compact_gather(batch: ColumnarBatch, out_cap: int, plan: tuple):
    idx = _compact_indices(batch.row_mask, batch.capacity, out_cap)
    return gather_batch(batch, idx, batch.num_rows, plan=plan).columns


def compact_batch(batch: ColumnarBatch) -> ColumnarBatch:
    """Gather live rows to the front and drop the selection mask (for
    consumers that need contiguous rows: sort output, host hand-off,
    not-yet-mask-aware operators). Costs one count sync, which sizes the
    output, and ONE program an output capacity: the live rows' positions
    and the gather of every column trace together, the small columns and
    the validity bits in packed words (gather_plan)."""
    if batch.row_mask is None:
        return shrink_batch(batch)
    n = int(batch.num_rows)
    cols = _compact_gather(batch, round_capacity(n),
                           gather_plan(batch.columns))
    carry_host_stats(batch.columns, cols)
    return ColumnarBatch(cols, n)


# ---------------------------------------------------------------------------
# Slice / concat (reference cudf Table.concatenate / contiguous split)
# ---------------------------------------------------------------------------

@_cc.jit(static_argnums=(1,))
def _shrink_gather(batch, new_cap: int):
    n = traced_rows(batch.num_rows)
    idx = jnp.arange(new_cap, dtype=jnp.int32)
    idx = jnp.where(idx < n, idx, -1)
    return gather_batch(batch, idx, batch.num_rows)


def shrink_batch(batch: ColumnarBatch) -> ColumnarBatch:
    """Compact a batch whose capacity far exceeds its row count (the shrink
    point for deferred-count operators). Materializes a lazy count (one
    round trip) — call once per stage output, never per input batch."""
    n = int(batch.num_rows)
    new_cap = round_capacity(n)
    if new_cap >= batch.capacity:
        return ColumnarBatch(batch.columns, n)
    out = _shrink_gather(batch, new_cap)
    return ColumnarBatch(out.columns, n)


def slice_batch(batch: ColumnarBatch, start: int, length: int) -> ColumnarBatch:
    out_cap = round_capacity(max(length, 1))
    idx = jnp.arange(out_cap, dtype=jnp.int32) + start
    idx = jnp.where(jnp.arange(out_cap) < length, idx, -1)
    return gather_batch(batch, idx, length)


def flatten_dict_column(col: ColumnVector, num_rows) -> ColumnVector:
    """Dict-encoded string -> flat offsets+bytes. The payload EXPANDS
    (repeated codes repeat their vocab entry), so the output byte plane is
    sized by the expansion: exactly when called eagerly (one scalar sync),
    by the static bound rows*vocab_bytes inside a trace."""
    voff = col.data["dict_offsets"]
    vraw = col.data["dict_bytes"]
    codes = col.data["codes"].astype(jnp.int32)
    valid = col.validity
    cap = int(codes.shape[0])
    vlens = voff[1:] - voff[:-1]
    lens = vlens[jnp.clip(codes, 0, vlens.shape[0] - 1)]
    if valid is not None:
        lens = jnp.where(valid, lens, 0)
    new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
    import jax.core as _core
    if isinstance(new_off, jax.Array) and not isinstance(new_off, _core.Tracer):
        out_cap = round_capacity(max(host_int(new_off[-1], "width"), 1))
    else:
        _cc.note_traced("dict_flattens_traced")
        out_cap = cap * int(vraw.shape[0])
        if out_cap > (1 << 28):
            raise NotImplementedError(
                "flattening a large dict string column inside a traced "
                "kernel (bound > 256MB); restructure via the vocab lift")
    starts = voff[jnp.clip(codes, 0, vlens.shape[0] - 1)]
    b = jnp.arange(out_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_off, b, side="right").astype(jnp.int32) - 1,
                   0, cap - 1)
    src = jnp.clip(starts[row] + (b - new_off[row]), 0, int(vraw.shape[0]) - 1)
    out_bytes = jnp.where(b < new_off[-1], vraw[src], 0).astype(jnp.uint8)
    return ColumnVector(col.dtype, {"offsets": new_off, "bytes": out_bytes},
                        col.validity, str_width=col.str_width)


def _shared_vocab(cols: List[ColumnVector]) -> bool:
    """One vocabulary OBJECT under all of these dictionary columns: the
    only sameness that costs no read of the planes."""
    d0 = cols[0].data
    return all(c.data["dict_offsets"] is d0["dict_offsets"]
               and c.data["dict_bytes"] is d0["dict_bytes"]
               for c in cols[1:])


def concat_batches(batches: List[ColumnarBatch]) -> ColumnarBatch:
    materialize_counts(batches)  # one bulk fetch, not one sync per batch
    masked = any(b.row_mask is not None for b in batches)
    nonempty = [b for b in batches if b.num_rows > 0]
    if not nonempty:
        return batches[0]
    if len(nonempty) == 1:
        return nonempty[0]
    total = sum(int(b.num_rows) for b in nonempty)
    if masked:
        # Selection-mask mode: stack FULL planes and concatenate masks — no
        # gather, no per-row work. Capacity grows to the sum of inputs; the
        # consumer (or an explicit compact) shrinks when worthwhile.
        mask = jnp.concatenate([b.live_mask() for b in nonempty])
        rows = [b.capacity for b in nonempty]
        cap = sum(rows)
    else:
        mask = None
        rows = [int(b.num_rows) for b in nonempty]
        cap = round_capacity(total)
    by_col = [[b.columns[ci] for b in nonempty]
              for ci in range(nonempty[0].num_cols)]
    ends = _offset_ends(by_col, rows)
    out_cols = [_concat_columns(cols, rows, cap, ends.get(ci))
                for ci, cols in enumerate(by_col)]
    return ColumnarBatch(out_cols, total, mask)


def _ends_of(cols: List[ColumnVector]) -> bool:
    """Does concatenating these parts need each part's offsets[rows]
    from the host: a flat string column, or an array or map column."""
    dtype = cols[0].dtype
    if isinstance(dtype, T.StringType):
        return not any(c.is_dict for c in cols)
    return isinstance(dtype, (T.ArrayType, T.MapType))


def _read_ends(by_col, rows) -> List[List[int]]:
    """offsets[r] of every part of every column in `by_col`: ONE read."""
    ends = host_planes([[c.data["offsets"][r] for c, r in zip(cols, rows)]
                        for cols in by_col], "width")
    return [[int(e) for e in col_ends] for col_ends in ends]


def _offset_ends(by_col, rows) -> dict:
    """{column index: each part's offsets[r]} for the columns of a concat
    whose destination offsets are static (flat strings, arrays, maps),
    read together: one sync for all of them, not one a column."""
    want = [ci for ci, cols in enumerate(by_col) if _ends_of(cols)]
    if not want:
        return {}
    return dict(zip(want, _read_ends([by_col[ci] for ci in want], rows)))


def _on_host(plane) -> bool:
    return plane is None or isinstance(plane, np.ndarray)


def concat_host_batches(batches: List[ColumnarBatch],
                        limit: Optional[int] = None
                        ) -> Optional[ColumnarBatch]:
    """`batches` as ONE compact batch (no mask, a host-int row count)
    assembled by numpy in source order, or None where that would cost a
    sync or a program: a row count still on the device, a plane that is
    not numpy, a flat-string or nested column, dictionary columns whose
    vocabulary is not one object (equal strings must stay one code), or,
    where a limit is given, more than `limit` rows in all. What a sharded
    stage's read-back (exec/sharded.MeshWave.read_back) hands on is
    exactly this shape, and a few rows of it are cheaper to lay together
    here than through concat_batches' eager slices and concatenates. The
    planes stay numpy: the consumer's jitted kernel uploads them."""
    if not batches:
        return None
    live = 0
    for b in batches:
        if not isinstance(b.num_rows, int) or not _on_host(b.row_mask):
            return None
        live += b.num_rows
    if limit is not None and live > limit:
        return None
    by_col = [[b.columns[ci] for b in batches]
              for ci in range(batches[0].num_cols)]
    for parts in by_col:
        if all(c.is_dict for c in parts):
            if not _shared_vocab(parts):
                return None
            planes = [c.data["codes"] for c in parts]
        elif any(isinstance(c.data, dict) for c in parts):
            return None
        else:
            planes = [c.data for c in parts]
        if not all(isinstance(p, np.ndarray) for p in planes) \
                or not all(_on_host(c.validity) for c in parts):
            return None
    # the rows each source keeps, in its own order (a masked batch's
    # count is its mask's)
    keep = [slice(0, b.num_rows) if b.row_mask is None else b.row_mask
            for b in batches]
    cap = round_capacity(max(live, 1))

    def laid(planes, dtype):
        out = np.zeros(cap, dtype)
        out[:live] = np.concatenate([p[k] for p, k in zip(planes, keep)])
        return out

    cols = []
    for parts in by_col:
        c0 = parts[0]
        validity = laid([np.ones(c.capacity, np.bool_) if c.validity is None
                         else c.validity for c in parts], np.bool_)
        if c0.is_dict:
            codes = laid([c.data["codes"] for c in parts],
                         c0.data["codes"].dtype)
            cols.append(ColumnVector(
                c0.dtype, {"codes": codes,
                           "dict_offsets": c0.data["dict_offsets"],
                           "dict_bytes": c0.data["dict_bytes"]}, validity,
                dict_unique=all(c.dict_unique for c in parts),
                str_width=_union_width(parts)))
        else:
            cols.append(ColumnVector(
                c0.dtype, laid([c.data for c in parts], c0.data.dtype),
                validity, bounds=_union_bounds(parts)))
    return ColumnarBatch(cols, live)


def _union_bounds(cols: List[ColumnVector]):
    """Conservative (min, max) union across concat inputs; None if any
    input lacks bounds (host metadata — see ColumnVector.bounds)."""
    bs = [c.bounds for c in cols]
    if any(b is None for b in bs):
        return None
    return (min(b[0] for b in bs), max(b[1] for b in bs))


def _union_width(cols: List[ColumnVector]) -> Optional[int]:
    """Longest string over concat inputs; None if any input lacks the
    stamp (host metadata: see ColumnVector.str_width)."""
    ws = [c.str_width for c in cols]
    return None if any(w is None for w in ws) else max(ws)


def unify_vocabs(cols: List[ColumnVector]):
    """Union the vocabularies of several dict-string columns host-side.
    Returns (union_offsets np.int32[k+1], union_bytes np.uint8[m],
    per-column code remaps). Equal strings map to ONE union code, so
    code-identity reasoning (bucket agg, ICI fixed-width exchange) stays
    sound across the inputs."""
    vocab_planes = []
    for c in cols:
        vocab_planes.extend([c.data["dict_offsets"], c.data["dict_bytes"]])
    host = host_planes(vocab_planes, "vocab")
    union: dict = {}
    remaps = []
    for i in range(len(cols)):
        off, by = np.asarray(host[2 * i]), np.asarray(host[2 * i + 1])
        remap = np.zeros(len(off) - 1, np.int32)
        for k in range(len(off) - 1):
            sv = bytes(by[off[k]: off[k + 1]])
            if sv not in union:
                union[sv] = len(union)
            remap[k] = union[sv]
        remaps.append(remap)
    ub = b"".join(union.keys())
    uoff = np.zeros(len(union) + 1, np.int32)
    uoff[1:] = np.cumsum([len(sv) for sv in union.keys()])
    ubytes = np.frombuffer(ub, np.uint8) if ub else np.zeros(1, np.uint8)
    return uoff, np.ascontiguousarray(ubytes), remaps


def align_dict_columns(cols: List[ColumnVector]) -> List[ColumnVector]:
    """NEW dict columns whose codes index ONE shared union vocabulary
    (inputs untouched). No-op (returns the same objects) when the vocab
    planes are already identical."""
    if _shared_vocab(cols):
        return list(cols)
    uoff, ubytes, remaps = unify_vocabs(cols)
    doff = jnp.asarray(uoff)
    dby = jnp.asarray(ubytes)
    width = max_entry_len(uoff)
    out = []
    for c, remap in zip(cols, remaps):
        codes = jnp.asarray(remap)[jnp.clip(c.data["codes"], 0,
                                            len(remap) - 1)]
        out.append(ColumnVector(c.dtype,
                                {"codes": codes, "dict_offsets": doff,
                                 "dict_bytes": dby}, c.validity,
                                dict_unique=True, str_width=width))
    return out


@_cc.jit(donate_argnums=(0,))
def _lay_at(plane: jax.Array, part: jax.Array, at) -> jax.Array:
    """`part` written into `plane` from position `at`, in place (the
    plane is donated: a 1 GiB byte plane is not copied once a part)."""
    return jax.lax.dynamic_update_slice(plane, part, (at,))


def _concat_columns(cols: List[ColumnVector], rows: List[int], cap: int,
                    ends: Optional[List[int]] = None) -> ColumnVector:
    """The parts' live rows as one column of capacity `cap`. `ends`: each
    part's offsets[r] where the caller read them already (concat_batches
    reads every column's at once)."""
    dtype = cols[0].dtype
    if any(c.is_dict for c in cols) and not all(c.is_dict for c in cols):
        cols = [flatten_dict_column(c, r) if c.is_dict else c
                for c, r in zip(cols, rows)]
    validity = jnp.concatenate([c.validity_or_default(r)[:r] for c, r in zip(cols, rows)])
    pad = cap - validity.shape[0]
    if pad > 0:
        validity = jnp.concatenate([validity, jnp.zeros(pad, jnp.bool_)])

    if all(c.is_dict for c in cols):
        if _shared_vocab(cols):
            codes = jnp.concatenate([c.data["codes"][:r] for c, r in zip(cols, rows)])
            if pad > 0:
                codes = jnp.concatenate([codes, jnp.zeros(pad, codes.dtype)])
            return ColumnVector(dtype, {"codes": codes,
                                        "dict_offsets": cols[0].data["dict_offsets"],
                                        "dict_bytes": cols[0].data["dict_bytes"]},
                                validity,
                                dict_unique=all(c.dict_unique for c in cols),
                                str_width=_union_width(cols))
        # Distinct vocab objects: UNIFY host-side (vocabs are small; this
        # runs at eager concat boundaries only). Equal strings must map to
        # one code — duplicated vocab entries would make "unique bucket"
        # reasoning (bucketed agg, merge-skip) silently wrong.
        uoff, ubytes, remaps = unify_vocabs(cols)
        code_parts = [jnp.asarray(remap)[c.data["codes"][:r]]
                      for c, r, remap in zip(cols, rows, remaps)]
        codes = jnp.concatenate(code_parts)
        if pad > 0:
            codes = jnp.concatenate([codes, jnp.zeros(pad, codes.dtype)])
        return ColumnVector(dtype, {"codes": codes,
                                    "dict_offsets": jnp.asarray(uoff),
                                    "dict_bytes": jnp.asarray(ubytes)},
                            validity, str_width=max_entry_len(uoff))

    if isinstance(dtype, T.StructType):
        kids = []
        for k in range(len(cols[0].data["children"])):
            kids.append(_concat_columns([c.data["children"][k] for c in cols],
                                        rows, cap))
        return ColumnVector(dtype, {"children": kids}, validity)

    if isinstance(dtype, (T.ArrayType, T.MapType)):
        # Host readback of per-part element counts keeps destination
        # offsets static (same discipline as string concat below); child
        # planes concat recursively, so arrays of strings/structs compose.
        elem_lens = ends if ends is not None else \
            _read_ends([cols], rows)[0]
        total_elems = sum(elem_lens)
        child_cap = round_capacity(max(total_elems, 1))
        off_parts = [jnp.zeros(1, jnp.int32)]
        base = 0
        for c, r, el in zip(cols, rows, elem_lens):
            off_parts.append(c.data["offsets"][1: r + 1].astype(jnp.int32)
                             + np.int32(base))
            base += el
        offsets = jnp.concatenate(off_parts)
        if cap + 1 - offsets.shape[0] > 0:
            offsets = jnp.concatenate([
                offsets, jnp.full(cap + 1 - offsets.shape[0], base, jnp.int32)])
        names = ["child"] if "child" in cols[0].data else ["keys", "values"]
        data = {"offsets": offsets}
        for nm in names:
            data[nm] = _concat_columns([c.data[nm] for c in cols],
                                       elem_lens, child_cap)
        return ColumnVector(dtype, data, validity)

    if isinstance(dtype, T.StringType):
        # Host readback of per-part byte lengths keeps destination offsets
        # static; concat happens between batches, off the jitted hot path.
        byte_lens = ends if ends is not None else \
            _read_ends([cols], rows)[0]
        total_bytes = sum(byte_lens)
        out_byte_cap = round_capacity(max(total_bytes, 1))
        out_bytes = jnp.zeros(out_byte_cap, jnp.uint8)
        off_parts = [jnp.zeros(1, jnp.int32)]
        base_rows = 0
        base_bytes = 0
        for c, r, blen in zip(cols, rows, byte_lens):
            o = c.data["offsets"]
            off_parts.append(o[1: r + 1].astype(jnp.int32) + np.int32(base_bytes))
            src = c.data["bytes"]
            # the part's plane laid in place, one copy: what lies behind
            # its live bytes is the next part's to overwrite, or dead
            # bytes behind the last; a part whose dead tail has no room
            # is cut to what is left (its live bytes fit: the plane holds
            # total_bytes)
            room = out_byte_cap - base_bytes
            out_bytes = _lay_at(out_bytes,
                                src if src.shape[0] <= room else src[:room],
                                jnp.int32(base_bytes))
            base_rows += r
            base_bytes += blen
        offsets = jnp.concatenate(off_parts)
        opad = cap + 1 - offsets.shape[0]
        if opad > 0:
            offsets = jnp.concatenate([offsets, jnp.broadcast_to(offsets[-1:], (opad,))])
        return ColumnVector(dtype, {"offsets": offsets, "bytes": out_bytes}, validity,
                            str_width=_union_width(cols),
                            str_bytes=total_bytes)

    merged = jnp.concatenate([c.data[:r] for c, r in zip(cols, rows)])
    if cap - merged.shape[0] > 0:
        merged = jnp.concatenate([merged, jnp.zeros(cap - merged.shape[0], merged.dtype)])
    return ColumnVector(dtype, merged, validity, bounds=_union_bounds(cols))


# ---------------------------------------------------------------------------
# Join candidate expansion (count-then-gather; the JoinGatherer analog)
# ---------------------------------------------------------------------------

def expand_ranges(lo: jax.Array, hi: jax.Array, total: int) -> Tuple[jax.Array, jax.Array]:
    """Given per-probe candidate ranges [lo_i, hi_i) into a sorted build side,
    emit flat (probe_idx, build_pos) pairs. total = sum(hi-lo), a host scalar.
    Tail entries (>= total) are -1."""
    out_cap = round_capacity(max(total, 1))
    counts = (hi - lo).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
    r = jnp.arange(out_cap, dtype=jnp.int32)
    probe = jnp.searchsorted(offsets, r, side="right").astype(jnp.int32) - 1
    probe = jnp.clip(probe, 0, lo.shape[0] - 1)
    pos = lo[probe] + (r - offsets[probe])
    in_range = r < total
    return jnp.where(in_range, probe, -1), jnp.where(in_range, pos, -1)
