"""Sort-based segmented groupby kernels.

Reference parity: cudf GroupByAggregation (hash-based on GPU). The
TPU-idiomatic formulation is sort-based: normalize keys to uint64 planes,
stable-sort, derive segment ids from key boundaries, then apply
jax.ops.segment_* reductions with a static segment capacity. Sorting keys
also gives deterministic float aggregation order (the reference needs
special handling for that; we get it for free).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnVector, ColumnarBatch, host_int, round_capacity,
)
from spark_rapids_tpu.ops import kernels as K


def group_segments(key_cols: List[ColumnVector], num_rows: int, live=None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort rows by the group keys. Returns (perm, seg_ids, seg_starts_mask)
    over the full capacity, where perm is the sorting permutation, seg_ids
    assigns each sorted position a dense group id (padded rows get id
    capacity-1... they share the trailing group but are masked by callers),
    and seg_starts_mask flags the first sorted row of each group."""
    from spark_rapids_tpu.columnar.batch import traced_rows
    nr = traced_rows(num_rows)
    norm = [K.normalize_key(c, num_rows, live=live) for c in key_cols]
    perm = K.lexsort_indices([(k, n, True, True) for k, n in norm], nr, live=live)
    cap = perm.shape[0]
    in_range = (jnp.arange(cap) < nr) if live is None else live[perm]
    boundary = jnp.zeros(cap, jnp.bool_).at[0].set(True)
    for k, nulls in norm:
        ks = k[perm]
        ns = nulls[perm]
        diff = jnp.concatenate([jnp.ones(1, jnp.bool_),
                                (ks[1:] != ks[:-1]) | (ns[1:] != ns[:-1])])
        boundary = boundary | diff
    boundary = boundary & in_range
    seg_ids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg_ids = jnp.where(in_range, seg_ids, cap - 1)
    return perm, seg_ids, boundary


def num_groups(boundary: jax.Array) -> int:
    return host_int(jnp.sum(boundary.astype(jnp.int32)))


def _float_minmax_prep(op: str, values: jax.Array, valid: jax.Array):
    """Spark float min/max semantics WITHOUT 64-bit bitcasts (the TPU x64
    rewriter cannot lower f64<->s64 bitcast-convert): NaN sorts above
    +inf and all NaNs are equal; -0.0 == 0.0. Returns (clean_plane,
    nan_flag, nonnan_flag): reduce clean_plane with plain min/max, then
    patch groups via the flags — max is NaN if any valid NaN; min is NaN
    only when no valid non-NaN value exists."""
    isnan = jnp.isnan(values)
    sentinel = jnp.array(np.inf if op == "min" else -np.inf, values.dtype)
    clean = jnp.where(values == 0.0, jnp.zeros_like(values), values)
    clean = jnp.where(valid & ~isnan, clean, jnp.full_like(values, sentinel))
    return clean, (valid & isnan), (valid & ~isnan)


def _float_minmax_patch(op: str, red: jax.Array, any_nan: jax.Array,
                        any_nonnan: jax.Array) -> jax.Array:
    nan = jnp.array(np.nan, red.dtype)
    if op == "max":
        return jnp.where(any_nan, nan, red)
    return jnp.where(any_nonnan, red, nan)


def global_agg(op: str, values: jax.Array, valid: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Ungrouped aggregation: plain masked tree-reductions, no permutation,
    no segment scatter (those cost 100x a reduction on TPU). Returns
    ([1]-shaped value, [1]-shaped validity)."""
    vdt = values.dtype
    nvalid = jnp.sum(valid.astype(jnp.int64))
    some = (nvalid > 0)[None]

    def one(x):
        return x[None]

    if op == "count":
        return one(nvalid), jnp.ones(1, jnp.bool_)
    if op == "count_all":
        return one(nvalid), jnp.ones(1, jnp.bool_)
    if op in ("sum", "sumsq"):
        v = values * values if op == "sumsq" else values
        return one(jnp.sum(jnp.where(valid, v, jnp.zeros_like(v)))), some
    if op in ("min", "max"):
        red = jnp.min if op == "min" else jnp.max
        is_float = np.dtype(vdt) in (np.dtype(np.float32), np.dtype(np.float64))
        if is_float:
            clean, nanf, nonnanf = _float_minmax_prep(op, values, valid)
            out = _float_minmax_patch(op, one(red(clean)),
                                      one(jnp.any(nanf)), one(jnp.any(nonnanf)))
            return out, some
        init = (_MIN_INIT if op == "min" else _MAX_INIT)[np.dtype(vdt)]
        masked = jnp.where(valid, values, jnp.full_like(values, init))
        return one(red(masked)), some
    if op in ("first", "last"):
        n = values.shape[0]
        pos = jnp.arange(n, dtype=jnp.int64)
        if op == "first":
            sel = jnp.min(jnp.where(valid, pos, n))
        else:
            sel = jnp.max(jnp.where(valid, pos, -1))
        has = (sel >= 0) & (sel < n)
        return one(values[jnp.clip(sel, 0, n - 1).astype(jnp.int32)]), has[None] & some
    if op == "any":
        return one(jnp.any(valid & values.astype(jnp.bool_))), some
    if op == "all":
        return one(jnp.all(jnp.where(valid, values.astype(jnp.bool_), True))), some
    raise ValueError(f"unknown global op {op}")


def bucket_agg(op: str, values: jax.Array, valid: jax.Array,
               bucket: jax.Array, B: int, matmul_ok: bool
               ) -> Tuple[jax.Array, jax.Array]:
    """Segmented reduction into a DENSE bucket space with no sort: the MXU
    answer to grouped aggregation (one-hot matmul for tiny B, bounded
    scatter otherwise). values/valid/bucket are in original row order;
    invalid rows route to the overflow bucket B and are dropped."""
    vdt = values.dtype
    safe_bucket = jnp.where(valid, bucket, B)
    if op in ("count", "count_all"):
        if matmul_ok:
            out = jnp.stack([
                jnp.sum((valid & (bucket == b)).astype(jnp.int64))
                for b in range(B)])
        else:
            out = jax.ops.segment_sum(jnp.where(valid, 1, 0), safe_bucket,
                                      num_segments=B + 1)[:B].astype(jnp.int64)
        return out, jnp.ones(B, jnp.bool_)
    if op in ("sum", "sumsq"):
        v = values * values if op == "sumsq" else values
        v = jnp.where(valid, v, jnp.zeros_like(v))
        nvalid = bucket_agg("count", values, valid, bucket, B, matmul_ok)[0]
        if matmul_ok:
            # Tiny bucket spaces: one masked tree-reduction per bucket.
            # B full passes over the plane are bandwidth-cheap, keep full
            # f64 precision (an MXU one-hot matmul accumulates f64 sums
            # with ~1e-6 relative error on TPU), and need no scatter.
            out = jnp.stack([
                jnp.sum(jnp.where(bucket == b, v, jnp.zeros_like(v)))
                for b in range(B)])
        else:
            out = jax.ops.segment_sum(v, safe_bucket, num_segments=B + 1)[:B]
        return out, nvalid > 0
    nvalid = jax.ops.segment_sum(jnp.where(valid, 1, 0), safe_bucket,
                                 num_segments=B + 1)[:B]
    if op in ("min", "max"):
        red = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        is_float = np.dtype(vdt) in (np.dtype(np.float32), np.dtype(np.float64))
        if is_float:
            clean, nanf, nonnanf = _float_minmax_prep(op, values, valid)
            out = red(clean, safe_bucket, num_segments=B + 1)[:B]
            any_nan = jax.ops.segment_max(nanf.astype(jnp.int32), safe_bucket,
                                          num_segments=B + 1)[:B] > 0
            any_nonnan = jax.ops.segment_max(nonnanf.astype(jnp.int32), safe_bucket,
                                             num_segments=B + 1)[:B] > 0
            return _float_minmax_patch(op, out, any_nan, any_nonnan), nvalid > 0
        init = (_MIN_INIT if op == "min" else _MAX_INIT)[np.dtype(vdt)]
        masked = jnp.where(valid, values, jnp.full_like(values, init))
        out = red(masked, safe_bucket, num_segments=B + 1)[:B]
        return out, nvalid > 0
    if op in ("first", "last"):
        n = values.shape[0]
        pos = jnp.arange(n, dtype=jnp.int64)
        if op == "first":
            sel = jax.ops.segment_min(jnp.where(valid, pos, n), safe_bucket,
                                      num_segments=B + 1)[:B]
        else:
            sel = jax.ops.segment_max(jnp.where(valid, pos, -1), safe_bucket,
                                      num_segments=B + 1)[:B]
        has = (sel >= 0) & (sel < n)
        return values[jnp.clip(sel, 0, n - 1).astype(jnp.int32)], has & (nvalid > 0)
    if op in ("any", "all"):
        v = values.astype(jnp.int32)
        if op == "any":
            masked = jnp.where(valid, v, 0)
            out = jax.ops.segment_max(masked, safe_bucket, num_segments=B + 1)[:B]
        else:
            masked = jnp.where(valid, v, 1)
            out = jax.ops.segment_min(masked, safe_bucket, num_segments=B + 1)[:B]
        return out.astype(jnp.bool_), nvalid > 0
    raise ValueError(f"unknown bucket op {op}")


_MAX_INIT = {
    np.dtype(np.int8): np.iinfo(np.int8).min,
    np.dtype(np.int16): np.iinfo(np.int16).min,
    np.dtype(np.int32): np.iinfo(np.int32).min,
    np.dtype(np.int64): np.iinfo(np.int64).min,
    np.dtype(np.float32): -np.inf,
    np.dtype(np.float64): -np.inf,
    np.dtype(np.bool_): False,
}
_MIN_INIT = {
    np.dtype(np.int8): np.iinfo(np.int8).max,
    np.dtype(np.int16): np.iinfo(np.int16).max,
    np.dtype(np.int32): np.iinfo(np.int32).max,
    np.dtype(np.int64): np.iinfo(np.int64).max,
    np.dtype(np.float32): np.inf,
    np.dtype(np.float64): np.inf,
    np.dtype(np.bool_): True,
}


def segmented_agg(op: str, values: jax.Array, valid: jax.Array,
                  seg_ids: jax.Array, seg_cap: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Apply one segmented reduction. values/valid are in SORTED order.
    Returns (out_values[seg_cap], out_valid[seg_cap]). SQL null semantics:
    sum/min/max/avg ignore nulls and are null for all-null groups; count
    counts non-null rows."""
    vdt = values.dtype
    nvalid = jax.ops.segment_sum(valid.astype(jnp.int64), seg_ids, num_segments=seg_cap)
    if op == "count":
        return nvalid, jnp.ones(seg_cap, jnp.bool_)
    if op == "count_all":
        ones = jnp.ones_like(seg_ids, dtype=jnp.int64)
        return jax.ops.segment_sum(ones, seg_ids, num_segments=seg_cap), \
            jnp.ones(seg_cap, jnp.bool_)
    if op == "sum":
        masked = jnp.where(valid, values, jnp.zeros_like(values))
        out = jax.ops.segment_sum(masked, seg_ids, num_segments=seg_cap)
        return out, nvalid > 0
    if op == "sumsq":
        masked = jnp.where(valid, values * values, jnp.zeros_like(values))
        out = jax.ops.segment_sum(masked, seg_ids, num_segments=seg_cap)
        return out, nvalid > 0
    if op in ("min", "max"):
        red = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        is_float = np.dtype(vdt) in (np.dtype(np.float32), np.dtype(np.float64))
        if is_float:
            clean, nanf, nonnanf = _float_minmax_prep(op, values, valid)
            out = red(clean, seg_ids, num_segments=seg_cap)
            any_nan = jax.ops.segment_max(nanf.astype(jnp.int32), seg_ids,
                                          num_segments=seg_cap) > 0
            any_nonnan = jax.ops.segment_max(nonnanf.astype(jnp.int32), seg_ids,
                                             num_segments=seg_cap) > 0
            return _float_minmax_patch(op, out, any_nan, any_nonnan), nvalid > 0
        init = (_MIN_INIT if op == "min" else _MAX_INIT)[np.dtype(vdt)]
        masked = jnp.where(valid, values, jnp.full_like(values, init))
        out = red(masked, seg_ids, num_segments=seg_cap)
        return out, nvalid > 0
    if op in ("first", "last"):
        # position of first/last valid row per segment
        n = values.shape[0]
        pos = jnp.arange(n, dtype=jnp.int64)
        if op == "first":
            masked_pos = jnp.where(valid, pos, n)
            sel = jax.ops.segment_min(masked_pos, seg_ids, num_segments=seg_cap)
        else:
            masked_pos = jnp.where(valid, pos, -1)
            sel = jax.ops.segment_max(masked_pos, seg_ids, num_segments=seg_cap)
        has = (sel >= 0) & (sel < n)
        sel_c = jnp.clip(sel, 0, n - 1).astype(jnp.int32)
        return values[sel_c], has & (nvalid > 0)
    if op == "any":
        masked = jnp.where(valid, values.astype(jnp.bool_), False)
        out = jax.ops.segment_max(masked.astype(jnp.int32), seg_ids, num_segments=seg_cap)
        return out.astype(jnp.bool_), nvalid > 0
    if op == "all":
        masked = jnp.where(valid, values.astype(jnp.bool_), True)
        out = jax.ops.segment_min(masked.astype(jnp.int32), seg_ids, num_segments=seg_cap)
        return out.astype(jnp.bool_), nvalid > 0
    raise ValueError(f"unknown segmented op {op}")


def _invert_float_bits(bits_u64: jax.Array, width: int, vdt):
    """Inverse of kernels._order_float_bits."""
    import jax.lax as lax
    if width == 64:
        sign = jnp.uint64(1 << 63)
        pos = (bits_u64 & sign) != 0
        raw = jnp.where(pos, bits_u64 ^ sign, ~bits_u64)
        # u64 -> f64 via two u32 bitcasts (TPU x64 rewriter limitation)
        lo = (raw & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (raw >> jnp.uint64(32)).astype(jnp.uint32)
        pair = jnp.stack([lo, hi], axis=-1)
        return lax.bitcast_convert_type(pair, jnp.float64)
    sign = jnp.uint64(0x80000000)
    mask = jnp.uint64(0xFFFFFFFF)
    b = bits_u64 & mask
    pos = (b & sign) != 0
    raw = jnp.where(pos, b ^ sign, (~b) & mask)
    return lax.bitcast_convert_type(raw.astype(jnp.uint32), jnp.float32)


def gather_group_keys(key_cols: List[ColumnVector], perm: jax.Array,
                      boundary: jax.Array, n_groups: int, num_rows: int,
                      live=None) -> List[ColumnVector]:
    """Representative key row per group = first sorted row of each segment.
    Sync-free: compacts boundary positions at full capacity (callers carry
    the true group count, possibly lazily). `live` is the SOURCE batch's
    selection mask — without it a masked batch's live rows past the live
    COUNT would gather as null (positional validity_or_default is only
    valid for front-packed batches)."""
    cap = boundary.shape[0]
    first_idx = K._compact_indices(boundary, cap, cap)
    out = []
    for c in key_cols:
        sorted_col = K.gather_column(c, perm, num_rows, src_live=live)
        out.append(K.gather_column(sorted_col, first_idx, num_rows))
    return out
