"""Device equi-join kernels.

Reference parity: GpuHashJoin.scala:104 (gather-map producing probe) +
JoinGatherer chunked assembly. cuDF builds a device hash table; the
TPU-idiomatic design is sort + binary-search:

1. normalize join keys to uint64 planes (ops.kernels.normalize_key),
2. combine multi-column keys into one u64 by hash mixing,
3. sort the BUILD side once by combined key,
4. per probe row, searchsorted left/right gives the hash-equal candidate
   range -- O(log n) per row, fully vectorized on the VPU,
5. count-then-gather: expand candidate ranges into (probe, build) pairs
   (host reads back ONE scalar = total candidates), then verify exact key
   equality per pair over the normalized planes and compact.

Null join keys never match (SQL semantics): null build rows are compacted
away before the sort; null probe rows force empty candidate ranges.
String keys use the equality-faithful 64-bit double-hash from
normalize_key (collision odds ~2^-64 per pair; documented incompat,
mirror of the reference's incompatOps discipline).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (
    ColumnVector, host_int, host_planes, round_capacity,
)
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.runtime import compile_cache as _cc


def _combine_keys(cols: List[ColumnVector], num_rows: int, live=None
                  ) -> Tuple[jax.Array, List[jax.Array], jax.Array]:
    """Returns (combined u64 hash, per-col normalized planes, any_null)."""
    planes = []
    any_null = None
    for c in cols:
        k, nulls = K.normalize_key(c, num_rows, live=live)
        planes.append(k)
        any_null = nulls if any_null is None else (any_null | nulls)
    h = jnp.zeros_like(planes[0])
    for k in planes:
        # 64-bit mix (splitmix64 finalizer per plane)
        x = h ^ k
        x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        h = x ^ (x >> jnp.uint64(31))
    return h, planes, any_null


#: direct-address table budget (int32 entries): dense integer join keys
#: (TPC-H orderkeys, dimension ids) take the 2-gather path below this
DENSE_KEY_RANGE_LIMIT = 1 << 26


def _dense_int_eligible(build_keys: List[ColumnVector],
                        probe_key_types) -> bool:
    if len(build_keys) != 1 or len(probe_key_types) != 1:
        return False
    bt, pt = build_keys[0].dtype, probe_key_types[0]
    from spark_rapids_tpu import types as T
    ok_types = (T.Int8Type, T.Int16Type, T.Int32Type, T.Int64Type,
                T.DateType, T.BooleanType)
    return isinstance(bt, ok_types) and isinstance(pt, ok_types)


class DenseBuildTable:
    """Direct-address layout of a build side with a single bounded integer
    key: starts[span+1] + sorted_orig[bcap] (counting sort by key), plus
    host facts (bmin, span, max_dup) fetched ONCE at prepare time. When
    max_dup == 1 (unique build keys — the star-schema shape), probing is
    completely sync-free: two gathers yield the matching build row per
    probe row, enabling mask-through join output with no pair expansion."""

    __slots__ = ("starts", "sorted_orig", "bmin", "span", "max_dup",
                 "bcap", "build_rows", "slot_idx")

    def __init__(self, starts, sorted_orig, bmin, span, max_dup, bcap,
                 build_rows):
        self.starts = starts
        self.sorted_orig = sorted_orig
        self.bmin = bmin
        self.span = span
        self.max_dup = max_dup
        self.bcap = bcap
        self.build_rows = build_rows
        #: unique-key builds: build row per key slot (-1 empty), computed
        #: once over the SPAN so probing is a single gather
        self.slot_idx = None
        if max_dup <= 1:
            occ = starts[1:] > starts[:-1]
            cand = sorted_orig[jnp.clip(starts[:-1], 0, bcap - 1)]
            self.slot_idx = jnp.where(occ, cand, -1)


def prepare_dense_build(build_keys: List[ColumnVector], build_rows: int,
                        probe_key_types) -> Optional[DenseBuildTable]:
    """Build the direct-address table when the dense-int path applies.
    probe_key_types: the probe keys' DataTypes (columns not needed).
    ONE host fetch (4 scalars). Returns None when ineligible."""
    if not _dense_int_eligible(build_keys, probe_key_types):
        return None
    bcap = build_keys[0].capacity
    bv = build_keys[0].data.astype(jnp.int64)
    valid = build_keys[0].validity_or_default(build_rows)
    b_in = (jnp.arange(bcap) < build_rows) & valid
    bmin_d = jnp.min(jnp.where(b_in, bv, jnp.int64(2**62)))
    bmax_d = jnp.max(jnp.where(b_in, bv, jnp.int64(-2**62)))
    nbuild_d = jnp.sum(b_in.astype(jnp.int32))
    bmin, bmax, nbuild = (int(x) for x in host_planes(
        [bmin_d, bmax_d, nbuild_d], "range"))
    span = bmax - bmin + 1
    if nbuild <= 0 or not (0 < span <= DENSE_KEY_RANGE_LIMIT):
        return None
    starts, cnt_max = _dense_counts(bv, b_in, bcap, jnp.int64(bmin), span)
    max_dup = host_int(cnt_max, "size")
    # the rows in key order: a placement when the keys are unique (the
    # star-schema shape: no sort, which the TPU compiler takes half a
    # minute over at a dimension's size), else the stable sort by key
    order = _dense_unique_order if max_dup <= 1 else _dense_order
    sorted_orig = order(bv, b_in, bcap, jnp.int64(bmin), starts)
    return DenseBuildTable(starts, sorted_orig, jnp.int64(bmin), span,
                           max_dup, bcap, build_rows)


def dense_lookup_planes(slot_idx: jax.Array, bmin, pv: jax.Array,
                        p_in: jax.Array) -> jax.Array:
    """Traced core of the sync-free unique-key probe: int32 build row
    index per probe row, -1 when unmatched. Shared by the eager path
    below and the fused masked-probe kernel (exec/tpu_nodes)."""
    span = slot_idx.shape[0]
    slot = pv - bmin
    inside = p_in & (slot >= 0) & (slot < span)
    sl = jnp.where(inside, slot, 0).astype(jnp.int32)
    return jnp.where(inside, slot_idx[sl], -1)


def dense_lookup(table: DenseBuildTable, probe_keys: List[ColumnVector],
                 probe_rows: int, probe_live=None) -> jax.Array:
    """Sync-free unique-key probe: int32[pcap] build row index per probe
    row, -1 when unmatched. Requires table.max_dup <= 1."""
    pv = probe_keys[0].data.astype(jnp.int64)
    # masked batches have live rows at ARBITRARY positions: combine the
    # column validity with the live mask directly, never arange<num_rows
    if probe_live is not None:
        p_in = probe_live if probe_keys[0].validity is None \
            else (probe_live & probe_keys[0].validity)
    else:
        p_in = probe_keys[0].validity_or_default(probe_rows)
    return dense_lookup_planes(table.slot_idx, table.bmin, pv, p_in)


def join_pairs(build_keys: List[ColumnVector], build_rows: int,
               probe_keys: List[ColumnVector], probe_rows: int,
               probe_live=None) -> Tuple[np.ndarray, np.ndarray]:
    """Compute matching (probe_idx, build_idx) pairs for an equi-join.
    Returned as device arrays (int32) with -1 padding; second return is the
    match count. Output order: probe-major (stable for the probe side).

    Two probe strategies:
    - DENSE-INT fast path: a single bounded integer key builds a
      direct-address (start, end) table over the key range — the probe is
      TWO O(probe) gathers and needs no hash verification. On this
      hardware a 32M-row binary search costs ~6s (22 round-trip gathers,
      64-bit lanes emulated); the dense path is ~50x cheaper and covers
      the TPC-H/star-schema join shape.
    - general path: sort build by 64-bit key hash, find each probe row's
      equal-hash candidate run by a SORT-MERGE rank over the hash union
      (measured: ``searchsorted`` on 64-bit lanes costs 8.7 s for 20M
      probes on v5e — 25x the cost of sorting the union), then expand +
      verify exact equality over the normalized planes."""
    table = prepare_dense_build(build_keys, build_rows,
                                [c.dtype for c in probe_keys])
    if table is not None:
        pcap0 = probe_keys[0].capacity
        if probe_live is not None:
            p_in0 = probe_live if probe_keys[0].validity is None \
                else (probe_live & probe_keys[0].validity)
        else:
            p_in0 = probe_keys[0].validity_or_default(probe_rows)
        return _dense_int_pairs(table,
                                probe_keys[0].data.astype(jnp.int64),
                                p_in0, pcap0)

    bh, bplanes, bnull = _combine_keys(build_keys, build_rows)
    ph, pplanes, pnull = _combine_keys(probe_keys, probe_rows,
                                       live=probe_live)
    bcap = bh.shape[0]
    pcap = ph.shape[0]
    b_in = (jnp.arange(bcap) < build_rows) & ~bnull
    # masked probe batches join WITHOUT compaction: liveness rides in
    p_in = ((probe_live if probe_live is not None
             else (jnp.arange(pcap) < probe_rows)) & ~pnull)

    # compact non-null build rows, then sort by hash
    bidx, bcount = K.filter_indices(b_in, bcap)
    bsel = jnp.clip(bidx, 0, bcap - 1)
    bh_c = jnp.where(bidx >= 0, bh[bsel], jnp.uint64(0xFFFFFFFFFFFFFFFF))
    order = jnp.argsort(bh_c)  # padded sentinel rows sort last
    sorted_h = bh_c[order]
    sorted_orig = jnp.where(bidx >= 0, bidx, -1)[order]

    lo, hi = _merge_rank_ranges(sorted_h, bcount, ph, p_in)
    total = host_int(jnp.sum((hi - lo).astype(jnp.int64)), "size")

    probe_i, build_pos = K.expand_ranges(lo, hi, total)
    build_i = jnp.where(build_pos >= 0,
                        sorted_orig[jnp.clip(build_pos, 0, bcap - 1)], -1)

    # exact verification over normalized planes (hash could collide)
    ok = (probe_i >= 0) & (build_i >= 0)
    psel = jnp.clip(probe_i, 0, pcap - 1)
    bsel2 = jnp.clip(build_i, 0, bcap - 1)
    for pp, bp in zip(pplanes, bplanes):
        ok = ok & (pp[psel] == bp[bsel2])
    idx, match_count = K.filter_indices(ok, ok.shape[0])
    sel = jnp.clip(idx, 0, ok.shape[0] - 1)
    out_p = jnp.where(idx >= 0, probe_i[sel], -1)
    out_b = jnp.where(idx >= 0, build_i[sel], -1)
    return out_p, out_b, match_count


@_cc.jit(static_argnames=("bcap", "span"))
def _dense_counts(bv, b_in, bcap, bmin, span):
    """(starts[span+1], the most rows one key has): where each key
    value's build rows begin in key order."""
    slot = jnp.where(b_in, (bv - bmin).astype(jnp.int32), span)
    cnt = jax.ops.segment_sum(jnp.ones(bcap, jnp.int32), slot,
                              num_segments=span + 1)[:span]
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(cnt).astype(jnp.int32)])
    return starts, jnp.max(cnt)


@_cc.jit(static_argnames=("bcap",))
def _dense_order(bv, b_in, bcap, bmin, starts):
    """sorted_orig[bcap]: the build rows ordered by (key, original index),
    a stable counting sort by key; -1 behind the last."""
    order = jnp.argsort(jnp.where(b_in, (bv - bmin),
                                  jnp.int64(1) << 62).astype(jnp.int64))
    return jnp.where(jnp.arange(bcap) < jnp.sum(b_in.astype(jnp.int32)),
                     order, -1)


@_cc.jit(static_argnames=("bcap",))
def _dense_unique_order(bv, b_in, bcap, bmin, starts):
    """_dense_order where no key repeats: a row's place in key order is
    where its key's rows begin."""
    span = starts.shape[0] - 1
    slot = jnp.where(b_in, (bv - bmin).astype(jnp.int32), span)
    place = jnp.where(b_in, starts[jnp.clip(slot, 0, span - 1)], bcap)
    return jnp.full(bcap + 1, -1, jnp.int32).at[place].set(
        jnp.arange(bcap, dtype=jnp.int32), mode="drop")[:bcap]


def _dense_int_pairs(table: DenseBuildTable, pv, p_in, pcap):
    starts, sorted_orig, bcap = table.starts, table.sorted_orig, table.bcap
    slot = pv - table.bmin
    inside = p_in & (slot >= 0) & (slot < table.span)
    sl = jnp.where(inside, slot, 0).astype(jnp.int32)
    lo = jnp.where(inside, starts[sl], 0)
    hi = jnp.where(inside, starts[sl + 1], 0)
    counts = hi - lo
    if table.max_dup <= 1:
        # unique build keys (the dominant case): pairs ARE the matching
        # probe rows — no range expansion at all
        m = counts > 0
        idx, match_count = K.filter_indices(m, pcap)
        sel = jnp.clip(idx, 0, pcap - 1)
        out_p = jnp.where(idx >= 0, sel, -1)
        bpos = jnp.where(idx >= 0, lo[sel], 0)
        out_b = jnp.where(idx >= 0,
                          sorted_orig[jnp.clip(bpos, 0, bcap - 1)], -1)
        return out_p, out_b, match_count
    total = host_int(jnp.sum(counts.astype(jnp.int64)), "size")
    probe_i, build_pos = K.expand_ranges(lo, hi, total)
    build_i = jnp.where(build_pos >= 0,
                        sorted_orig[jnp.clip(build_pos, 0, bcap - 1)], -1)
    return probe_i, build_i, total


def _merge_rank_ranges(sorted_h: jax.Array, bcount, ph: jax.Array,
                       p_in: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per probe row, the candidate run [lo, hi) of equal hashes in the
    sorted build plane — via ONE stable sort of the hash union (build rows
    tie-break before probe rows) instead of two 64-bit binary searches.
    sorted_h must carry the all-ones sentinel beyond bcount."""
    bcap = sorted_h.shape[0]
    pcap = ph.shape[0]
    # dead probe rows get the sentinel too: their run resolves empty below
    php = jnp.where(p_in, ph, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    allh = jnp.concatenate([sorted_h, php])
    isq = jnp.concatenate([jnp.zeros(bcap, jnp.uint8),
                           jnp.ones(pcap, jnp.uint8)])
    iota = jnp.arange(bcap + pcap, dtype=jnp.int32)
    sh, sq, si = jax.lax.sort((allh, isq, iota), num_keys=2, is_stable=True)
    # build rows at union positions <= i (build sorts before equal probes)
    nb_prefix = jnp.cumsum((sq == 0).astype(jnp.int32))
    # scatter each probe row's prefix count back to its original position
    dest = jnp.where(sq == 1, si - bcap, pcap)
    r = jnp.zeros(pcap + 1, jnp.int32).at[dest].set(nb_prefix,
                                                    mode="drop")[:pcap]
    r = jnp.minimum(r, bcount)  # sentinel pad rows are not candidates
    last_b = r - 1  # compact index of the last build row with h <= h_p
    lb = jnp.clip(last_b, 0, bcap - 1)
    eq = (last_b >= 0) & (last_b < bcount) & (sorted_h[lb] == ph) & p_in
    # first row of each equal-hash run in the sorted build plane
    pos = jnp.arange(bcap, dtype=jnp.int32)
    bound = jnp.concatenate([jnp.ones(1, jnp.bool_),
                             sorted_h[1:] != sorted_h[:-1]])
    run_start = jax.lax.cummax(jnp.where(bound, pos, 0))
    lo = jnp.where(eq, run_start[lb], 0)
    hi = jnp.where(eq, r, 0)
    return lo, hi


def probe_matched_mask(pairs_idx: jax.Array, cap: int) -> jax.Array:
    """bool[cap]: rows of a side that appear in the matched pairs. Pairs
    only ever reference LIVE rows (join_pairs gates on the live mask), so
    no in-range clamp — masked probe batches have live rows at arbitrary
    positions."""
    m = jnp.zeros(cap + 1, jnp.bool_)
    sel = jnp.where(pairs_idx >= 0, pairs_idx, cap)
    m = m.at[sel].set(True, mode="drop")
    return m[:cap]


def unmatched_indices(mask_matched: jax.Array, live: jax.Array
                      ) -> Tuple[jax.Array, int]:
    """Indices of LIVE rows not matched (for outer-join completion).
    `live` is the side's liveness plane (bool[cap])."""
    cap = mask_matched.shape[0]
    un = (~mask_matched) & live
    return K.filter_indices(un, cap)
