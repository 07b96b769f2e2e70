"""Shape canonicalization: THE padding-bucket policy for device planes.

Every XLA computation is compiled per static shape, and a fresh compile
costs orders of magnitude more than a dispatch. The engine therefore
never traces at a batch's exact
row count: capacities snap to a small set of padding buckets so traces
are shared across batches AND queries, with the live row count riding as
a traced scalar and padded tail rows masked by the existing validity /
selection-mask planes (columnar/batch.py). The reference never needs
this — cuDF kernels are shape-polymorphic — so bucketing is the price a
TPU-native engine pays to buy the same property back.

This module is the ONE home of that policy (``columnar.batch.
round_capacity`` delegates here). Two knobs shape the bucket set:

- ``spark.rapids.compile.shapes.growthFactor`` — buckets grow
  geometrically by this factor from the minimum capacity. 2.0 (default)
  is exactly the historical next-power-of-two policy: log2(max/min)
  buckets, up to ~2x padding waste. Smaller factors (1.25, 1.5) trade
  more buckets (more traces) for tighter padding — the right call when
  HBM, not compile count, is the binding constraint.
- ``spark.rapids.compile.shapes.dtypeAlign`` — round every bucket up to
  a whole number of TPU tiles for the plane's dtype (the (sublane, 128)
  native tile: 8*128 elements for 4-byte lanes, 16*128 for 2-byte,
  32*128 for 1-byte). Power-of-two buckets >= 1024 are always aligned
  already; this matters for non-2.0 growth factors, where an unaligned
  bucket would pay a partial-tile relayout on every kernel.

The policy is consulted from kernel depths where no conf rides along, so
``config.set_session_conf`` publishes the active values as module
globals (the MIN_CAPACITY pattern). The bucket function is pure and
monotone: bucket(n) >= n, and bucket(bucket(n)) == bucket(n) — the
fixpoint property ``is_bucketed`` checks and ``ensure_bucketed`` (the
fuse/compiled entry-point canonicalizer) restores for foreign batches.
"""
from __future__ import annotations

import math
from typing import Optional

#: geometric growth factor between buckets; 2.0 == next power of two
GROWTH_FACTOR: float = 2.0
#: snap buckets to whole native tiles for the plane's dtype width
DTYPE_ALIGN: bool = True

#: elements per native TPU tile at each itemsize: (sublanes * 128 lanes),
#: sublanes = 32 / itemsize (f32 tile = (8, 128), bf16 (16, 128),
#: int8/bool (32, 128)). 8-byte lanes decompose into two 4-byte planes,
#: so they share the 4-byte tile.
_TILE_ELEMS = {1: 32 * 128, 2: 16 * 128, 4: 8 * 128, 8: 8 * 128}


def configure(growth_factor: float, dtype_align: bool) -> None:
    """Publish the session policy (called by config.set_session_conf).
    Growth factors are clamped to (1.0, 4.0]: a factor at or below 1.0
    would make every row count its own bucket — the exact recompile
    storm this module exists to prevent."""
    global GROWTH_FACTOR, DTYPE_ALIGN
    g = float(growth_factor)
    GROWTH_FACTOR = min(max(g, 1.0625), 4.0)
    DTYPE_ALIGN = bool(dtype_align)


def _align_for(itemsize: Optional[int]) -> int:
    if not DTYPE_ALIGN or not itemsize:
        return 1
    return _TILE_ELEMS.get(int(itemsize), 8 * 128)


def bucket_rows(n: int, minimum: int, itemsize: Optional[int] = None
                ) -> int:
    """Smallest policy bucket >= n: geometric growth from `minimum` by
    GROWTH_FACTOR, tile-aligned for `itemsize` once buckets exceed one
    tile. The default policy (growth 2.0) reproduces the historical
    next-power-of-two capacities bit for bit."""
    n = max(int(n), 1, int(minimum))
    g = GROWTH_FACTOR
    align = _align_for(itemsize)
    if g == 2.0:
        # fast path == the historical policy (the power-of-two ladder is
        # anchor-independent: pow2(max(n, minimum)) is always a member);
        # powers of two past one tile are whole-tile multiples already,
        # so alignment is free
        cap = 1 << (n - 1).bit_length()
        if align > 1 and cap > align:
            cap = ((cap + align - 1) // align) * align
        return cap
    # ONE canonical ladder anchored at 1 — b0 = 1, b_{k+1} =
    # align(ceil(b_k * g)) — walked, not solved in log space: every
    # ladder value maps to itself (bucket(bucket(n)) == bucket(n)) with
    # no float-slop edge cases, and the walk is O(log_g n) integer
    # steps. The anchor must NOT be `minimum`: call sites use different
    # floors (MIN_CAPACITY vs minimum=1 kernels), and per-minimum
    # ladders would be disjoint — the same row count mapping to
    # different capacities at different sites multiplies the trace zoo
    # this policy exists to shrink, and breaks the minimum=1 fixpoint
    # membership check ensure_bucketed relies on. `minimum` is a floor
    # on the RESULT, not the anchor.
    cap = 1
    while cap < n:
        nxt = math.ceil(cap * g)
        if align > 1 and nxt > align:
            nxt = ((nxt + align - 1) // align) * align
        cap = nxt
    return cap


def bucket_pool_bytes(nbytes: int, slack: int = 8) -> int:
    """Capacity for a raw byte pool (encoded Parquet bit pools,
    io/encoded.py): bucket on the 1-byte ladder with `slack` guard bytes
    so 32-bit word pairs gathered at the last bit offset stay in bounds,
    rounded to whole u32 words so the pool reinterprets as a word plane
    without a tail copy. Pools use minimum=32 — they are auxiliary
    planes, not row planes, so the session MIN_CAPACITY floor does not
    apply."""
    cap = bucket_rows(int(nbytes) + int(slack), 32, 1)
    return ((cap + 3) // 4) * 4


def is_bucketed(capacity: int, minimum: int,
                itemsize: Optional[int] = None) -> bool:
    """Is `capacity` already a policy bucket (the fixpoint check the
    compiled entry points use before deciding to pad)?"""
    return int(capacity) == bucket_rows(int(capacity), minimum, itemsize)


# ---------------------------------------------------------------------------
# entry-point canonicalization
# ---------------------------------------------------------------------------

def ensure_bucketed(batch):
    """Pad a batch whose row capacity is off the bucket ladder up to the
    enclosing bucket — the INGESTION-side canonicalizer for foreign
    batches (hand-built tests, external integrations handing planes to
    the engine).

    Everything the engine itself produces is already bucketed (every
    capacity decision routes through round_capacity), so engine batches
    pass the fixpoint check untouched. This must be applied where the
    padded batch REPLACES the original wholesale — mid-pipeline callers
    hold the original planes and combine them with downstream outputs,
    so an entry point must never pad behind their back. Padded tail
    rows are invalid under the existing validity/mask semantics, so
    results are unchanged. Nested (array/map/struct) columns fall back
    to the caller's shape (their child planes carry independent
    capacities); a batch containing one is returned as-is.
    """
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar import batch as B

    # ladder membership with minimum=1, NOT the session floor: batches
    # legitimately smaller than MIN_CAPACITY exist (kernels that size by
    # round_capacity(n, minimum=1)) and are already shared-trace shapes —
    # padding them to the floor would desync them from sibling planes
    # the caller still holds at the small capacity
    cap = batch.capacity
    if is_bucketed(cap, 1) or not batch.columns:
        return batch
    new_cap = bucket_rows(cap, 1)
    pad = new_cap - cap
    cols = []
    for c in batch.columns:
        if c.is_nested:
            return batch
        if isinstance(c.data, dict):
            if c.is_dict:
                data = dict(c.data)
                data["codes"] = jnp.pad(c.data["codes"], (0, pad))
            else:  # flat string: offsets[cap+1] -> [new_cap+1], tail
                # rows own empty slices at the last offset
                off = c.data["offsets"]
                data = dict(c.data)
                data["offsets"] = jnp.pad(off, (0, pad), mode="edge")
        else:
            data = jnp.pad(c.data, (0, pad))
        validity = c.validity
        if validity is not None:
            validity = jnp.pad(validity, (0, pad))  # False tail
        cols.append(B.ColumnVector(c.dtype, data, validity,
                                   dict_unique=c.dict_unique,
                                   bounds=c.bounds))
    row_mask = batch.row_mask
    if row_mask is not None:
        row_mask = jnp.pad(row_mask, (0, pad))  # padded rows are dead
    return B.ColumnarBatch(cols, batch.num_rows, row_mask)
