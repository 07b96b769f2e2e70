"""Pallas sorted-window segmented reduction — the groupby hot path.

Reference parity: SURVEY §7.3.1's "hard" kernel list (the cudf hash-agg
shard). Read on a v5e in an early round (not re-measured on today's
chip): end-to-end sort + kernel = 317 ms vs 607 ms for the 3-scatter
XLA bucket path at 16.7M rows -> 4M groups, bit-exact sums.

Design: after a single co-sort by the packed key, dense group ids are
MONOTONE, so a 1024-row tile touches a contiguous id span <= 1024 wide.
Each grid step runs ONE bf16 one-hot matmul [2*TILE, TILE] @ [TILE, P]
on the MXU and accumulates into a two-block output window selected by a
scalar-prefetched block base — zero scatters, zero gathers. Payload
values are 8-bit balanced digits (|d| <= 2^7), exact in bf16; per-slot
f32 accumulation is exact while a group's row count stays <= 2^17 (the
caller wraps a lax.cond fallback on the post-hoc count column, which is
itself exact to 2^24 rows).

Output-block protocol: Pallas TPU does NOT load output windows from HBM
on first visit, so the kernel INITIALIZES a block on the step that first
maps it and ACCUMULATES on consecutive revisits; monotone ids mean each
buffer's block index advances by 0 or 1, so every block is first-visited
exactly once. Untouched tails are masked out host-side.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu.runtime import compile_cache as _cc

TILE = 1024  # 1-D i32 blocks must match XLA's 1024-element tiling
#: per-group row-count bound: 8-bit digits reach 2^8, so counts <= 2^16
#: keep every per-slot f32 accumulation within the exact-integer range
MAX_GROUP_ROWS = 1 << 16
#: digit shifts covering 47 bits below the batch max exponent
#: (callers scale by _exponent_scale(m) * 2^11, so the top digit
#: stays < 2^7 — comfortably bf16-exact)
SHIFTS = (40, 32, 24, 16, 8, 0)


def _kernel_factory(P: int):
    from jax.experimental import pallas as pl

    def kernel(bases_ref, gid_ref, pay_ref, olo_ref, ohi_ref):
        t = pl.program_id(0)
        base = bases_ref[t]
        g = gid_ref[...].reshape(TILE)
        local = g - base * TILE
        iota = lax.broadcasted_iota(jnp.int32, (2 * TILE, TILE), 0)
        # bf16 on the HBM side (payload plane), f32 inside VMEM: the
        # one-hot values and 8-bit digits are exact either way, but the
        # ACCUMULATION must be f32 (bf16 dot accumulation drops bits on
        # the interpret backend)
        oh = (iota == local[None, :]).astype(jnp.float32)
        acc = jnp.dot(oh, pay_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
        moved = jnp.logical_or(t == 0,
                               base != bases_ref[jnp.maximum(t - 1, 0)])

        @pl.when(moved)
        def _init():
            olo_ref[...] = acc[:TILE]
            ohi_ref[...] = acc[TILE:]

        @pl.when(jnp.logical_not(moved))
        def _accumulate():
            olo_ref[...] += acc[:TILE]
            ohi_ref[...] += acc[TILE:]

    return kernel


#: eligibility ceiling for the engine path (exec/tpu_nodes): past ~8M
#: rows the enclosing fused stage (sorted planes + digit lanes + the
#: cond fallback's scatter temps) measured 18.5G HBM vs the v5e's
#: 15.75G — larger batches stay on the scatter path
CHUNK_ROWS = 1 << 23


@_cc.jit(static_argnames=("outcap",))
def segsum_window(gid: jax.Array, payload: jax.Array, outcap: int
                  ) -> jax.Array:
    """gid i32[N] sorted ascending (dense ids); payload bf16[N, P] (8-bit
    digit values are bf16-exact; bf16 halves the HBM footprint of the
    payload plane) with P a multiple of 8. Returns f32[outcap, P] per-id
    sums; outcap must be a multiple of 2*TILE and exceed max(gid)+1."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, P = payload.shape
    assert n % TILE == 0 and outcap % (2 * TILE) == 0, (n, outcap)
    T = n // TILE
    bases = jnp.clip(gid[::TILE] // TILE, 0, outcap // TILE - 2)
    from spark_rapids_tpu.ops.pallas_kernels import _interpret, _x64_off
    with _x64_off():
        lo, hi = pl.pallas_call(
            _kernel_factory(P),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(T,),
                in_specs=[
                    pl.BlockSpec((TILE,), lambda t, b: (t,)),
                    pl.BlockSpec((TILE, P), lambda t, b: (t, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((TILE, P), lambda t, b: (b[t], 0)),
                    pl.BlockSpec((TILE, P), lambda t, b: (b[t] + 1, 0)),
                ],
            ),
            out_shape=[jax.ShapeDtypeStruct((outcap, P), jnp.float32)] * 2,
            interpret=_interpret(),
        )(bases, gid.astype(jnp.int32), payload)
    sb = (jnp.arange(outcap, dtype=jnp.int32) // TILE)[:, None]
    lo_keep = (sb >= bases[0]) & (sb <= bases[-1])
    hi_keep = (sb >= bases[0] + 1) & (sb <= bases[-1] + 1)
    return jnp.where(lo_keep, lo, 0.0) + jnp.where(hi_keep, hi, 0.0)


#: rows a grid step of bucket_digit_sums
DIGIT_TILE = 8192
#: rows a block of its output: a block's digit sums stay within 2^30
#: (|digit| <= 2^8), exact in int32
DIGIT_BLOCK_ROWS = 1 << 22
#: a non-finite value's q plane: above every finite q (|q| <= 2^24);
#: bit 0 marks +inf or NaN, bit 1 -inf or NaN
SPECIAL = 1 << 26


def signed_bytes(part: jax.Array) -> List[jax.Array]:
    """int32 |part| <= 2^24 as three balanced base-2^8 digits, most
    significant first: |d2| <= 2^8, |d1|, |d0| <= 2^7 (bf16-exact)."""
    d0 = ((part + 128) & 255) - 128
    part = (part - d0) >> 8
    d1 = ((part + 128) & 255) - 128
    return [(part - d1) >> 8, d1, d0]


def _tile_sums(b, counts, splits, B: int, P: int) -> jax.Array:
    """One tile's per-bucket lane sums, i32[B, P]: its rows' lanes (the
    counts, then a sum's six balanced digits of q and n and its two flag
    bits) and the one-hot of their buckets, contracted on the MXU (bf16
    in, f32 out: a tile's sums reach its rows * 2^8, exact)."""
    lanes = list(counts)
    for q, n in splits:
        special = q >= SPECIAL
        lanes += signed_bytes(jnp.where(special, 0, q)) + signed_bytes(n)
        lanes += [(special & ((q & 1) != 0)).astype(jnp.int32),
                  (special & ((q & 2) != 0)).astype(jnp.int32)]
    lanes += [jnp.zeros_like(b)] * (P - len(lanes))
    pay = jnp.stack(lanes).astype(jnp.float32).astype(jnp.bfloat16)
    onehot = (lax.broadcasted_iota(jnp.int32, (B, b.shape[0]), 0)
              == b[None, :]).astype(jnp.float32).astype(jnp.bfloat16)
    return lax.dot_general(onehot, pay, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32
                           ).astype(jnp.int32)


def _digit_kernel_factory(n_counts: int, n_sums: int, B: int, P: int):
    from jax.experimental import pallas as pl

    def kernel(bucket_ref, *refs):
        *ins, out_ref = refs
        vals = [r[...] for r in ins]
        part = _tile_sums(bucket_ref[...], vals[:n_counts],
                          [(vals[n_counts + 2 * s], vals[n_counts + 2 * s + 1])
                           for s in range(n_sums)], B, P)

        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[...] = part

        @pl.when(pl.program_id(1) != 0)
        def _accumulate():
            out_ref[...] += part

    return kernel


def bucket_digit_sums(bucket: jax.Array, counts: List[jax.Array],
                      splits: List[Tuple[jax.Array, jax.Array]], B: int
                      ) -> jax.Array:
    """Per-bucket sums of a tiny-bucket aggregate's lanes in one pass.

    bucket i32[N] in [0, B); counts: 0/1 i32[N] planes; splits: each
    sum's (q, n) i32[N] planes, value = q * 2^24 + n (or q = SPECIAL +
    its flag bits). A grid step forms its rows' lanes and one-hot in VMEM
    and contracts them on the MXU (`_tile_sums`); a block's steps add in
    int32. Returns int64[B, P]: the counts' sums, then eight lanes a sum
    (digits weighted 2^SHIFTS, then the two flag counts)."""
    from jax.experimental import pallas as pl
    from spark_rapids_tpu.ops.pallas_kernels import _interpret, _x64_off
    planes = [bucket] + list(counts) + [p for qn in splits for p in qn]
    n = bucket.shape[0]
    tile = min(DIGIT_TILE, -(-n // 1024) * 1024)
    block = min(DIGIT_BLOCK_ROWS, -(-n // tile) * tile)
    pad = -n % block
    planes = [(jnp.pad(p, (0, pad)) if pad else p).astype(jnp.int32)
              for p in planes]
    steps, nb = block // tile, (n + pad) // block
    Bp = -(-B // 16) * 16
    P = -(-(len(counts) + 8 * len(splits)) // 16) * 16
    nc = len(counts)
    if _interpret() and jax.typeof(bucket).vma:
        # the interpreter would run the body on one shard's varying values
        # beside unvarying constants, which shard_map refuses: the same
        # tiles through XLA (Mosaic strips the mesh axes and needs none)
        tiles = [p.reshape(-1, tile) for p in planes]
        parts = jax.vmap(lambda b, *v: _tile_sums(
            b, v[:nc], list(zip(v[nc::2], v[nc + 1::2])), Bp, P))(*tiles)
        out = jnp.sum(parts.reshape(nb, steps, Bp, P), axis=1)
    else:
        with _x64_off():
            out = pl.pallas_call(
                _digit_kernel_factory(nc, len(splits), Bp, P),
                grid=(nb, steps),
                in_specs=[pl.BlockSpec((tile,),
                                       lambda i, j: (i * steps + j,))
                          for _ in planes],
                out_specs=pl.BlockSpec((None, Bp, P), lambda i, j: (i, 0, 0)),
                # under shard_map the sums vary over the mesh as rows do
                out_shape=jax.ShapeDtypeStruct((nb, Bp, P), jnp.int32,
                                               vma=jax.typeof(bucket).vma),
                interpret=_interpret(),
            )(*planes)
    return jnp.sum(out.astype(jnp.int64), axis=0)[:B]


def float_digits(clean: jax.Array, scale) -> List[jax.Array]:
    """8-bit balanced digit planes of round(clean*scale) (f32 each)."""
    s = jnp.round(clean * scale)
    out = []
    rem = s
    for shift in SHIFTS:
        d = jnp.round(rem / np.float64(2.0 ** shift)) if shift \
            else jnp.round(rem)
        if shift:
            rem = rem - d * np.float64(2.0 ** shift)
        out.append(d.astype(jnp.bfloat16))
    return out


def digits_to_f64(cols: List[jax.Array]) -> jax.Array:
    tot = jnp.zeros(cols[0].shape[0], jnp.float64)
    for d, shift in zip(cols, SHIFTS):
        tot = tot + d.astype(jnp.float64) * np.float64(2.0 ** shift)
    return tot


def int_digits(code: jax.Array, nbits: int) -> Tuple[List[jax.Array], List[int]]:
    """Unsigned 8-bit digit planes of a small nonnegative int plane."""
    shifts = list(range(0, nbits, 8))[::-1]
    out = []
    for sh in shifts:
        out.append(((code >> sh) & 0xFF).astype(jnp.bfloat16))
    return out, shifts


def int_digits_to_val(cols: List[jax.Array], shifts: List[int],
                      counts: jax.Array) -> jax.Array:
    """Recover per-group int values from digit-times-count sums."""
    safe = jnp.maximum(counts, 1.0)
    v = jnp.zeros(cols[0].shape[0], jnp.float64)
    for d, sh in zip(cols, shifts):
        v = v + jnp.round(d.astype(jnp.float64) / safe) \
            * np.float64(1 << sh)
    return v
