"""ICI all-to-all hash exchange — the TPU-native shuffle.

Reference parity: GpuShuffleExchangeExecBase.prepareBatchShuffleDependency
(partition on device, slice, hand to transport) + the UCX/MULTITHREADED
transports of SURVEY.md §2.7. Here the whole exchange is ONE fused XLA
program per device: route rows to per-destination send buffers, a single
`lax.all_to_all` moves them over ICI, and the receive side is immediately
usable — no serialization, no bounce buffers, no fetch protocol.

Static-shape discipline: send buffers are [P, C]. The exec right-sizes C
before tracing: ONE fused count pass over the source partitions fetches
the per-(source, destination) row counts, and C = the global max rounded
to a capacity bucket — so the collective moves ~rows/P per lane instead
of the full local capacity (an ~P-fold ICI bandwidth saving at even
hash spread). Callers without counts fall back to C = local capacity.

All functions here are *per-shard* functions meant to run inside
`shard_map` over a mesh from parallel.mesh. They operate on plane dicts
(name -> [N] array) plus a validity plane, the in-kernel mirror of
columnar.batch.ColumnarBatch.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def route_rows(target: jax.Array, valid: jax.Array, num_parts: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compute the scatter layout sending each row to `target` partition.

    Returns (order, row_idx, col_idx): gather local rows with `order`, then
    scatter them into a [num_parts, C+1] buffer at [row_idx, col_idx]
    (col C is the drop slot for invalid rows).
    """
    n = valid.shape[0]
    t = jnp.where(valid, target.astype(jnp.int32), num_parts)
    order = jnp.argsort(t, stable=True)
    t_sorted = t[order]
    starts = jnp.searchsorted(t_sorted, jnp.arange(num_parts + 1, dtype=t_sorted.dtype))
    pos = jnp.arange(n, dtype=jnp.int32) - starts[jnp.clip(t_sorted, 0, num_parts - 1)].astype(jnp.int32)
    dst_ok = t_sorted < num_parts
    row_idx = jnp.clip(t_sorted, 0, num_parts - 1)
    col_idx = jnp.where(dst_ok, pos, n)
    return order, row_idx, col_idx


def all_to_all_exchange(planes: Dict[str, jax.Array], valid: jax.Array,
                        target: jax.Array, axis_names,
                        send_cap: int = 0
                        ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Exchange rows across the mesh so row i lands on device target[i].

    Per-shard (inside shard_map). `axis_names` is a str or tuple of mesh
    axis names to shuffle over; the number of participating devices P is
    the product of those axis sizes. `send_cap` (static) bounds the rows
    any one source sends to any one destination; 0 = local capacity (the
    conservative bound). Rows past a destination's send_cap are DROPPED —
    callers must size it from real counts. Returns ([P*send_cap] planes,
    [P*send_cap] valid)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    P = 1
    for a in axis_names:
        P *= lax.axis_size(a)
    n = valid.shape[0]
    C = int(send_cap) if send_cap else n
    order, row_idx, col_idx = route_rows(target, valid, P)
    # overflow beyond the sized lane drops into the slack column
    col_idx = jnp.where(col_idx < C, col_idx, C)

    send_valid = (jnp.zeros((P, C + 1), jnp.bool_)
                  .at[row_idx, col_idx].set(valid[order], mode="drop")[:, :C])
    recv_valid = lax.all_to_all(send_valid, axis_names, split_axis=0,
                                concat_axis=0, tiled=True)
    out_valid = recv_valid.reshape(P * C)

    out_planes = {}
    for name, plane in planes.items():
        send = (jnp.zeros((P, C + 1), plane.dtype)
                .at[row_idx, col_idx].set(plane[order], mode="drop")[:, :C])
        recv = lax.all_to_all(send, axis_names, split_axis=0,
                              concat_axis=0, tiled=True)
        out_planes[name] = recv.reshape(P * C)
    return out_planes, out_valid


def broadcast_planes(planes: Dict[str, jax.Array], valid: jax.Array,
                     axis_names) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Replicate a (small) shard to every device over the mesh — the
    broadcast-join build side (reference GpuBroadcastExchangeExec; ICI
    all-gather instead of a driver round-trip)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    v = valid
    out = dict(planes)
    for a in reversed(axis_names):
        v = lax.all_gather(v, a, tiled=True)
        out = {k: lax.all_gather(p, a, tiled=True) for k, p in out.items()}
    return out, v


def local_sorted_group_agg(key: jax.Array, valid: jax.Array,
                           values: Dict[str, jax.Array]
                           ) -> Dict[str, jax.Array]:
    """Pure-array segmented aggregation by a u64 key plane (per shard).

    Sort by key (invalid rows to the end), detect group boundaries, and
    segment-reduce each value plane. Returns planes of length N:
      keys    — group key at each group slot (garbage past num_groups)
      sum_*   — per-group sums for each value plane
      count   — per-group row count
      groups  — scalar-compatible [N] bool marking live group slots
    The in-kernel mirror of ops.groupby's sort-based aggregation, usable
    under shard_map after an exchange.
    """
    n = valid.shape[0]
    big = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    k = jnp.where(valid, key, big)
    order = jnp.argsort(k, stable=True)
    ks = k[order]
    vs = valid[order]
    boundary = jnp.concatenate([jnp.array([True]), ks[1:] != ks[:-1]]) & vs
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg = jnp.where(vs, seg, n - 1)
    out = {"keys": jnp.zeros(n, key.dtype).at[jnp.where(boundary, seg, n - 1)].set(
        jnp.where(boundary, ks, 0), mode="drop")}
    ngroups = jnp.sum(boundary.astype(jnp.int32))
    out["groups"] = jnp.arange(n) < ngroups
    ones = jnp.where(vs, 1, 0)
    out["count"] = jax.ops.segment_sum(ones, seg, num_segments=n)
    for name, plane in values.items():
        p = plane[order]
        p = jnp.where(vs, p, jnp.zeros((), p.dtype))
        out["sum_" + name] = jax.ops.segment_sum(p, seg, num_segments=n)
    return out
