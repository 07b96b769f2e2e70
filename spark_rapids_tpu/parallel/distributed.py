"""Distributed query-step builders: jit-once SPMD programs over a mesh.

Reference parity: one Spark stage in the reference is scan → project/filter
→ partial agg → shuffle write | shuffle read → final agg (SURVEY.md §3.3,
§3.4). Here the WHOLE pipeline — including the exchange — is a single
`shard_map`-ped, jitted XLA program: local compute, `all_to_all` over ICI,
final segmented aggregation, with no host round-trip in the middle.

These builders are the flagship "model" of the framework: what the graft
entry dry-runs multi-chip.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.parallel import exchange as X
from spark_rapids_tpu.runtime import compile_cache as _cc


def splitmix64(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def make_distributed_groupby_sum(mesh: Mesh, filter_fn: Callable,
                                 value_names: Sequence[str]):
    """Build a jitted SPMD step computing
    ``SELECT key, sum(v) FOR v IN value_names, count(*) GROUP BY key``
    with a pre-filter, over rows sharded across the whole mesh.

    Inputs (global arrays, sharded over all mesh axes on dim 0):
      key   : uint64[N]   — normalized group key plane
      valid : bool[N]
      values: dict name -> [N] numeric plane
    `filter_fn(valid, values) -> bool[N]` runs locally before the exchange
    (predicate pushdown below the shuffle, as the reference plans it).

    Returns per-device group planes (keys/count/sum_*/groups) still sharded
    over the mesh — every group lives on exactly one device.
    """
    axes = mesh.axis_names
    nparts = 1
    for a in axes:
        nparts *= mesh.shape[a]

    def step(key, valid, values):
        def shard_fn(key, valid, values):
            keep = valid & filter_fn(valid, values)
            target = (splitmix64(key) % jnp.uint64(nparts)).astype(jnp.int32)
            planes = dict(values)
            planes["__key"] = key
            recv, rvalid = X.all_to_all_exchange(planes, keep, target, axes)
            rkey = recv.pop("__key")
            return X.local_sorted_group_agg(rkey, rvalid, recv)

        spec = P(axes)
        in_specs = (spec, spec, {n: spec for n in values})
        out_spec = {k: spec for k in
                    ["keys", "groups", "count"] + ["sum_" + n for n in value_names]}
        return shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec)(key, valid, values)

    return _cc.jit(step)


def make_distributed_reduction(mesh: Mesh, reduce_fn: Callable):
    """Build a jitted SPMD step for a full reduction (no group keys):
    each device reduces its shard, then `psum` over every mesh axis —
    TPC-H q6 shape (scan → filter → sum)."""
    axes = mesh.axis_names

    def step(valid, values):
        def shard_fn(valid, values):
            local = reduce_fn(valid, values)
            for a in axes:
                local = lax.psum(local, a)
            return local

        spec = P(axes)
        in_specs = (spec, {n: spec for n in values})
        return shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P())(valid, values)

    return _cc.jit(step)


def shard_global(mesh: Mesh, arr: jax.Array) -> jax.Array:
    """Place a host array onto the mesh, sharded over all axes on dim 0."""
    return jax.device_put(arr, NamedSharding(mesh, P(mesh.axis_names)))
