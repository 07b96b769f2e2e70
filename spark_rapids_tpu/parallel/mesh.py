"""Device mesh construction.

The framework's parallelism axes (SURVEY.md §2.11 mapping):

- ``part`` — partition parallelism: rows are hash/range/round-robin
  partitioned across this axis; the shuffle collective (all_to_all) rides
  it. This is the analog of Spark's task/partition data parallelism.
- ``dp``  — batch parallelism *within* a partition: long scans split their
  row ranges across this axis; reduction-style merges use psum over it.

A 1-D mesh (dp=1) is the common case — one device per Spark-partition
shard. Both axes participate in the shuffle exchange (the mesh is flattened
for hash partitioning), so grouped aggregation lands every key on exactly
one device.

This module is also the policy home for multichip execution sizing
(``multichip_devices``/``mesh_fingerprint``, consumed by exec/sharded.py
and the compile-cache conf fingerprint) and for the collective-primitive
roster tpulint TPU-L016 enforces (``SANCTIONED_COLLECTIVE_MODULES``).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

#: The mesh axis sharded stages and the ICI exchange ride. One name, one
#: place: exec/sharded.py, exchange call sites, and the compile-cache mesh
#: fingerprint all read it from here.
PART_AXIS = "part"

#: Modules allowed to invoke XLA collective primitives (`all_to_all`,
#: `psum`, `shard_map`). tpulint TPU-L016 fails any call site outside this
#: roster: a collective in an unvetted module means a program whose SPMD
#: axis contract nobody reviewed — deadlocks on mismatched meshes, or
#: silent replication where sharding was intended. Keys are repo paths
#: relative to the package root; values document why each module is
#: sanctioned (rendered into docs/metrics.md by gen_docs).
SANCTIONED_COLLECTIVE_MODULES = {
    "parallel/exchange.py":
        "the shuffle collective itself — all_to_all lane exchange plus the "
        "psum axis-size fallback",
    "parallel/distributed.py":
        "hand-built distributed groupby/reduction probes (shard_map + psum) "
        "kept as the minimal-repro harness for mesh debugging",
    "exec/sharded.py":
        "the sharded-execution planner's shard_map dispatch wrapper — one "
        "SPMD program per batch-wave",
    "exec/tpu_nodes.py":
        "ShuffleExchangeExec's ICI repartition path — shard_map over the "
        "exchange collective with per-(src,dst) lane sizing",
}


class MeshDeviceError(RuntimeError):
    """The device set a mesh was built over no longer matches
    ``jax.devices()`` — dispatching onto the stale mesh would hand XLA
    dead device handles and crash opaquely mid-program. Raised by
    ``check_mesh_devices`` before any sharded dispatch."""


def mesh_devices(n: Optional[int] = None) -> Sequence:
    devs = jax.devices()
    if n is None:
        return devs
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return devs[:n]


def _validate_axis_names(axis_names) -> Tuple[str, ...]:
    names = tuple(axis_names)
    if not names:
        raise ValueError("axis_names must name at least one mesh axis")
    for a in names:
        if not isinstance(a, str) or not a:
            raise ValueError(
                f"axis_names must be non-empty strings, got {a!r} in {names!r}")
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate mesh axis names: {names!r}")
    return names


def make_mesh(n_devices: Optional[int] = None, dp: int = 1,
              axis_names=("dp", "part")) -> Mesh:
    axis_names = _validate_axis_names(axis_names)
    devs = list(mesh_devices(n_devices))
    n = len(devs)
    if n % dp != 0:
        raise ValueError(f"dp={dp} does not divide device count {n}")
    if len(axis_names) == 1:
        if dp != 1:
            raise ValueError("dp > 1 needs a two-axis mesh (dp, part)")
        arr = np.asarray(devs)
    else:
        arr = np.asarray(devs).reshape(dp, n // dp)
    return Mesh(arr, axis_names=axis_names)


def check_mesh_devices(mesh: Mesh) -> None:
    """Raise :class:`MeshDeviceError` if any device the mesh was built
    over has since left ``jax.devices()`` (backend restart, runtime
    reinit mid-session). Called before every sharded dispatch wave so
    the failure is a typed, attributable error instead of an opaque XLA
    crash on a dead handle."""
    live = {id(d) for d in jax.devices()}
    stale = [d for d in mesh.devices.flat if id(d) not in live]
    if stale:
        raise MeshDeviceError(
            f"mesh built over {mesh.devices.size} devices but "
            f"{len(stale)} of them are no longer in jax.devices() "
            f"(stale: {[str(d) for d in stale]}); the device runtime was "
            "re-initialized — rebuild the mesh before dispatching")


def multichip_on(conf) -> bool:
    """Whether the session runs over a mesh. An explicit
    ``spark.rapids.sql.multichip.enabled`` decides; unset, the engine
    takes the mesh it is given: on when the process has more than one
    accelerator device, off with one and on the CPU simulator (whose
    virtual devices are a test harness, not a deployment)."""
    from spark_rapids_tpu import config as C
    v = conf.get(C.MULTICHIP_ENABLED)
    if v is not None:
        return bool(v)
    devs = jax.devices()
    return len(devs) > 1 and devs[0].platform != "cpu"


def placement_devices(conf) -> Sequence:
    """The devices a cached table's row ranges are placed over, in mesh
    order (partition p lives on entry p mod n); empty when the session
    runs no mesh."""
    if not multichip_on(conf):
        return ()
    return tuple(mesh_devices(multichip_devices(conf)))


def multichip_devices(conf) -> int:
    """How many devices the `part` axis gets under the session conf:
    ``spark.rapids.sql.multichip.devices`` (0 = all available), clamped
    to what the process actually has. Always >= 1."""
    from spark_rapids_tpu import config as C
    avail = len(jax.devices())
    requested = int(conf.get(C.MULTICHIP_DEVICES) or 0)
    if requested <= 0:
        return avail
    return max(1, min(requested, avail))


def mesh_fingerprint(conf) -> Tuple:
    """The mesh component of the compile-cache conf fingerprint: axis
    name + device count. Sharded executables trace against a specific
    mesh shape, so a 1-device and an 8-device session must never share
    cache entries (ISSUE 20 isolation requirement)."""
    return (PART_AXIS, multichip_devices(conf))
