"""Sharded stage execution: one SPMD dispatch per batch-WAVE over the mesh.

Whole-stage fusion (exec/stage_fusion.py) already collapsed each pipeline
stage to one dispatch per batch — but a 16-partition query still issues 16
independent single-device programs per wave of input, and every one of
them pays the full host->device round trip. Under
``spark.rapids.sql.multichip.enabled`` this pass goes one level up: it
rewrites eligible ``FusedStageExec`` nodes into ``ShardedStageExec``,
which packs one batch per partition into a single set of
``[n_shards * capacity]`` planes, lays them across the ``part`` axis of
the device mesh, and runs the SAME composed member-body chain per-shard
inside ``shard_map`` — one XLA dispatch per wave instead of one per
partition, with aggregate HBM bandwidth scaling with the mesh.

Eligibility (the v1 restriction set; everything else falls back per-shard
to the single-device fused path through the tagging tree):

- every member body is carry-free and non-exhausting (a LIMIT budget or
  row_base carry is per-partition loop state that cannot live inside one
  SPMD program);
- the stage's input and output schemas are fixed-width (flat string /
  nested planes are per-batch ragged — their byte-plane shapes differ per
  shard, so they cannot pack into one uniform SPMD operand). Dict-encoded
  shuffle keys still cross the mesh: they ride ShuffleExchangeExec's ICI
  all-to-all, which aligns vocabs host-side before the collective;
- a chain rooted at DeviceDecodeScanExec is excluded for the same
  raggedness reason (encoded vocab planes vary per batch).

The planner records WHY a stage stayed single-device on the node
(``_shard_fallback_reason``) so plan dumps can show it. Runtime failures
(a trace that won't compose under shard_map) degrade the same way the
fused path degrades to the unfused chain: per-slot replay through a fresh
single-device FusedStageExec over the already-pulled batches.

Dispatches ride the ordinary fuse.fused choke point — lifecycle
checkpoints, the device.dispatch fault site, the watchdog, the
dispatch-budget hook, and the compile cache's mesh-fingerprinted keys all
apply unchanged. Per-wave shard row counts feed the kernel cost auditor
(kernel_audit.note_shards) so shard skew shows up as a column in the
roofline table and EXPLAIN ANALYZE.
"""
from __future__ import annotations

import logging
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (ColumnVector, ColumnarBatch,
                                             traced_rows)
from spark_rapids_tpu.exec import compiled, fuse
from spark_rapids_tpu.exec.stage_fusion import (_ReplaySourceExec,
                                                fused_stage_cls)
from spark_rapids_tpu.parallel import mesh as MESH
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import obs as OBS
from spark_rapids_tpu.runtime import trace as TR

log = logging.getLogger("spark_rapids_tpu")

#: column dtypes whose device planes are per-batch ragged: they cannot
#: pack into one uniform SPMD operand (see module header)
_WIDE_TYPES = (T.StringType, T.ArrayType, T.StructType, T.MapType)


class _NotShardable(Exception):
    """Runtime layout guard: a wave's batches cannot pack (dict/encoded
    planes slipped past the static schema check). Triggers the per-slot
    single-device fallback, never an error."""


def _exec_base():
    from spark_rapids_tpu.exec import tpu_nodes as X
    return X


def make_sharded_stage_exec():
    X = _exec_base()

    class ShardedStageExec(X.TpuExec):
        """A fused stage executed per-shard inside shard_map: one SPMD
        dispatch per wave of (up to) n_shards partition batches. Members
        keep their plan nodes and metrics exactly as under FusedStageExec;
        only the dispatch granularity changes."""

        def __init__(self, plan, children, conf, members, stage_id=0,
                     n_shards=1):
            super().__init__(plan, children, conf)
            self.members = members
            self.stage_id = stage_id
            self.n_shards = int(n_shards)
            self.bodies = [m.stage_body() for m in members]
            self._key_bodies = tuple(b.key for b in self.bodies)
            self._mesh = None  # built lazily at first materialization
            self._failed = False
            self._out: Optional[List[list]] = None
            import threading
            self._lock = threading.Lock()

        @property
        def schema(self):
            return self.members[-1].schema

        def name(self) -> str:
            ops = "+".join(type(m).__name__.replace("Exec", "")
                           for m in reversed(self.members))
            return f"ShardedStageExec({ops})x{self.n_shards}"

        def tree_string(self, indent: int = 0) -> str:
            pad = "  " * indent
            sid = self.stage_id
            lines = [f"{pad}*({sid}) {self.name()} "
                     f"[sharded n={self.n_shards}]"]
            for m in reversed(self.members):
                lines.append(f"{pad}  *({sid}) {type(m).__name__} "
                             f"<- {m.plan.describe()} [sharded]")
            lines.append(self.children[0].tree_string(indent + 1))
            return "\n".join(lines)

        # -- dispatch ----------------------------------------------------

        def _build(self, in_dtypes):
            bodies = self.bodies
            mesh = self._mesh
            spec = P(MESH.PART_AXIS)

            def build():
                fns = [b.builder() for b in bodies]

                def shard_fn(col_planes, live, nrows, pid):
                    cols = [ColumnVector(dt, p["data"], p["validity"])
                            for p, dt in zip(col_planes, in_dtypes)]
                    batch = ColumnarBatch(cols, nrows[0], live)
                    errs_all, rows = [], []
                    for f, b in zip(fns, bodies):
                        batch, errs, _ = f(batch, pid[0], b.init_carry())
                        errs_all.append(errs)
                        rows.append(jnp.sum(
                            batch.live_mask().astype(jnp.int64)
                        ).reshape(1))
                    out_planes = [compiled._planes_of(c)
                                  for c in batch.columns]
                    return (out_planes, batch.live_mask(),
                            tuple(errs_all), tuple(rows))

                return shard_map(shard_fn, mesh=mesh,
                                 in_specs=(spec, spec, spec, spec),
                                 out_specs=(spec, spec, spec, spec))
            return build

        def _pack(self, slots, in_dtypes, pids, cap):
            """Concatenate one (possibly absent) batch per shard slot into
            [m*cap] planes. Dead slots pack as all-dead zero planes, so
            every wave dispatches the full mesh shape."""
            m = self.n_shards
            n_cols = len(in_dtypes)
            col_data = [[] for _ in range(n_cols)]
            col_val = [[] for _ in range(n_cols)]
            live_parts, nr_parts, bounds = [], [], []
            for b in slots:
                if b is None:
                    for j, dt in enumerate(in_dtypes):
                        col_data[j].append(jnp.zeros(cap, dt.np_dtype))
                        col_val[j].append(jnp.zeros(cap, jnp.bool_))
                    live_parts.append(jnp.zeros(cap, jnp.bool_))
                    nr_parts.append(jnp.int32(0))
                    bounds.append(None)
                    continue
                bcap = b.capacity
                pad = cap - bcap
                live = b.live_mask()
                if pad:
                    live = jnp.concatenate(
                        [live, jnp.zeros(pad, jnp.bool_)])
                live_parts.append(live)
                nr_parts.append(jnp.asarray(traced_rows(b.num_rows),
                                            jnp.int32))
                bounds.append([c.bounds for c in b.columns])
                for j, c in enumerate(b.columns):
                    d = c.data
                    if isinstance(d, dict):
                        raise _NotShardable(
                            f"column {j} has ragged dict planes")
                    if pad:
                        d = jnp.concatenate(
                            [d, jnp.zeros(pad, d.dtype)])
                    v = c.validity
                    if v is None:
                        v = jnp.ones(bcap, jnp.bool_)
                    if pad:
                        v = jnp.concatenate(
                            [v, jnp.zeros(pad, jnp.bool_)])
                    col_data[j].append(d)
                    col_val[j].append(v)
            planes = [{"data": jnp.concatenate(col_data[j]),
                       "validity": jnp.concatenate(col_val[j])}
                      for j in range(n_cols)]
            live = jnp.concatenate(live_parts)
            nrs = jnp.stack(nr_parts)
            pid_arr = jnp.asarray(
                [pids[i] if i < len(pids) else 0 for i in range(m)],
                jnp.int32)
            return planes, live, nrs, pid_arr, bounds

        def _coalesce(self, batches):
            """Concatenate one partition's pulled batches host-side into
            ONE batch, so a group dispatches one wave per STAGE instead
            of one per upstream batch. Post-exchange partitions hold one
            batch per SENDER (the aggregate merge's unique-key contract
            at the exchange edge), which would otherwise cost n_senders
            waves per stage. Members here are carry-free row-local ops
            (the eligibility set), so batch boundaries within a
            partition carry no semantics for this stage. Numpy concat
            is a memcpy; the packed planes device_put once per wave.
            The stage holds a whole group's partitions at once either
            way, so this does not change the peak-memory order."""
            if len(batches) <= 1:
                return batches
            if any(isinstance(c.data, dict)
                   for b in batches for c in b.columns):
                return batches  # ragged dict planes: per-batch waves
            live = np.concatenate(
                [np.asarray(b.live_mask()) for b in batches])
            cols = []
            for j in range(len(batches[0].columns)):
                parts = [b.columns[j] for b in batches]
                data = np.concatenate(
                    [np.asarray(c.data) for c in parts])
                validity = np.concatenate(
                    [np.ones(c.capacity, np.bool_) if c.validity is None
                     else np.asarray(c.validity) for c in parts])
                cols.append(ColumnVector(parts[0].dtype, data, validity))
            return [ColumnarBatch(cols, int(live.sum()), live)]

        def _out_bounds(self, in_bounds, out_cols):
            if in_bounds is None:
                return
            bounds = in_bounds
            for b in self.bodies:
                if b.bounds_map is None:
                    return
                bounds = b.bounds_map(bounds)
            for c, bd in zip(out_cols, bounds):
                if bd is not None:
                    c.bounds = bd

        # -- fallbacks ---------------------------------------------------

        def _single_delegate(self, source):
            """A single-device FusedStageExec over `source`, sharing this
            node's metrics registry so fallback rows still land under the
            sharded stage in last_metrics/explain."""
            cls = fused_stage_cls()
            d = cls(self.plan, [source], self.conf, self.members,
                    stage_id=self.stage_id)
            d.metrics = self.metrics
            return d

        # -- the wave loop -----------------------------------------------

        def _materialize(self, ctx):
            child = self.children[0]
            nparts = child.num_partitions
            m = self.n_shards
            outs: List[list] = [[] for _ in range(nparts)]
            in_dtypes = [f.dtype for f in child.schema.fields]
            out_dtypes = [f.dtype for f in self.schema.fields]
            out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
            in_batches = self.metrics.metric(M.NUM_INPUT_BATCHES)
            disp = self.metrics.metric(M.STAGE_DISPATCHES)
            waves = self.metrics.metric(M.SHARD_WAVES)
            member_t = [mb.metrics.metric(M.OP_TIME)
                        for mb in self.members]
            member_rows = [mb.metrics.metric(M.NUM_OUTPUT_ROWS)
                           for mb in self.members]
            from spark_rapids_tpu.analysis import kernel_audit as KA
            from spark_rapids_tpu.expr.core import SparkException
            from spark_rapids_tpu.runtime.lifecycle import \
                QueryCancelledError
            from spark_rapids_tpu.runtime.retry import with_retry_no_split
            if self._mesh is None:
                self._mesh = MESH.make_mesh(
                    m, dp=1, axis_names=(MESH.PART_AXIS,))
            sharding = NamedSharding(self._mesh, P(MESH.PART_AXIS))

            for g0 in range(0, nparts, m):
                slot_pids = list(range(g0, min(g0 + m, nparts)))
                if self._failed:
                    for pidx in slot_pids:
                        outs[pidx] = list(self._single_delegate(
                            child).execute_partition(ctx, pidx))
                    continue
                queues = [self._coalesce(list(
                    child.execute_partition(ctx, p)))
                    for p in slot_pids]
                for w in range(max((len(q) for q in queues), default=0)):
                    slots: List[Optional[ColumnarBatch]] = [
                        q[w] if w < len(q) else None for q in queues]
                    n_live = sum(1 for b in slots if b is not None)
                    if n_live == 0:
                        break
                    slots.extend([None] * (m - len(slots)))
                    cap = max(b.capacity for b in slots
                              if b is not None)
                    self._acquire(ctx)
                    MESH.check_mesh_devices(self._mesh)
                    in_batches.add(n_live)
                    t0 = time.perf_counter_ns()
                    try:
                        planes, live, nrs, pid_arr, bounds = self._pack(
                            slots, in_dtypes, slot_pids, cap)
                        key = ("sharded_stage", self._key_bodies, m, cap,
                               tuple(str(dt.np_dtype)
                                     for dt in in_dtypes))
                        fn = fuse.fused(key, self._build(in_dtypes))
                        args = jax.device_put(
                            (planes, live, nrs, pid_arr), sharding)
                        # retry-on-OOM wraps the wave exactly as the
                        # single-device fused dispatch is wrapped: a
                        # device OOM replays the SAME wave (no split —
                        # the pack is already capacity-bucketed), and
                        # only a non-OOM trace failure degrades to the
                        # per-slot fallback below
                        out_planes, out_live, errs_all, rows = \
                            with_retry_no_split(lambda: fn(*args))
                    except (SparkException, MESH.MeshDeviceError,
                            QueryCancelledError):
                        # typed errors (incl. a cooperative cancel at
                        # the compile/dispatch checkpoints) propagate:
                        # the fallback is for shard-map trace failures,
                        # not for resurrecting cancelled work
                        raise
                    except Exception:
                        # per-slot replay through the single-device fused
                        # path: the already-pulled batches must not
                        # re-execute the source (stage_fusion fallback
                        # discipline, lifted one level)
                        self._failed = True
                        OBS.note_exec_fallback("sharded_stage")
                        log.warning(
                            "sharded stage trace failed for %s; falling "
                            "back to the single-device fused path",
                            self.name(), exc_info=True)
                        for i, pidx in enumerate(slot_pids):
                            rest = queues[i][w:]
                            if not rest:
                                continue
                            src = _ReplaySourceExec(
                                child.schema, rest, iter(()))
                            outs[pidx].extend(self._single_delegate(
                                src).execute_partition(ctx, pidx))
                        break
                    dt_ns = time.perf_counter_ns() - t0
                    if TR.active() is not None:
                        TR.emit_span(self.name(), t0, dt_ns, cat="exec",
                                     args={"stage_id": self.stage_id,
                                           "n_shards": m,
                                           "live_slots": n_live})
                    for errs in errs_all:
                        compiled.raise_errors(errs)
                    disp.add(1)
                    waves.add(1)
                    # ONE host assembly per wave, then numpy slicing.
                    # Eager ops on the sharded outputs (a slice, a sum)
                    # each run the full GSPMD partitioner — measured
                    # 20-40x a single-device op on the CPU mesh, and a
                    # sharded jnp.sum even launches a cross-device
                    # all-reduce. device_get only gathers the local
                    # shards (no XLA program). The emitted batches keep
                    # the host numpy planes: every consumer either
                    # feeds them back into a jitted kernel (which
                    # accepts numpy) or packs them for the next wave /
                    # exchange, and per-slice device re-uploads here
                    # measured ~0.15ms x n_slots x n_planes per wave.
                    out_planes, out_live, rows = jax.device_get(
                        (out_planes, out_live, rows))
                    share = dt_ns // len(self.members)
                    for mt, mr, r in zip(member_t, member_rows, rows):
                        mt.add(share)
                        mr.add(int(r.sum()))
                    KA.note_shards(m, rows[-1])
                    cap_out = int(out_live.shape[0]) // m
                    for i, pidx in enumerate(slot_pids):
                        if slots[i] is None:
                            continue
                        lo, hi = i * cap_out, (i + 1) * cap_out
                        mask = out_live[lo:hi]

                        def _slice(x, lo=lo, hi=hi):
                            return None if x is None else x[lo:hi]
                        cols = [compiled._col_from_planes(
                            {k: _slice(v) for k, v in p.items()}, dt)
                            for p, dt in zip(out_planes, out_dtypes)]
                        self._out_bounds(bounds[i], cols)
                        nr = int(mask.sum())
                        out_rows.add(nr)
                        outs[pidx].append(ColumnarBatch(cols, nr, mask))
            return outs

        def execute_partition(self, ctx, pidx):
            with self._lock:
                if self._out is None:
                    self._out = self._materialize(ctx)
            yield from self._out[pidx]

    return ShardedStageExec


_SHARDED_CLS = None


def sharded_stage_cls():
    global _SHARDED_CLS
    if _SHARDED_CLS is None:
        _SHARDED_CLS = make_sharded_stage_exec()
    return _SHARDED_CLS


# ---------------------------------------------------------------------------
# The planner pass
# ---------------------------------------------------------------------------

def _fallback_reason(node) -> Optional[str]:
    """None when the fused stage can shard; otherwise the reason it stays
    single-device (recorded on the node for plan dumps)."""
    X = _exec_base()
    for b in node.bodies:
        if b.has_carry or b.exhausts:
            return (f"member {b.name or b.key[0]} carries per-partition "
                    "loop state (row_base/limit budget) that cannot live "
                    "inside one SPMD program")
    if any(isinstance(mb, X.DeviceDecodeScanExec) for mb in node.members):
        return ("device-decode input planes are per-batch ragged "
                "(encoded vocab sizes differ per shard)")
    schemas = [node.children[0].schema] + [mb.schema for mb in node.members]
    for sch in schemas:
        for f in sch.fields:
            if isinstance(f.dtype, _WIDE_TYPES):
                return (f"column {f.name} is {type(f.dtype).__name__}: "
                        "ragged byte planes cannot pack into one SPMD "
                        "operand")
    return None


def shard_stages(exec_root, conf):
    """Entry point: rewrite eligible FusedStageExec nodes into
    ShardedStageExec (applied by plan/overrides.convert_plan after
    fuse_stages, before pipeline insertion). No-op unless
    spark.rapids.sql.multichip.enabled."""
    if not conf.get(C.MULTICHIP_ENABLED):
        return exec_root
    m = MESH.multichip_devices(conf)
    fused_cls = fused_stage_cls()
    cls = sharded_stage_cls()

    def rewrite(node):
        node.children = [rewrite(c) for c in node.children]
        if isinstance(node, fused_cls):
            reason = _fallback_reason(node)
            if reason is None:
                return cls(node.plan, node.children, node.conf,
                           node.members, stage_id=node.stage_id,
                           n_shards=m)
            node._shard_fallback_reason = reason
            log.debug("stage %d stays single-device: %s",
                      node.stage_id, reason)
        return node

    return rewrite(exec_root)
