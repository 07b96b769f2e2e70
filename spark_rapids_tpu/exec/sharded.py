"""Sharded stage execution: one SPMD dispatch per batch-WAVE over the mesh.

Whole-stage fusion (exec/stage_fusion.py) already collapsed each pipeline
stage to one dispatch per batch — but a 16-partition query still issues 16
independent single-device programs per wave of input, and every one of
them pays the full host->device round trip. Under a mesh
(``parallel/mesh.multichip_on``) this pass goes one level up. It rewrites
eligible ``FusedStageExec`` nodes into ``ShardedStageExec``, and marks the
partial ``HashAggregateExec`` over a mesh-placed cache (``shard_over``);
both run the SAME per-shard bodies inside ``shard_map`` — one XLA dispatch
per wave instead of one per partition, with aggregate HBM bandwidth
scaling with the mesh.

Where the operands come from (``MeshWave``):

- **in place**: the child is a ``CachedScanExec`` whose partitions were
  placed one a device (uniform planes, one vocabulary a string column).
  The resident shards are assembled into global arrays with
  ``jax.make_array_from_single_device_arrays`` — no copy, no host hop —
  and only the columns the bodies name become operands. What leaves the
  chips is the stage's output (for the aggregate: partial states, a few
  rows a shard), in ONE ``device_get`` (``read_back``): numpy planes,
  host-int row counts, one vocabulary object a string column. A grouped
  aggregate's exchange hands such states on without exchanging them
  while they are few (``ShuffleExchangeExec._bypass``: one batch for the
  final aggregate, laid together by numpy); a global aggregate's merge
  lays them together the same way. ``meshPutBytes`` stays 0.
- **host pack**: any other input. One batch per partition is packed into
  ``[n_shards * capacity]`` planes and ``device_put`` across the ``part``
  axis; the bytes are counted in ``meshPutBytes``.

Eligibility of a fused chain (the v1 restriction set; everything else
falls back per-shard to the single-device fused path through the tagging
tree):

- every member body is carry-free and non-exhausting (a LIMIT budget or
  row_base carry is per-partition loop state that cannot live inside one
  SPMD program);
- flat string / nested planes are per-batch ragged — their byte-plane
  shapes differ per shard, so they cannot pack into one uniform SPMD
  operand. Dictionary-coded strings of a placed cache share one
  vocabulary and shard as their codes; anywhere else dict columns still
  cross the mesh through ShuffleExchangeExec's ICI all-to-all (where
  there are rows enough to exchange), which aligns vocabs host-side
  before the collective;
- a chain rooted at DeviceDecodeScanExec is excluded for the same
  raggedness reason (encoded vocab planes vary per batch).

The planner records WHY a stage stayed single-device on the node
(``_shard_fallback_reason``) so plan dumps can show it. Runtime failures
(a trace that won't compose under shard_map) degrade the same way the
fused path degrades to the unfused chain: per-slot replay through a fresh
single-device FusedStageExec over the already-pulled batches; the
aggregate returns to its per-partition update.

Dispatches ride the ordinary fuse.fused choke point — lifecycle
checkpoints, the device.dispatch fault site, the watchdog, the
dispatch-budget hook, and the compile cache's mesh-fingerprinted keys all
apply unchanged. The dispatch and the read-back of its output are spans
(``shardDispatchTime``, ``shardReadbackTime``) through runtime/trace.py's
one resolver, so a profiler capture names idle gaps by them. Per-wave
shard row counts feed the kernel cost auditor (kernel_audit.note_shards)
and the query's phase account (``mesh.shard_rows``).
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
                                             host_planes, traced_rows)
from spark_rapids_tpu.exec import compiled, fuse
from spark_rapids_tpu.exec.stage_fusion import (_ReplaySourceExec,
                                                fused_stage_cls)
from spark_rapids_tpu.parallel import mesh as MESH
from spark_rapids_tpu.plan.prune import _refs
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import obs as OBS

log = logging.getLogger("spark_rapids_tpu")

#: column dtypes whose device planes are per-batch ragged: they cannot
#: pack into one uniform SPMD operand (see module header)
_WIDE_TYPES = (T.StringType, T.ArrayType, T.StructType, T.MapType)

_SPEC = P(MESH.PART_AXIS)


class _NotShardable(Exception):
    """Runtime layout guard: a wave's batches cannot pack (dict/encoded
    planes slipped past the static schema check). Triggers the per-slot
    single-device fallback, never an error."""


def _exec_base():
    from spark_rapids_tpu.exec import tpu_nodes as X
    return X


def input_refs(members, tail_exprs=None) -> Optional[frozenset]:
    """The child's columns a stage reads: the columns its members'
    expressions name (followed through projections), plus `tail_exprs`
    (the aggregate's keys, inputs and filter) or, with no tail, whatever
    flows to the output. None means every column (a member this walk
    does not know, or a chain that passes its input through)."""
    X = _exec_base()
    cur = None  # current column -> child columns; None is the identity
    used: set = set()

    def deps(exprs) -> set:
        idx: set = set()
        for e in exprs:
            _refs(e, idx)
        if cur is None:
            return idx
        return set().union(*(cur[i] for i in idx)) if idx else set()

    for m in members:
        if isinstance(m, X.FilterExec):
            used |= deps([m.plan.condition])
        elif isinstance(m, X.ProjectExec):
            new = [deps([e]) for e in m.plan.exprs]
            used |= set().union(*new) if new else set()
            cur = new
        else:
            return None
    if tail_exprs is not None:
        used |= deps([e for e in tail_exprs if e is not None])
    elif cur is None:
        return None
    else:
        used |= set().union(*cur) if cur else set()
    return frozenset(used)


def _plane_bytes(tree) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


class MeshWave:
    """What a sharded stage and the sharded partial aggregate share: the
    mesh, a wave's operands (resident shards in place, or a host pack
    put over the mesh), the keyed SPMD program, the read-back of its
    output and the split into one batch a slot. Holds expression-level
    state only: its program lives in the process-wide compile cache and
    must not pin an exec tree (and the HBM-resident batches under it)."""

    def __init__(self, n_shards: int, bodies, in_dtypes,
                 used: Optional[frozenset], tail=None, tail_key=None):
        self.m = int(n_shards)
        self.bodies = list(bodies)
        self.in_dtypes = list(in_dtypes)
        self.used = sorted(used) if used is not None \
            else list(range(len(in_dtypes)))
        #: the aggregate's update phase, `fn(batch) -> (state, errs)`
        #: built by `tail()`, ending the per-shard program; None for a
        #: narrow chain, whose output is its last member's batch
        self.tail = tail
        self._key = (tuple(b.key for b in self.bodies), tail_key, self.m,
                     tuple(self.used),
                     tuple(str(dt.np_dtype) if not isinstance(
                         dt, _WIDE_TYPES) else type(dt).__name__
                         for dt in self.in_dtypes))
        self.mesh = None
        #: per-shard live rows the stage's last body put out, summed over
        #: this query's waves (the phase account's mesh.shard_rows)
        self.shard_rows = np.zeros(self.m, np.int64)

    def ensure_mesh(self):
        if self.mesh is None:
            self.mesh = MESH.make_mesh(self.m, dp=1,
                                       axis_names=(MESH.PART_AXIS,))
        MESH.check_mesh_devices(self.mesh)
        return self.mesh

    # -- operands ----------------------------------------------------------

    def resident_operands(self, shards):
        """Global arrays over the mesh assembled from one resident batch
        a device, no copy; None when the shards are not uniform planes in
        place (then the caller packs, or runs unsharded)."""
        mesh = self.ensure_mesh()
        devs = list(mesh.devices.flat)
        if shards is None or len(shards) != self.m or \
                any(b is None or b.row_mask is not None for b in shards):
            return None
        cap = shards[0].capacity
        if any(b.capacity != cap for b in shards):
            return None
        part = NamedSharding(mesh, _SPEC)
        repl = NamedSharding(mesh, P())

        def assemble(arrs, sharding, scale):
            a0 = arrs[0]
            for a, d in zip(arrs, devs):
                if not isinstance(a, jax.Array) or a.shape != a0.shape \
                        or a.dtype != a0.dtype or a.devices() != {d}:
                    return None
            shape = (a0.shape[0] * scale,) + a0.shape[1:]
            return jax.make_array_from_single_device_arrays(
                shape, sharding, list(arrs))

        planes, layout = [], []
        for j in self.used:
            cols = [b.columns[j] for b in shards]
            has_v = cols[0].validity is not None
            if any((c.validity is not None) != has_v for c in cols):
                return None
            if all(c.is_dict for c in cols):
                out = {"codes": assemble([c.data["codes"] for c in cols],
                                         part, self.m),
                       "dict_offsets": assemble(
                           [c.data["dict_offsets"] for c in cols], repl, 1),
                       "dict_bytes": assemble(
                           [c.data["dict_bytes"] for c in cols], repl, 1)}
                kind = ("dict", out["dict_offsets"].shape
                        if out["dict_offsets"] is not None else None,
                        out["dict_bytes"].shape
                        if out["dict_bytes"] is not None else None,
                        all(c.dict_unique for c in cols))
            elif any(isinstance(c.data, dict) for c in cols):
                return None  # flat strings, nested: ragged a shard
            else:
                out = {"data": assemble([c.data for c in cols], part,
                                        self.m)}
                kind = ("fixed",)
            out["validity"] = assemble([c.validity for c in cols], part,
                                       self.m) if has_v else None
            if any(v is None for k, v in out.items() if k != "validity") \
                    or (has_v and out["validity"] is None):
                return None
            planes.append(out)
            layout.append(kind + (has_v,))
        nrows = np.asarray([int(b.num_rows) for b in shards], np.int32)
        bounds = [[c.bounds for c in b.columns] for b in shards]
        return planes, None, nrows, cap, tuple(layout), bounds

    def packed_operands(self, slots, cap):
        """One (possibly absent) batch per shard slot concatenated into
        [m*cap] planes on the host side and put over the mesh. Dead slots
        pack as all-dead zero planes, so every wave dispatches the full
        mesh shape. Returns the operands and the bytes put."""
        mesh = self.ensure_mesh()
        m = self.m
        col_data = {j: [] for j in self.used}
        col_val = {j: [] for j in self.used}
        live_parts, nr_parts, bounds = [], [], []
        for b in slots:
            if b is None:
                for j in self.used:
                    dt = self.in_dtypes[j]
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
            for j in self.used:
                c = b.columns[j]
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
                  for j in self.used]
        live = jnp.concatenate(live_parts)
        nrs = jnp.stack(nr_parts)
        put = _plane_bytes((planes, live))
        planes, live, nrs = jax.device_put(
            (planes, live, nrs), NamedSharding(mesh, _SPEC))
        layout = tuple(("fixed", True) for _ in self.used)
        return (planes, live, nrs, cap, layout, bounds), put

    # -- the program -------------------------------------------------------

    def _build(self, layout, masked: bool, cap: int):
        bodies, tail = self.bodies, self.tail
        in_dtypes, used, mesh = self.in_dtypes, self.used, self.mesh

        def plane_specs(kind):
            if kind[0] == "dict":
                sp = {"codes": _SPEC, "dict_offsets": P(),
                      "dict_bytes": P()}
            else:
                sp = {"data": _SPEC}
            sp["validity"] = _SPEC if kind[-1] else None
            return sp

        def build():
            fns = [b.builder() for b in bodies]
            upd = tail() if tail is not None else None

            def shard_fn(col_planes, live, nrows, pid):
                # columns the stage does not name hold no planes: a body
                # that touched one fails the trace, never reads garbage
                cols = [ColumnVector(dt, jax.ShapeDtypeStruct(
                    (cap,), jnp.int8), None) for dt in in_dtypes]
                for j, p, kind in zip(used, col_planes, layout):
                    cols[j] = compiled._col_from_planes(p, in_dtypes[j])
                    if kind[0] == "dict":
                        cols[j].dict_unique = kind[3]
                batch = ColumnarBatch(cols, nrows[0], live)
                errs_all, rows = [], []
                for f, b in zip(fns, bodies):
                    batch, errs, _ = f(batch, pid[0], b.init_carry())
                    errs_all.append(errs)
                    rows.append(jnp.sum(
                        batch.live_mask().astype(jnp.int64)).reshape(1))
                if upd is not None:
                    if not rows:  # the rows the update phase takes in
                        rows.append(jnp.sum(
                            batch.live_mask().astype(jnp.int64)
                        ).reshape(1))
                    batch, errs = upd(batch)
                    errs_all.append(errs)
                out_planes = [compiled._planes_of(c)
                              for c in batch.columns]
                out_rows = jnp.asarray(traced_rows(batch.num_rows),
                                       jnp.int32).reshape(1)
                return (out_planes, batch.live_mask(), out_rows,
                        tuple(errs_all), tuple(rows))

            return shard_map(
                shard_fn, mesh=mesh,
                in_specs=([plane_specs(k) for k in layout],
                          _SPEC if masked else None, _SPEC, _SPEC),
                out_specs=_SPEC)
        return build

    def dispatch(self, operands, pids):
        """Issue the wave's SPMD program (keyed, retried on OOM); its
        outputs stay on the mesh until `read_back`."""
        from spark_rapids_tpu.runtime.retry import with_retry_no_split
        planes, live, nrows, cap, layout, _bounds = operands
        key = ("sharded_stage",) + self._key + (cap, layout,
                                                live is not None)
        fn = fuse.fused(key, self._build(layout, live is not None, cap))
        pid_arr = np.asarray(
            [pids[i] if i < len(pids) else 0 for i in range(self.m)],
            np.int32)
        # retry-on-OOM wraps the wave exactly as the single-device fused
        # dispatch is wrapped: a device OOM replays the SAME wave (no
        # split — the operands are already capacity-bucketed), and only
        # a non-OOM trace failure degrades to the caller's fallback
        return with_retry_no_split(
            lambda: fn(planes, live, nrows, pid_arr))

    def read_back(self, out, present, out_dtypes):
        """The wave's output on the host, split into one batch a present
        slot: ONE host assembly, then numpy slicing. Eager ops on the
        sharded outputs (a slice, a sum) each run the full GSPMD
        partitioner — measured 20-40x a single-device op on the CPU
        mesh, and a sharded jnp.sum even launches a cross-device
        all-reduce. device_get only gathers the local shards (no XLA
        program). The emitted batches keep the host numpy planes: every
        consumer either feeds them back into a jitted kernel (which
        accepts numpy) or packs them for the next wave / exchange.
        Returns ({slot: batch}, rows per body [m])."""
        from spark_rapids_tpu.analysis import kernel_audit as KA
        m = self.m
        out_planes, out_live, out_rows, errs_all, rows = \
            host_planes(out, "shard")
        for errs in errs_all:
            compiled.raise_errors(errs)
        KA.note_shards(m, rows[-1])
        self.shard_rows += np.asarray(rows[-1], np.int64)

        def part(x, i):
            if x is None:
                return None
            k = x.shape[0] // m
            return x[i * k:(i + 1) * k]

        # every shard ran one program over one vocabulary: the slots
        # share slot 0's copy, ONE object a plane, so a consumer that
        # asks "same vocabulary?" by identity hears yes
        vocabs = [{k: part(p[k], 0) for k in ("dict_offsets", "dict_bytes")}
                  if "codes" in p else {} for p in out_planes]
        batches = {}
        for i in present:
            cols = []
            for p, vocab, dt in zip(out_planes, vocabs, out_dtypes):
                sl = {k: part(v, i) for k, v in p.items()}
                sl.update(vocab)
                cols.append(compiled._col_from_planes(sl, dt))
            batches[i] = ColumnarBatch(cols, int(out_rows[i]),
                                       part(out_live, i))
        return batches, rows


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
            self.shard_wave = MeshWave(
                n_shards, self.bodies,
                [f.dtype for f in children[0].schema.fields],
                input_refs(members))
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
            # every plane of every batch in ONE read (none where the
            # batches are the host's already)
            live, data, valid = host_planes((
                [b.live_mask() for b in batches],
                [[c.data for c in b.columns] for b in batches],
                [[c.validity for c in b.columns] for b in batches]), "shard")
            live = np.concatenate([np.asarray(v) for v in live])
            cols = []
            for j in range(len(batches[0].columns)):
                parts = [b.columns[j] for b in batches]
                plane = np.concatenate([np.asarray(d[j]) for d in data])
                validity = np.concatenate(
                    [np.ones(c.capacity, np.bool_) if v[j] is None
                     else np.asarray(v[j]) for c, v in zip(parts, valid)])
                cols.append(ColumnVector(parts[0].dtype, plane, validity))
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
            wave = self.shard_wave
            outs: List[list] = [[] for _ in range(nparts)]
            out_dtypes = [f.dtype for f in self.schema.fields]
            out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
            in_batches = self.metrics.metric(M.NUM_INPUT_BATCHES)
            disp = self.metrics.metric(M.STAGE_DISPATCHES)
            waves = self.metrics.metric(M.SHARD_WAVES)
            put_bytes = self.metrics.metric(M.MESH_PUT_BYTES)
            disp_t = self.metrics.metric(M.SHARD_DISPATCH_TIME)
            back_t = self.metrics.metric(M.SHARD_READBACK_TIME)
            member_t = [mb.metrics.metric(M.OP_TIME)
                        for mb in self.members]
            member_rows = [mb.metrics.metric(M.NUM_OUTPUT_ROWS)
                           for mb in self.members]
            from spark_rapids_tpu.expr.core import SparkException
            from spark_rapids_tpu.runtime.lifecycle import \
                QueryCancelledError
            resident = getattr(child, "resident_shards", None)

            for g0 in range(0, nparts, m):
                slot_pids = list(range(g0, min(g0 + m, nparts)))
                if self._failed:
                    for pidx in slot_pids:
                        outs[pidx] = list(self._single_delegate(
                            child).execute_partition(ctx, pidx))
                    continue
                # a cache placed over the mesh is consumed where it
                # lives; anything else is pulled, packed and put
                placed = None
                if resident is not None and len(slot_pids) == m:
                    placed = wave.resident_operands(resident(slot_pids))
                if placed is not None:
                    queues = [[None]] * len(slot_pids)
                else:
                    queues = [self._coalesce(list(
                        child.execute_partition(ctx, p)))
                        for p in slot_pids]
                for w in range(max((len(q) for q in queues), default=0)):
                    slots: List[Optional[ColumnarBatch]] = [
                        q[w] if w < len(q) else None for q in queues]
                    present = [i for i, b in enumerate(slots)
                               if b is not None or placed is not None]
                    if not present:
                        break
                    slots.extend([None] * (m - len(slots)))
                    self._acquire(ctx)
                    in_batches.add(len(present))
                    t0 = time.perf_counter_ns()
                    try:
                        with self.span(disp_t):
                            if placed is not None:
                                operands = placed
                            else:
                                cap = max(b.capacity for b in slots
                                          if b is not None)
                                operands, put = wave.packed_operands(
                                    slots, cap)
                                put_bytes.add(put)
                            out = wave.dispatch(operands, slot_pids)
                        with self.span(back_t):
                            batches, rows = wave.read_back(
                                out, present, out_dtypes)
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
                            if placed is not None:
                                outs[pidx].extend(self._single_delegate(
                                    child).execute_partition(ctx, pidx))
                                continue
                            rest = queues[i][w:]
                            if not rest:
                                continue
                            src = _ReplaySourceExec(
                                child.schema, rest, iter(()))
                            outs[pidx].extend(self._single_delegate(
                                src).execute_partition(ctx, pidx))
                        break
                    dt_ns = time.perf_counter_ns() - t0
                    disp.add(1)
                    waves.add(1)
                    share = dt_ns // len(self.members)
                    for mt, mr, r in zip(member_t, member_rows, rows):
                        mt.add(share)
                        mr.add(int(r.sum()))
                    bounds = operands[5]
                    for i in present:
                        b = batches[i]
                        self._out_bounds(bounds[i], b.columns)
                        out_rows.add(b.num_rows)
                        outs[slot_pids[i]].append(b)
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
    child = node.children[0]
    used = input_refs(node.members)
    in_fields = child.schema.fields
    placed = isinstance(child, X.CachedScanExec)
    for i, f in enumerate(in_fields):
        if used is not None and i not in used:
            continue  # a column no member names never becomes an operand
        if isinstance(f.dtype, T.StringType) and placed:
            continue  # one vocabulary over the mesh: shards as its codes
        if isinstance(f.dtype, _WIDE_TYPES):
            return (f"column {f.name} is {type(f.dtype).__name__}: "
                    "ragged byte planes cannot pack into one SPMD "
                    "operand")
    for mb in node.members:
        through = _string_passthrough(mb) if placed else ()
        for i, f in enumerate(mb.schema.fields):
            if isinstance(f.dtype, _WIDE_TYPES) and i not in through:
                return (f"column {f.name} is {type(f.dtype).__name__}: "
                        "ragged byte planes cannot pack into one SPMD "
                        "operand")
    return None


def _string_passthrough(member) -> set:
    """Output columns of `member` that are an input column handed on
    untouched (a filter's all; a projection's bare references): a coded
    string keeps its vocabulary through them."""
    from spark_rapids_tpu.expr.core import Alias, BoundRef
    X = _exec_base()
    if isinstance(member, X.FilterExec):
        return set(range(len(member.schema.fields)))
    out = set()
    if isinstance(member, X.ProjectExec):
        for i, e in enumerate(member.plan.exprs):
            inner = e.children[0] if isinstance(e, Alias) else e
            if isinstance(inner, BoundRef):
                out.add(i)
    return out


def _agg_fallback_reason(node) -> Optional[str]:
    """None when the partial aggregate's update phase can run per shard
    over a placed cache; otherwise why it keeps its per-partition path."""
    from spark_rapids_tpu.expr.core import BoundRef
    if node.kern.has_custom:
        return "custom segmented aggregates hold no mergeable partial state"
    if node.conf.get(C.AGG_FORCE_SINGLE_PASS):
        return "forceSinglePass concatenates the raw input on one device"
    if any(not isinstance(e, BoundRef) for e in node.plan.group_exprs) \
            and node.kern._packed_ok:
        # computed keys take the packed-radix path, whose host probe of
        # the evaluated key columns has no place inside one SPMD program
        return "group keys are expressions probed on the host a batch"
    if any(b.has_carry or b.exhausts for b in node.pre_chain or ()):
        return "an absorbed member carries per-partition loop state"
    return None


def shard_stages(exec_root, conf):
    """Entry point: rewrite eligible FusedStageExec nodes into
    ShardedStageExec and mark the partial aggregate over a cached table
    to run its update phase per shard (applied by
    plan/overrides.convert_plan after fuse_stages, before pipeline
    insertion). No-op unless the session runs a mesh."""
    if not MESH.multichip_on(conf):
        return exec_root
    X = _exec_base()
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
        elif isinstance(node, X.HashAggregateExec) \
                and node.mode == "partial" \
                and isinstance(node.children[0], X.CachedScanExec):
            reason = _agg_fallback_reason(node)
            if reason is None:
                node.shard_over = m
            else:
                node._shard_fallback_reason = reason
        return node

    return rewrite(exec_root)
