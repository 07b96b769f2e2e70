"""Whole-stage vertical fusion: one device dispatch per batch per stage.

The framework already fuses each operator's INTERNAL work into one jitted
call (exec/fuse.py header), but a Scan→Filter→Project→partial-HashAggregate
chain still paid one dispatch PER OPERATOR per batch, each with its fixed
host-side cost. This pass is the TPU-idiomatic analog of
Spark's whole-stage codegen (which the reference GPU plugin deliberately
lacks, SURVEY §2.4): it walks the converted TpuExec tree and collapses
maximal linear chains of narrow operators into ONE traced computation, so
the host issues exactly one XLA call per input batch per pipeline stage.

Two collapse shapes:

- a chain of narrow operators (non-trivial Project, Filter, Expand,
  device Limit) becomes a ``FusedStageExec`` whose per-batch function
  composes the members' traced bodies (fuse.StageBody) inside one
  ``fuse.fused`` entry, threading ANSI error planes and per-operator
  carries (ProjectExec's row_base, LimitExec's remaining budget);
- a chain feeding the update phase of a partial/complete
  HashAggregateExec is ABSORBED into the aggregate's update kernel
  (HashAggregateExec.pre_chain — the generalization of the existing
  pre_filter predicate fusion), so scan→filter→project→partial-agg runs
  as one dispatch per batch. Absorption is gated to aggregations taking
  the general sort-based update path: the packed-radix fast path needs
  eager host probes of the evaluated key columns, which a composed trace
  cannot provide, and losing radix would cost more than a dispatch saves.

Fallback: a stage whose composed trace fails on its FIRST batch rebuilds
the unfused operator chain over the remaining input (gated per stage, so
one exotic expression never disables fusion elsewhere). Everything sits
behind spark.rapids.sql.stageFusion.enabled (default on).

Per-operator attribution: the fused function additionally returns each
member's live output row count (a device scalar, added to the member's
NUM_OUTPUT_ROWS as a LazyRowCount — no sync), and the stage's measured
opTime is split evenly across members. stageDispatches counts composed
entries so dispatch-budget tests can assert the one-per-batch contract.
"""
from __future__ import annotations

import logging
import time
from typing import List, Optional

import jax.numpy as jnp

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar.batch import LazyRowCount
from spark_rapids_tpu.exec import compiled, fuse
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import obs as OBS
from spark_rapids_tpu.runtime import trace as TR

log = logging.getLogger("spark_rapids_tpu")


# ---------------------------------------------------------------------------
# The fused stage exec
# ---------------------------------------------------------------------------

class _ReplaySourceExec:
    """Single-use source yielding already-pulled batches then the rest of
    a live iterator (the unfused-fallback bridge: the chain's real input
    iterator has already been advanced and must not re-execute)."""

    def __init__(self, schema, batches, rest):
        self.schema = schema
        self._batches = list(batches)
        self._rest = rest
        self.children: List = []
        self.num_partitions = 1

    def execute_partition(self, ctx, pidx):
        yield from self._batches
        yield from self._rest


def _exec_base():
    from spark_rapids_tpu.exec import tpu_nodes as X
    return X


def rebuild_chain(members, source):
    """Reconstruct the original unfused operator chain over `source`
    (members are child-most first; all construct as (plan, children,
    conf)). Shared by FusedStageExec's and HashAggregateExec's per-stage
    trace-failure fallbacks. Each rebuilt exec shares its member's
    MetricsRegistry so rows processed through the fallback still show up
    under the members that last_metrics() reports."""
    prev = source
    for m in members:
        prev = type(m)(m.plan, [prev], m.conf)
        prev.metrics = m.metrics
    return prev


def make_fused_stage_exec():
    """FusedStageExec is defined against the live TpuExec base lazily to
    keep this module importable without pulling the whole operator
    library at import time."""
    X = _exec_base()

    class FusedStageExec(X.TpuExec):
        """Linear chain of narrow operators executed as ONE composed jit
        per input batch. `members` are the original exec nodes, child-most
        first; they keep their plan nodes (explain/metrics attribution)
        but their driver loops never run — only their stage bodies do."""

        def __init__(self, plan, children, conf, members, stage_id=0):
            super().__init__(plan, children, conf)
            self.members = members
            self.stage_id = stage_id
            self.bodies = [m.stage_body() for m in members]
            self._key = ("fused_stage", tuple(b.key for b in self.bodies))
            self._failed = False
            #: a member matches strings over a byte plane: the members run
            #: apart from the first batch that brings a flat string column
            #: (tpu_nodes.meets_flat_string)
            self._matches_strings = any(X.stage_matches_strings(m)
                                        for m in members)

        @property
        def schema(self):
            return self.members[-1].schema

        def name(self) -> str:
            ops = "+".join(type(m).__name__.replace("Exec", "")
                           for m in reversed(self.members))
            return f"FusedStageExec({ops})"

        def tree_string(self, indent: int = 0) -> str:
            pad = "  " * indent
            sid = self.stage_id
            lines = [f"{pad}*({sid}) {self.name()}"]
            for m in reversed(self.members):
                lines.append(f"{pad}  *({sid}) {type(m).__name__} "
                             f"<- {m.plan.describe()} [fused]")
            lines.append(self.children[0].tree_string(indent + 1))
            return "\n".join(lines)

        def _build(self):
            bodies = self.bodies

            def build():
                fns = [b.builder() for b in bodies]

                def fn(batch, pid, carries):
                    errs_all, rows, out_carries = [], [], []
                    for f, c in zip(fns, carries):
                        batch, errs, c2 = f(batch, pid, c)
                        errs_all.append(errs)
                        out_carries.append(c2)
                        rows.append(jnp.sum(
                            batch.live_mask().astype(jnp.int64)))
                    return (batch, tuple(errs_all), tuple(out_carries),
                            tuple(rows))
                return fn
            return build

        def _unfused_chain(self, source):
            return rebuild_chain(self.members, source)

        def _apart(self, ctx, pidx, batch, rest):
            """`batch` and the `rest` of the input through the member
            chain, each operator its own program."""
            src = _ReplaySourceExec(self.children[0].schema, [batch], rest)
            return self._unfused_chain(src).execute_partition(ctx, pidx)

        def _carry_bounds(self, in_batch, out_batch):
            bounds = [c.bounds for c in in_batch.columns]
            for b in self.bodies:
                if b.bounds_map is None:
                    return
                bounds = b.bounds_map(bounds)
            for c, bd in zip(out_batch.columns, bounds):
                if bd is not None:
                    c.bounds = bd

        def execute_partition(self, ctx, pidx):
            if self._failed:
                yield from self._unfused_chain(
                    self.children[0]).execute_partition(ctx, pidx)
                return
            out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS)
            in_batches = self.metrics.metric(M.NUM_INPUT_BATCHES)
            disp = self.metrics.metric(M.STAGE_DISPATCHES)
            # opTime attribution: the dispatch time splits EVENLY across
            # members (the stage records only dispatch/row metrics itself,
            # so summing opTime over a snapshot is not double-counted)
            member_t = [m.metrics.metric(M.OP_TIME) for m in self.members]
            member_rows = [m.metrics.metric(M.NUM_OUTPUT_ROWS)
                           for m in self.members]
            exhaust_idx = [i for i, b in enumerate(self.bodies)
                           if b.exhausts]
            fn = fuse.fused(self._key, self._build())
            carries = tuple(b.init_carry() for b in self.bodies)
            pid = jnp.int32(pidx)
            it = self.children[0].execute_partition(ctx, pidx)
            first = True
            has_carry = any(b.has_carry for b in self.bodies)
            for batch in it:
                if self._matches_strings and X.meets_flat_string(batch) \
                        and (first or not has_carry):
                    yield from self._apart(ctx, pidx, batch, it)
                    return
                self._acquire(ctx)
                in_batches.add(1)
                t0 = time.perf_counter_ns()
                try:
                    out, errs_all, carries, rows = fn(batch, pid, carries)
                except Exception as ex:
                    # per-stage fallback: run the unfused chain over this
                    # batch and the rest of the input. (A retrace for a
                    # NEW column layout can fail even after other layouts
                    # succeeded, so no first-call-only gate.) ANSI/
                    # analysis errors are deterministic, not trace
                    # failures — re-raise instead of replaying them; and
                    # mid-stream the members' loop carries (row_base,
                    # limit budget) cannot be reconstructed — only a
                    # clean start falls back then.
                    from spark_rapids_tpu.expr.core import SparkException
                    if isinstance(ex, SparkException) or (
                            not first and has_carry):
                        raise
                    self._failed = True
                    OBS.note_exec_fallback("fused_stage")
                    log.warning(
                        "stage fusion trace failed for %s; falling back "
                        "to the unfused chain", self.name(), exc_info=True)
                    yield from self._apart(ctx, pidx, batch, it)
                    return
                first = False
                dt = time.perf_counter_ns() - t0
                if TR.active() is not None:
                    # the stage owns the timing (dt also splits across
                    # member opTime below), so the trace event is emitted
                    # from the already-measured interval instead of a
                    # metric_span; gated so name() never builds when off
                    TR.emit_span(self.name(), t0, dt, cat="exec", args={
                        "stage_id": self.stage_id,
                        "members": len(self.members)})
                    TR.instant("stageDispatch", cat="dispatch",
                               args={"stage_id": self.stage_id})
                for errs in errs_all:
                    compiled.raise_errors(errs)
                disp.add(1)
                share = dt // len(self.members)
                for mt, mr, r in zip(member_t, member_rows, rows):
                    mt.add(share)
                    mr.add(LazyRowCount(r))
                out_rows.add(out.num_rows)
                self._carry_bounds(batch, out)
                if exhaust_idx:
                    # issue the carry D2H NOW and consume it only after
                    # the yield: the scalar transfer overlaps downstream
                    # consumption of this batch instead of serializing
                    # between dispatches (runtime/pipeline.py deferred-
                    # fetch discipline; semantics unchanged — the value
                    # is still read before the next batch is pulled)
                    from spark_rapids_tpu.runtime.pipeline import start_d2h
                    for i in exhaust_idx:
                        start_d2h(carries[i])
                yield out
                # LIMIT early exit: a zero remaining-budget carry means
                # every later batch is all-dead — stop consuming input
                # (one scalar fetch per batch, only when a limit member
                # exists; the unfused LimitExec pays the same sync)
                if exhaust_idx and all(int(carries[i]) <= 0
                                       for i in exhaust_idx):
                    return

    return FusedStageExec


_FUSED_CLS = None


def fused_stage_cls():
    global _FUSED_CLS
    if _FUSED_CLS is None:
        _FUSED_CLS = make_fused_stage_exec()
    return _FUSED_CLS


# ---------------------------------------------------------------------------
# The planner pass
# ---------------------------------------------------------------------------

def _fusable(node) -> bool:
    """Static chain-membership check. Trivial projects join chains for
    free (pure column re-listing inside the trace) but never justify one
    — see _dispatching."""
    X = _exec_base()
    if isinstance(node, (X.ProjectExec, X.FilterExec, X.LimitExec,
                         X.DeviceDecodeScanExec)):
        return len(node.children) == 1
    if isinstance(node, X.ExpandExec):
        if len(node.children) != 1:
            return False
        # cross-projection vocab unification cannot run inside a trace,
        # and output capacity grows n_proj-fold: fixed-width, small fans
        from spark_rapids_tpu import types as T
        if len(node.plan.projections) > 8:
            return False
        return all(not isinstance(dt, (T.StringType, T.ArrayType,
                                       T.StructType, T.MapType))
                   for dt in node.plan.schema.types)
    return False


def _dispatching(node) -> bool:
    """Does this member cost a device dispatch when run unfused? (Trivial
    projects and limits do not; fusing is only worthwhile when >= 2
    dispatching members collapse, or >= 1 absorbs into an aggregate.)"""
    X = _exec_base()
    if isinstance(node, X.ProjectExec):
        return node._trivial_indices() is None
    return isinstance(node, (X.FilterExec, X.ExpandExec,
                             X.DeviceDecodeScanExec))


def _collect_chain(node):
    """Maximal fusable chain starting at `node` going down. Returns
    (members_top_first, input_exec). An already-built FusedStageExec
    decomposes back into its members (so an aggregate constructed over a
    fused chain still absorbs it)."""
    fused_cls = fused_stage_cls()
    chain = []
    cur = node
    while True:
        if isinstance(cur, fused_cls):
            chain.extend(reversed(cur.members))
            cur = cur.children[0]
            continue
        if not _fusable(cur):
            break
        chain.append(cur)
        cur = cur.children[0]
    return chain, cur


def _agg_absorbable(node) -> bool:
    X = _exec_base()
    if not isinstance(node, X.HashAggregateExec):
        return False
    if node.mode not in ("partial", "complete"):
        return False
    # the packed-radix and MXU-bucket fast paths probe EVALUATED key
    # columns host-side per batch; a composed trace cannot feed them, and
    # trading radix for one saved dispatch loses on big batches
    return not node.kern.has_custom and not node.kern._packed_ok


def fuse_stages(exec_root, conf):
    """Entry point: rewrite a converted TpuExec tree, collapsing fusable
    chains (applied by plan/overrides.convert_plan after conversion)."""
    if not conf.get(C.STAGE_FUSION_ENABLED):
        return exec_root
    counter = [0]
    return _rewrite(exec_root, conf, counter)


def fusion_groups(exec_root) -> list:
    """Export the fused stages of a converted exec tree as data (what the
    query-history record stores and the history server renders): one
    entry per stage — id, kind (fused chain vs aggregate-absorbed), and
    the member operator names child-most first (an absorbed chain ends
    with the aggregate it dispatches through). Derived from the ONE
    canonical walk (metrics.walk_exec_tree), so the member/pre-chain/
    no-recurse discipline can never drift from what last_metrics and
    explain_analyze report."""
    from spark_rapids_tpu.runtime.metrics import walk_exec_tree
    groups, cur = [], None
    for _k, node, _d, role, sid in walk_exec_tree(exec_root):
        if role is None:
            cur = None
            if sid is not None:
                cur = {"stage_id": sid,
                       "kind": ("fused" if getattr(node, "members", None)
                                else "absorbed"),
                       "members": [], "_self": type(node).__name__}
                groups.append(cur)
        elif cur is not None:
            cur["members"].append(type(node).__name__)
    for g in groups:
        self_name = g.pop("_self")
        if g["kind"] == "absorbed":
            g["members"].append(self_name)
    return groups


def _rewrite(node, conf, counter):
    X = _exec_base()

    if _agg_absorbable(node):
        chain, input_exec = _collect_chain(node.children[0])
        bodies = [m.stage_body() for m in reversed(chain)]
        # forceSinglePass concatenates the RAW child batches host-side
        # before one update — impossible over still-encoded batches, so
        # a chain rooted at a device-decode scan must not absorb there
        concat_ok = not (conf.get(C.AGG_FORCE_SINGLE_PASS) and any(
            isinstance(m, X.DeviceDecodeScanExec) for m in chain))
        if chain and concat_ok and all(not b.has_carry for b in bodies) \
                and any(_dispatching(m) for m in chain):
            counter[0] += 1
            node.pre_chain = bodies
            node.pre_chain_members = list(reversed(chain))
            node.fused_stage_id = counter[0]
            node.children = [_rewrite(input_exec, conf, counter)]
            return node

    if _fusable(node):
        chain, input_exec = _collect_chain(node)
        # the fusion boundary is 2 dispatching members (any fewer is
        # illegal — plan_verify PV-FUSE); the measured cost pass may
        # RAISE it for this plan when history shows fusion's retrace
        # cost outweighs the dispatch savings
        min_members = 2
        from spark_rapids_tpu.plan import cost as _cost
        h = _cost.current_hints()
        if h is not None and h.fusion_min_members is not None:
            min_members = max(2, int(h.fusion_min_members))
        if sum(1 for m in chain if _dispatching(m)) >= min_members:
            counter[0] += 1
            members = list(reversed(chain))  # child-most first
            cls = fused_stage_cls()
            return cls(node.plan, [_rewrite(input_exec, conf, counter)],
                       conf, members, stage_id=counter[0])

    node.children = [_rewrite(c, conf, counter) for c in node.children]
    return node
