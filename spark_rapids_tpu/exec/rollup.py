"""GROUP BY ROLLUP as one sort: `RollupAggregateExec`.

The lowering of ROLLUP (sql/dataframe.py `_agg_grouping_sets`) is
Aggregate(keys + grouping id) over Expand(one projection a level): every
input row is written once a level and the copies are grouped again, nine
times the rows for the eight keys of TPC-DS query 67. A rollup's levels
are the prefixes of one key list, so one order serves them all: sort the
rows once by the whole list, and level k's groups are the runs over which
the first k keys do not change. Sums are differences of ONE set of prefix
sums at the runs' ends (exact integer planes: ops/radix.f64_sum_planes), so
a group that holds the same rows at two levels has the same sum at both,
bit for bit, whatever the order of its rows.

The planner (plan/overrides.py) puts this exec in place of
HashAggregateExec(ExpandExec(child)) where `rollup_shape` recognises the
shape from the plan alone; what it cannot know until it sees a batch
(whether the keys pack into two int64 planes: ops/radix.plan_packing_planes)
is decided there, and a batch it cannot take runs through the general pair
of operators it replaced.

Three keyed programs and the shared sort a batch, one read-back (the
levels' group counts, which size the output):

  rollup_pack   keys -> packed planes, aggregate inputs -> integer planes
  argsort/take  exec/tpu_nodes._argsort_planes (one pass a plane)
  rollup_scan   sorted planes, how many leading keys each row shares with
                the row before it, exclusive prefix sums, counts a level
  rollup_emit   a level's runs compacted from the level below it, totals
                as differences; the levels end to end by one gather a
                plane, then the key columns unpacked and the aggregates
                evaluated once over the output batch
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnVector, ColumnarBatch, LazyRowCount, host_planes, round_capacity,
    traced_rows,
)
from spark_rapids_tpu.exec import compiled, fuse
from spark_rapids_tpu.exec import tpu_nodes as X
from spark_rapids_tpu.expr.core import BoundRef, Cast, EvalCtx, Literal
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.ops import radix as R
from spark_rapids_tpu.ops.pallas_decode import _cumsum
from spark_rapids_tpu.plan import nodes as P
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.obs.phases import device_mark

#: state reductions that are differences of prefix sums
_OPS = frozenset({"sum", "count", "count_all"})


@dataclasses.dataclass(frozen=True)
class RollupShape:
    """What `rollup_shape` read off Aggregate(Expand(child))."""
    key_exprs: tuple    # the rollup's keys, bound to the child's schema
    levels: tuple       # (keys kept, grouping id) a projection
    agg_inputs: tuple   # an aggregate's inputs, bound to the child's schema


def _const_int(e) -> Optional[int]:
    if isinstance(e, Cast):
        e = e.children[0]
    if isinstance(e, Literal) and isinstance(e.value, int) \
            and not isinstance(e.value, bool):
        return e.value
    return None


def rollup_shape(agg: P.Aggregate, ex: P.Expand) -> Optional[RollupShape]:
    """The shape of a rollup in Aggregate(Expand(child)), or None: group
    keys that are the Expand's key columns and then its grouping id, each
    projection keeping a PREFIX of the keys and nulling the rest, the
    other columns passed through alike by every projection, and
    aggregates over those that reduce by sums and counts alone."""
    from spark_rapids_tpu.expr.aggregates import SegmentedAgg
    refs = agg.group_exprs
    if len(refs) < 2 or not all(isinstance(e, BoundRef) for e in refs):
        return None
    key_cols = [e.index for e in refs[:-1]]
    gid_col = refs[-1].index
    rows = ex.projections
    if len(set(key_cols + [gid_col])) != len(refs):
        return None
    keys: List = [None] * len(key_cols)
    levels = []
    for row in rows:
        gid = _const_int(row[gid_col])
        if gid is None:
            return None
        kept = 0
        for j, c in enumerate(key_cols):
            e = row[c]
            if isinstance(e, Literal) and e.value is None:
                continue
            if j != kept or (keys[j] is not None and
                             e.fingerprint() != keys[j].fingerprint()):
                return None  # not a prefix, or not the same key
            keys[j] = e
            kept += 1
        levels.append((kept, gid))
    if any(k is None for k in keys) or \
            len({k for k, _ in levels}) != len(levels):
        return None
    special = set(key_cols) | {gid_col}

    def through(e):
        """`e` over the Expand's output as the same over its child, or
        None where it reads a key, the id or a column the projections do
        not hand on alike."""
        bad = []

        def f(x):
            if isinstance(x, BoundRef):
                col = [row[x.index] for row in rows]
                if x.index in special or any(
                        c.fingerprint() != col[0].fingerprint()
                        for c in col[1:]):
                    bad.append(x)
                    return x
                return col[0]
            return x
        out = e.transform(f)
        return None if bad else out

    agg_inputs = []
    for a in agg.aggs:
        if isinstance(a.fn, SegmentedAgg):
            return None
        for (_, sdt), (op, _) in zip(a.fn.state_schema(), a.fn.update_ops()):
            if op not in _OPS or isinstance(
                    sdt, (T.StringType, T.ArrayType, T.MapType,
                          T.StructType, T.DecimalType)):
                return None
        ins = [through(c) for c in a.fn.children]
        if any(i is None for i in ins):
            return None
        agg_inputs.append(tuple(ins))
    return RollupShape(tuple(keys), tuple(levels), tuple(agg_inputs))


def _states(plan: P.Aggregate):
    """(aggregate, input, op, state type) of every state column."""
    out = []
    for ai, a in enumerate(plan.aggs):
        for (_, sdt), (op, idx) in zip(a.fn.state_schema(),
                                       a.fn.update_ops()):
            out.append((ai, idx, op, sdt))
    return out


def _is_float(sdt) -> bool:
    return np.dtype(sdt.np_dtype).kind == "f"


def _build_pack(shape: RollupShape, states, specs, ansi: bool):
    def rollup_pack(batch, ranges):
        live = batch.live_mask()
        ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                       batch.capacity, ansi, live=live)
        kcols = [e.eval_tpu(ectx) for e in shape.key_exprs]
        planes = [R.pack_keys(sp, kcols[a:b], ranges[2 * a: 2 * b], live)
                  for sp, a, b in specs]
        srcs = [[e.eval_tpu(ectx) for e in ins] for ins in shape.agg_inputs]
        # a state's input and the rows of it that count, as they lie: the
        # scan takes them into the sorted order, one gather each
        values = []
        for ai, idx, op, _ in states:
            if op == "count_all":
                continue
            src = srcs[ai][idx]
            valid = live if src.validity is None else (src.validity & live)
            values.append((valid, None if op == "count" else src.data))
        return planes, values, dict(ectx.errors)
    return rollup_pack


def _build_scan(states, specs, level_keys: Tuple[int, ...]):
    nk = specs[-1][2]
    kept = [st for st in states if st[2] != "count_all"]

    def rollup_scan(planes, perm, values):
        cap = perm.shape[0]
        sp = [p[perm] for p in planes]
        n_live = jnp.sum((sp[0] != R._SENTINEL).astype(jnp.int32))
        pos = jnp.arange(cap, dtype=jnp.int32)
        # shared[i]: the leading keys row i has in common with row i - 1
        shared = jnp.zeros(cap, jnp.int32)
        same = jnp.ones(cap, jnp.bool_)
        for (spec, _, _), cur in zip(specs, sp):
            prev = jnp.roll(cur, 1)
            shift = spec.total_bits
            for b in spec.bits:
                shift -= b
                shared = shared + (same & (
                    (cur >> jnp.int64(shift)) == (prev >> jnp.int64(shift)))
                ).astype(jnp.int32)
            same = same & (cur == prev)
        shared = jnp.where(pos == 0, -1, shared)
        shared = jnp.where(pos < n_live, shared, nk)
        # exclusive prefix sums in the sorted order and the whole sums, by
        # name: "pos" the row's own position (count(*)); a state's "n<i>"
        # rows that count; a sum's "v<i>" (integers) or "hi<i>", "lo<i>",
        # "spec<i>", "ninf<i>" (ops/radix.f64_sum_planes)
        rows = {"pos": jnp.ones(cap, jnp.int32)}
        scales, special = [], jnp.int32(0)
        for i, ((_, _, op, sdt), (valid, data)) in enumerate(
                zip(kept, values)):
            valid = valid[perm]
            rows[f"n{i}"] = valid.astype(jnp.int32)
            if op == "sum" and _is_float(sdt):
                fplanes, scale = R.f64_sum_planes(data[perm], valid)
                rows.update(zip((f"hi{i}", f"lo{i}", f"spec{i}",
                                 f"ninf{i}"), fplanes))
                scales.append(scale)
                special = special + jnp.sum(
                    (fplanes[2] != 0).astype(jnp.int32)) + jnp.sum(fplanes[3])
            elif op == "sum":
                rows[f"v{i}"] = jnp.where(valid, data[perm].astype(jnp.int64),
                                          jnp.int64(0))
        before, whole = {"pos": pos}, {"pos": n_live}
        for name, x in rows.items():
            if name != "pos":
                c = _cumsum(x)
                before[name], whole[name] = c - x, c[-1]
        counts = jnp.stack(
            [jnp.sum((shared < k).astype(jnp.int32)) for k in level_keys]
            + [special])
        return sp, shared, before, whole, scales, counts
    return rollup_scan


def _needed(states, plain: bool) -> List[str]:
    """The prefix sums the emit reads: a float sum with no NaN or
    infinity among its inputs (`plain`, from the scan's read-back) needs
    neither of the planes that count them."""
    names, i = [], 0
    for _, _, op, sdt in states:
        if op == "count_all":
            names.append("pos")
            continue
        names.append(f"n{i}")
        if op == "sum" and _is_float(sdt):
            names += [f"hi{i}", f"lo{i}"] + (
                [] if plain else [f"spec{i}", f"ninf{i}"])
        elif op == "sum":
            names.append(f"v{i}")
        i += 1
    return sorted(set(names))


def _build_emit(gid_type, shape: RollupShape, states, specs, kern,
                caps: Tuple[int, ...], out_cap: int, plain: bool):
    # the levels from the finest down: each is compacted out of the one
    # before it
    order = sorted(range(len(shape.levels)),
                   key=lambda i: -shape.levels[i][0])
    # where a level's runs start in the levels laid end to end at their
    # capacities, and what a level keeps and calls itself
    laid = np.concatenate([[0], np.cumsum([caps[li] for li in order])])
    keeps = np.array([shape.levels[li][0] for li in order], np.int32)
    gids = np.array([shape.levels[li][1] for li in order],
                    gid_type.np_dtype)

    def rollup_emit(batch, ranges, sp, shared, before, whole, scales,
                    counts):
        ectx = EvalCtx(batch.columns, traced_rows(batch.num_rows),
                       batch.capacity, False, live=batch.live_mask())
        kcols = [e.eval_tpu(ectx) for e in shape.key_exprs]
        cur = {"sp": sp, "shared": shared, "before": before}
        n_prev = shared.shape[0]
        planes: List[List[jax.Array]] = [[] for _ in sp]
        totals = {name: [] for name in before}
        for li in order:
            keep, cap, n = shape.levels[li][0], caps[li], counts[li]
            sel = jnp.clip(K._compact_indices(cur["shared"] < keep, n_prev,
                                              cap), 0)
            cur = jax.tree_util.tree_map(lambda x: x[sel], cur)
            n_prev = n
            g = jnp.arange(cap, dtype=jnp.int32)
            for p, plane in zip(planes, cur["sp"]):
                p.append(plane)
            # a run's sum: the prefix sum before the next run of its
            # level (the whole sum behind the last) less the one before it
            for name, ex in cur["before"].items():
                nxt = jnp.concatenate([ex[1:], ex[-1:]])
                totals[name].append(
                    jnp.where(g == n - 1, whole[name], nxt) - ex)
        # the levels end to end, each at its count: slot j of the output
        # is run j - first[level] of its level
        ns = jnp.stack([counts[li] for li in order])
        first = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                 jnp.cumsum(ns)[:-1].astype(jnp.int32)])
        j = jnp.arange(out_cap, dtype=jnp.int32)
        level = jnp.zeros(out_cap, jnp.int32)
        for k in range(1, len(order)):
            level = level + (j >= first[k]).astype(jnp.int32)
        src = jnp.asarray(laid[:-1], jnp.int32)[level] + j - first[level]
        src = jnp.clip(src, 0, int(laid[-1]) - 1)
        keep_j = jnp.asarray(keeps)[level]

        def slot(pieces):
            return jnp.concatenate(pieces)[src]

        cols: List[ColumnVector] = []
        for (spec, a, b), pieces in zip(specs, planes):
            for i, c in enumerate(R.unpack_keys(
                    spec, slot(pieces), ranges[2 * a: 2 * b], kcols[a:b]), a):
                cols.append(ColumnVector(c.dtype, c.data,
                                         c.validity & (keep_j > i),
                                         dict_unique=c.dict_unique))
        cols.append(ColumnVector(gid_type, jnp.asarray(gids)[level], None))
        tot = {name: slot(t) for name, t in totals.items()}
        none = jnp.zeros(out_cap, jnp.int32)
        i, si = 0, 0   # state with an input, scale
        for _, _, op, sdt in states:
            if op == "count_all":
                cols.append(ColumnVector(
                    sdt, tot["pos"].astype(sdt.np_dtype),
                    jnp.ones(out_cap, jnp.bool_)))
                continue
            n_valid = tot[f"n{i}"]
            if op == "count":
                s_ = n_valid
            elif _is_float(sdt):
                s_ = R.f64_sum_finish(
                    tot[f"hi{i}"], tot[f"lo{i}"],
                    none.astype(jnp.int64) if plain else tot[f"spec{i}"],
                    none if plain else tot[f"ninf{i}"], scales[si])
                si += 1
            else:
                s_ = tot[f"v{i}"]
            cols.append(ColumnVector(
                sdt, s_.astype(sdt.np_dtype),
                jnp.ones(out_cap, jnp.bool_) if op == "count"
                else n_valid > 0))
            i += 1
        total = jnp.sum(ns)
        return kern._evaluate_states(
            ColumnarBatch(cols, LazyRowCount(total))).columns
    return rollup_emit


class RollupAggregateExec(X.TpuExec):
    """Aggregate(Expand(child)) of rollup shape (module docstring). One
    partition; its child is the Expand's child."""

    def __init__(self, plan, children, conf, expand_plan: P.Expand,
                 shape: RollupShape):
        super().__init__(plan, children, conf)
        self.expand_plan = expand_plan
        self.shape = shape
        self.kern = X._AggKernels(plan.group_exprs, plan.group_names,
                                  plan.aggs, None)

    def tree_string(self, indent: int = 0) -> str:
        head, nl, rest = super().tree_string(indent).partition("\n")
        return (f"{head} [rollup: one sort, {len(self.shape.levels)} "
                f"levels]{nl}{rest}")

    def _fp(self):
        return (tuple(e.fingerprint() for e in self.shape.key_exprs),
                self.shape.levels,
                tuple(tuple(e.fingerprint() for e in ins)
                      for ins in self.shape.agg_inputs),
                tuple(a.fn.fingerprint() for a in self.plan.aggs))

    def _packing(self, batch: ColumnarBatch):
        """(planes' specs, ranges on the device, the key columns) where
        the keys pack into two planes, else None: the aggregate's own
        probe (X._probe_key_ranges) with room for a second plane."""
        kcols = [batch.columns[e.index] if isinstance(e, BoundRef)
                 else compiled.run_stage([e], batch)[0]
                 for e in self.shape.key_exprs]
        probed = X._probe_key_ranges(kcols, batch.live_mask(),
                                     list(self.shape.key_exprs))
        if probed is None:
            return None
        specs = R.plan_packing_planes(kcols, probed[1])
        if specs is None:
            return None
        return tuple(specs), probed[0], kcols

    def _general(self, ctx, batch: ColumnarBatch):
        """The operators this exec stands for, over the batch in hand."""
        src = X._MaterializedExec(self.expand_plan.children[0], [batch],
                                  self.conf)
        expand = X.ExpandExec(self.expand_plan, [src], self.conf)
        agg = X.HashAggregateExec(self.plan, [expand], self.conf,
                                  mode="complete")
        expand.metrics = agg.metrics = self.metrics
        yield from agg.execute_partition(ctx, 0)

    def execute_partition(self, ctx, pidx):
        agg_t = self.metrics.metric(M.AGG_TIME)
        dev_t = self.metrics.metric(M.AGG_DEVICE_TIME)
        batches = list(self.children[0].execute_partition(ctx, pidx))
        if not batches:
            return
        self._acquire(ctx)
        t0 = time.perf_counter_ns()
        # a masked batch stays as it is: its dead rows pack to the
        # sentinel and sort behind the live ones (compacting the ten
        # columns of query 67's joined rows first cost 3.8 s of a 12 s
        # pass on the chip, PERF.md PR 33)
        batch = K.concat_batches(batches) if len(batches) > 1 else batches[0]
        packing = self._packing(batch)
        if packing is None:
            yield from self._general(ctx, batch)
            return
        specs, ranges, kcols = packing
        states = _states(self.plan)
        ansi = self.conf.get(C.ANSI_ENABLED)
        shape, fp = self.shape, self._fp()
        skey = tuple(sp.key for sp, _, _ in specs)
        level_keys = tuple(k for k, _ in shape.levels)
        with self.span(agg_t):
            pack = fuse.fused(("rollup_pack", fp, skey, ansi),
                              lambda: _build_pack(shape, states, specs, ansi))
            planes, values, errs = pack(batch, ranges)
            compiled.raise_errors(errs)
            perm = X._argsort_planes(
                planes, [R.MAX_PACK_BITS + 1] * len(planes))
            scan = fuse.fused(
                ("rollup_scan", fp, skey, level_keys),
                lambda: _build_scan(states, specs, level_keys))
            sp, shared, before, whole, scales, counts_d = scan(
                planes, perm, values)
            device_mark(dev_t, counts_d, t0)
            # tpulint: deferred-fetch the levels' counts size the output
            counts = [int(c) for c in host_planes(counts_d, "size")]
            t0 = time.perf_counter_ns()
            plain, counts = counts[-1] == 0, counts[:-1]
            total = sum(counts)
            caps = tuple(round_capacity(max(c, 1)) for c in counts)
            out_cap = round_capacity(max(total, 1))
            # the program keeps expressions and kernels, never the plan
            # (its tree holds the cached tables)
            kern, gid_type = self.kern, \
                self.plan.group_exprs[-1].data_type()
            emit = fuse.fused(
                ("rollup_emit", fp, skey, caps, out_cap, plain),
                lambda: _build_emit(gid_type, shape, states, specs, kern,
                                    caps, out_cap, plain))
            names = _needed(states, plain)
            cols = emit(batch, ranges, sp, shared,
                        {n: before[n] for n in names},
                        {n: whole[n] for n in names}, scales, counts_d)
            device_mark(dev_t, cols[-1].data, t0)
        # the keys keep what the host knows of them (the final ORDER BY
        # reads a string key's width from it)
        for kc, c in zip(kcols, cols):
            c.bounds, c.str_width = kc.bounds, kc.str_width
        self.metrics.metric(M.AGG_GROUPS).set_max(total)
        self.metrics.metric(M.NUM_OUTPUT_ROWS).add(total)
        self.metrics.metric(M.NUM_OUTPUT_BATCHES).add(1)
        yield ColumnarBatch(cols, total)
