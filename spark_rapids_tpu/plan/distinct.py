"""Distinct aggregates as Spark plans them (AggUtils.planAggregateWithOneDistinct).

`count/sum/avg(DISTINCT e)` beside any other aggregates becomes two
Aggregate nodes:

- the distinct level groups by the keys plus `e` (a float `e`
  normalised, so -0.0 and 0.0 are one value and all NaNs one) and
  carries every other aggregate's partial states (`PartialState`);
- the merge level groups by the keys: it merges those states
  (`MergedState`, Spark's PartialMerge) and takes each distinct
  aggregate as the plain aggregate over the one row a distinct value now
  has. A NULL `e` is a group of the distinct level like any other, so it
  counts in the other aggregates' states, and the plain count, sum or
  avg above leaves it out.

All distinct aggregates of one Aggregate share one input; a second one
is rejected, as Spark's Expand rewrite for it is not planned here.
"""
from __future__ import annotations

from typing import List

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import core as E
from spark_rapids_tpu.expr.aggregates import NamedAgg
from spark_rapids_tpu.plan import nodes as P

#: the distinct level's name for the value it groups by
VALUE = "__distinct"


def plan_aggregate(keys: List[E.Expression], named: List[NamedAgg],
                   child: P.PlanNode) -> P.PlanNode:
    """P.Aggregate of `named` grouped by `keys`; with distinct aggregates
    among `named`, the two levels above."""
    if not any(na.fn.distinct for na in named):
        return P.Aggregate(keys, named, child)
    # bound here: the states' types depend on their inputs', and two
    # spellings of one column are one input
    named = [na.transform(lambda n: P._bind_leaf(n, child.schema))
             for na in named]
    inputs = {na.fn.children[0].fingerprint() for na in named
              if na.fn.distinct}
    if len(inputs) > 1:
        raise E.SparkException(
            "distinct aggregates over more than one input in one "
            f"aggregate are not supported: {sorted(inputs)}")
    for na in named:
        if isinstance(na.fn, A.SegmentedAgg):
            raise E.SparkException(
                f"{type(na.fn).__name__} beside a distinct aggregate is "
                "not supported: it has no partial state to carry")
    value = next(na.fn.children[0] for na in named if na.fn.distinct)
    if isinstance(value.data_type(), (T.Float32Type, T.Float64Type)):
        value = E.NormalizeNaNAndZero(value)
    carried, states = [], {}
    for i, na in enumerate(named):
        if not na.fn.distinct:
            states[i] = [f"__state{i}_{j}"
                         for j in range(len(na.fn.state_schema()))]
            carried += [NamedAgg(A.PartialState(na.fn, j), name)
                        for j, name in enumerate(states[i])]
    lower = P.Aggregate(list(keys) + [E.Alias(value, VALUE)], carried,
                        child)
    lower.distinct_level = True
    lower.note = " [distinct level]"
    fields = lower.schema.fields
    at = {f.name: E.BoundRef(k, f.dtype, f.name)
          for k, f in enumerate(fields)}
    upper_aggs = []
    for i, na in enumerate(named):
        if na.fn.distinct:
            fn = type(na.fn)(at[VALUE])
        else:
            fn = A.MergedState(na.fn, *[at[s] for s in states[i]])
        upper_aggs.append(NamedAgg(fn, na.name))
    upper = P.Aggregate([E.BoundRef(k, fields[k].dtype, fields[k].name)
                         for k in range(len(keys))], upper_aggs, lower)
    upper.note = " [merge level]"
    return upper
