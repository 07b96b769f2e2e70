"""Column pruning (reference: Catalyst ColumnPruning, which Spark runs
before the plugin ever sees a plan — this engine owns its own logical
plans, so it needs the pass itself).

Why it matters on TPU: a join materializes its build-side payload with
one full-capacity random gather PER COLUMN, and a window sorts then
gathers every input column — measured ~150-350 ms per 8-30M-row gather
on v5e. Dropping unreferenced columns before those operators is worth
more than any kernel tuning on them.

Why it matters more still over files: a `read_parquet` view names every
column of its table, and each column a scan keeps is parsed by the host
and uploaded whether or not a later operator reads it (PERF.md, PR 29:
Q6 names 4 of lineitem's 16 columns).

A Filter over a join first hands the conjuncts that read one side alone
to that side (`push_filters`; the SQL text's WHERE sits above its joins,
and a dimension filtered before a star join is a smaller build table and a
probe that can be compacted early); a join's own ON condition is split the
same way (`_push_on`: a conjunct over the null-supplying side of an outer
join filters that side's input, which is the same join; one over the
preserved side stays on the join). An Aggregate of counts over a join's
right side is then computed below the join, a key at a time
(`_counts_below_join`). Then three rewrites of columns. Two
are applied bottom-up:
- Project(Join(l, r)):   push the used-column subset below the join
- Project(Window(c)):    push the used-column subset below the window
  (and Aggregate(Project(c)) folds the project into the aggregate).
Both rebuild the intermediate node with remapped BoundRefs and keep the
outer Project's schema byte-identical. The third runs top-down over
their result:
- ParquetScan under operators that read a strict subset of its columns:
  a narrowed COPY of the scan (`ParquetScan.narrowed`) takes its place,
  and the operators between it and the Project, Aggregate or Join that
  absorbs the change are rebuilt with remapped BoundRefs. The scan node
  itself is never touched: a view's scan is shared by later queries.
  The same walk drops the columns nobody reads from a Project and an
  Expand whose parent's use is known, and puts a column-subset Project
  over a join input that cannot narrow itself (a CachedRelation, another
  join): a join gathers every build column it is handed.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr import core as E
from spark_rapids_tpu.plan import nodes as P


def _refs(e, out: Set[int]) -> None:
    if isinstance(e, E.BoundRef):
        out.add(e.index)
    for c in e.children:
        _refs(c, out)


def _remap(e, m: Dict[int, int]):
    def f(x):
        if isinstance(x, E.BoundRef):
            return E.BoundRef(m[x.index], x.dtype, x.name)
        return x
    return e.transform(f)


def _subset_project(child: P.PlanNode, used: List[int]) -> P.PlanNode:
    fields = child.schema.fields
    exprs = [E.BoundRef(i, fields[i].dtype, fields[i].name) for i in used]
    return P.Project(exprs, child)


def _clone_project(old: P.Project, new_child: P.PlanNode,
                   new_exprs) -> P.Project:
    q = P.Project.__new__(P.Project)
    q.children = [new_child]
    q.raw_exprs = old.raw_exprs
    q.exprs = new_exprs
    q.names = old.names
    return q


def _clone_join(j: P.Join, left: P.PlanNode, right: P.PlanNode,
                ml: Dict[int, int], mr: Dict[int, int],
                mc: Dict[int, int]) -> P.Join:
    """`j` over new children: `ml`/`mr` remap the key expressions of a
    side, `mc` the condition over the concatenated schema. A new node,
    not a copy: the build-side caches an exec leaves on a Join belong to
    the children it had."""
    nj = P.Join.__new__(P.Join)
    nj.children = [left, right]
    nj.left_keys = [_remap(e, ml) for e in j.left_keys]
    nj.right_keys = [_remap(e, mr) for e in j.right_keys]
    nj.how = j.how
    nj.condition_raw = j.condition_raw
    nj.condition = (_remap(j.condition, mc)
                    if j.condition is not None else None)
    return nj


def _prune_join(p: P.Project, j: P.Join):
    if j.how in ("left_semi", "left_anti"):
        return p  # output = left schema only; nothing to split
    left, right = j.children
    nl = len(left.schema.fields)
    nr = len(right.schema.fields)
    out_used: Set[int] = set()
    for e in p.exprs:
        _refs(e, out_used)
    cond_used: Set[int] = set()
    if j.condition is not None:
        _refs(j.condition, cond_used)
    used_l: Set[int] = {i for i in out_used | cond_used if i < nl}
    used_r: Set[int] = {i - nl for i in out_used | cond_used if i >= nl}
    for e in j.left_keys:
        _refs(e, used_l)
    for e in j.right_keys:
        _refs(e, used_r)
    if len(used_l) >= nl and len(used_r) >= nr:
        return p
    ul, ur = sorted(used_l), sorted(used_r)
    ml = {old: new for new, old in enumerate(ul)}
    mr = {old: new for new, old in enumerate(ur)}
    mc = {**{o: ml[o] for o in ul},
          **{o + nl: mr[o] + len(ul) for o in ur}}
    nj = _clone_join(
        j, _subset_project(left, ul) if len(ul) < nl else left,
        _subset_project(right, ur) if len(ur) < nr else right, ml, mr, mc)
    return _clone_project(p, nj, [_remap(e, mc) for e in p.exprs])


def _prune_window(p: P.Project, w: P.WindowNode):
    from spark_rapids_tpu.expr.window import WindowExpr, WindowSpec
    child = w.children[0]
    nc = len(child.schema.fields)
    out_used: Set[int] = set()
    for e in p.exprs:
        _refs(e, out_used)
    used_c: Set[int] = {i for i in out_used if i < nc}
    for we in w.window_exprs:
        for e in we.spec.partition_exprs:
            _refs(e, used_c)
        for o in we.spec.order_specs:
            _refs(o.expr, used_c)
        for e in we.fn.children:
            _refs(e, used_c)
    if len(used_c) >= nc:
        return p
    uc = sorted(used_c)
    m = {old: new for new, old in enumerate(uc)}
    nw = P.WindowNode.__new__(P.WindowNode)
    nw.children = [_subset_project(child, uc)]
    nw.names = w.names
    nexprs = []
    for we in w.window_exprs:
        spec = WindowSpec([_remap(e, m) for e in we.spec.partition_exprs],
                          [P.SortOrder(_remap(o.expr, m), o.ascending,
                                       o.nulls_first)
                           for o in we.spec.order_specs],
                          we.spec.frame)
        nexprs.append(WindowExpr(_remap(we.fn, m), spec))
    nw.window_exprs = nexprs
    # outer project: child cols remap; appended window cols shift down
    mo = dict(m)
    for j_ in range(len(w.window_exprs)):
        mo[nc + j_] = len(uc) + j_
    return _clone_project(p, nw, [_remap(e, mo) for e in p.exprs])


def _absorbable_project(pr: P.Project) -> bool:
    """A Project may fold into its consumer only when its expressions are
    deterministic and context-free: partition-context expressions
    (spark_partition_id, monotonically_increasing_id), rand, and UDF
    tiers evaluate with state the aggregate stage does not carry."""
    from spark_rapids_tpu.plan.overrides import _contains_project_only

    def bad(e) -> bool:
        name = type(e).__name__
        if name in ("Rand", "PythonRowUDF", "JaxColumnarUDF"):
            return True
        return any(bad(c) for c in e.children)

    return not any(_contains_project_only(e) or bad(e) for e in pr.exprs)


def _absorb_project_into_agg(a: P.Aggregate, pr: P.Project) -> P.Aggregate:
    """Aggregate(Project(c)) -> Aggregate'(c): substitute the project's
    expressions into the aggregate's key/input expressions so key+input
    evaluation happens INSIDE the fused aggregation kernel — the project's
    intermediate batch (a full-capacity materialization per column) never
    exists. The reference reaches the same shape via Catalyst's
    CollapseProject before the plugin sees the plan."""
    def subst(e):
        def f(x):
            if isinstance(x, E.BoundRef):
                return pr.exprs[x.index]
            return x
        return e.transform(f)

    na = P.Aggregate.__new__(P.Aggregate)
    na.children = [pr.children[0]]
    na.raw_group_exprs = a.raw_group_exprs
    na.group_exprs = [subst(e) for e in a.group_exprs]
    na.group_names = list(a.group_names)
    na.aggs = [ag.transform(lambda n: subst(n) if isinstance(n, E.BoundRef)
                            else n) for ag in a.aggs]
    return na


def _conjuncts(e) -> list:
    if isinstance(e, E.And):
        return _conjuncts(e.children[0]) + _conjuncts(e.children[1])
    return [e]


def _filter_over(child: P.PlanNode, conjs: list) -> P.PlanNode:
    """Filter(`conjs`, child), pushed on below `child` where it is a join."""
    if not conjs:
        return child
    cond = conjs[0]
    for c in conjs[1:]:
        cond = E.And(cond, c)
    f = P.Filter.__new__(P.Filter)
    f.children = [child]
    f.condition = cond
    return _push_filter(f)


def _by_side(cond, nl: int, ok_l: bool, ok_r: bool) -> tuple:
    """The conjuncts of `cond`, bound over a join's two inputs end to end
    (`nl` columns on the left), as (those that read the left input alone
    and may move there, the same for the right, remapped to it, the
    rest). One that reads no column, or whose value depends on where it
    runs, stays."""
    to_l, to_r, stay = [], [], []
    for c in _conjuncts(cond):
        used: Set[int] = set()
        _refs(c, used)
        if not used or _contains_context(c):
            stay.append(c)
        elif ok_l and all(i < nl for i in used):
            to_l.append(c)
        elif ok_r and all(i >= nl for i in used):
            to_r.append(_remap(c, {i: i - nl for i in used}))
        else:
            stay.append(c)
    return to_l, to_r, stay


def _push_filter(f: P.Filter) -> P.PlanNode:
    """Filter(Join(l, r)) -> Filter'(Join(Filter(l), Filter(r))): each
    conjunct that reads one side alone moves to that side, where the join
    lets it (both sides of an inner join, the left side of a left, semi
    or anti join). The join is a NEW node: the one in hand may be shared
    with a sibling plan that has no such filter."""
    j = f.children[0]
    if not isinstance(j, P.Join) or \
            j.how not in ("inner", "left", "left_semi", "left_anti"):
        return f
    to_l, to_r, stay = _by_side(
        f.condition, len(j.children[0].schema.fields), True,
        j.how == "inner")
    if not to_l and not to_r:
        return f
    nj = P.Join.__new__(P.Join)
    nj.children = [_filter_over(j.children[0], to_l),
                   _filter_over(j.children[1], to_r)]
    nj.left_keys, nj.right_keys = j.left_keys, j.right_keys
    nj.how, nj.condition_raw, nj.condition = j.how, j.condition_raw, \
        j.condition
    if not stay:
        return nj
    f.children = [nj]  # `f` is new or the caller's own copy
    f.condition = stay[0]
    for c in stay[1:]:
        f.condition = E.And(f.condition, c)
    return f


def _contains_context(e) -> bool:
    """Expressions whose value depends on where they run (partition
    context, rand, UDF tiers) stay where the query put them."""
    from spark_rapids_tpu.plan.overrides import _contains_project_only
    if _contains_project_only(e):
        return True
    return type(e).__name__ in ("Rand", "PythonRowUDF", "JaxColumnarUDF") \
        or any(_contains_context(c) for c in e.children)


#: the inputs of a join that a conjunct of its ON condition, reading
#: that input alone, may filter instead: `L JOIN R ON k AND r(R)` is
#: `L JOIN (R WHERE r) ON k` wherever R's rows without a partner are not
#: kept. A conjunct over an input whose rows ARE kept (the left of a LEFT
#: join) decides which of them find a partner, not which exist: it stays
_ON_PUSHABLE = {"inner": (True, True), "left": (False, True),
                "right": (True, False), "left_semi": (False, True),
                "left_anti": (False, True)}


def _push_on(j: P.Join) -> P.PlanNode:
    """Join(l, r, keys, condition) -> Join'(Filter(l), Filter(r), keys,
    what is left of the condition). The join is a NEW node (see
    _push_filter)."""
    to_l, to_r, stay = _by_side(
        j.condition, len(j.children[0].schema.fields),
        *_ON_PUSHABLE.get(j.how, (False, False)))
    if not to_l and not to_r:
        return j
    nj = P.Join.__new__(P.Join)
    nj.children = [_filter_over(j.children[0], to_l),
                   _filter_over(j.children[1], to_r)]
    nj.left_keys, nj.right_keys, nj.how = j.left_keys, j.right_keys, j.how
    nj.condition = nj.condition_raw = None
    for c in stay:
        nj.condition = c if nj.condition is None else E.And(nj.condition, c)
    return nj


def push_filters(p: P.PlanNode) -> P.PlanNode:
    """Bottom-up over the plan; children are replaced in place as the
    other rewrites do, a Filter or a join whose condition moves is
    rebuilt."""
    p.children = [push_filters(c) for c in p.children]
    if isinstance(p, P.Join) and p.condition is not None and p.left_keys:
        return _push_on(p)
    if isinstance(p, P.Filter) and isinstance(p.children[0], P.Join):
        f = P.Filter.__new__(P.Filter)
        f.children, f.condition = list(p.children), p.condition
        return _push_filter(f)
    return p


def _counts_below_join(a: P.Aggregate) -> P.PlanNode:
    """Aggregate[G; count(x)...](L JOIN R ON keys), with G over L's
    columns and every x over R's, inner or left, no other condition ->
    Aggregate[G; sum(coalesce(n, 0))...](L JOIN (Aggregate[R's keys;
    count(x) as n...](R)) ON keys): a left row's partners are counted
    once a key, below the join, instead of being paired with it and
    counted above. The join's build keys are then unique, so it is the
    sync-free mask-through probe whatever R's fan-out was, its output is
    L's rows, and nothing of R crosses it but a count (TPC-H Q13: 7.4 M
    orders against 750 K customers). Right for any L: a left row of group
    g with key k adds count(x | key = k) to g either way, 0 where an outer
    join found it no partner. Not for a GLOBAL aggregate: over no row at
    all its count is 0, and a sum of no counts is NULL."""
    from spark_rapids_tpu.expr import aggregates as A
    j = a.children[0]
    if not isinstance(j, P.Join) or j.how not in ("inner", "left") \
            or j.condition is not None or not j.left_keys or not a.aggs \
            or not a.group_exprs:
        return a
    left, right = j.children
    nl = len(left.schema.fields)
    if any(i >= nl for i in _refs_of(a.group_exprs)):
        return a
    for ag in a.aggs:
        used = _refs_of(ag.fn.children)
        if type(ag.fn) is not A.Count or not used \
                or any(i < nl for i in used) \
                or any(_contains_context(e) for e in ag.fn.children):
            return a
    if any(_contains_context(e) for e in j.right_keys):
        return a
    nk = len(j.right_keys)
    down = {i: i - nl for i in range(nl, nl + len(right.schema.fields))}
    below = P.Aggregate.__new__(P.Aggregate)
    below.children = [right]
    below.raw_group_exprs = list(j.right_keys)
    below.group_exprs = list(j.right_keys)
    below.group_names = [f"__key{i}" for i in range(nk)]
    below.aggs = [A.NamedAgg(A.Count(*[_remap(e, down)
                                       for e in ag.fn.children]),
                             f"__count{i}") for i, ag in enumerate(a.aggs)]
    below.note = " [counts below join]"
    nj = P.Join.__new__(P.Join)
    nj.children = [left, below]
    nj.left_keys = j.left_keys
    nj.right_keys = [E.BoundRef(i, e.data_type(), f"__key{i}")
                     for i, e in enumerate(j.right_keys)]
    nj.how, nj.condition, nj.condition_raw = j.how, None, None
    above = P.Aggregate.__new__(P.Aggregate)
    above.children = [nj]
    above.raw_group_exprs = a.raw_group_exprs
    above.group_exprs = a.group_exprs
    above.group_names = a.group_names
    above.aggs = [A.NamedAgg(A.Sum(E.Coalesce(
        E.BoundRef(nl + nk + i, T.INT64, f"__count{i}"),
        E.Literal(0, T.INT64))), ag.name) for i, ag in enumerate(a.aggs)]
    return above


def _refs_of(exprs) -> Set[int]:
    out: Set[int] = set()
    for e in exprs:
        _refs(e, out)
    return out


def _narrow_scan(s: P.ParquetScan, req: Optional[Set[int]]):
    if req is None:
        return s, None
    fields = s.schema.fields
    keep = set(req)
    n_file = len(fields) - len(s.partition_fields())
    if n_file and not any(i < n_file for i in keep):
        # no column of the files is read (count(*), or partition values
        # alone): the cheapest one carries the row count
        keep.add(min(range(n_file),
                     key=lambda i: fields[i].dtype.default_size()))
    if len(keep) >= len(fields):
        return s, None
    keep = sorted(keep)
    return s.narrowed(keep), {old: new for new, old in enumerate(keep)}


def _rebuilt(p: P.PlanNode, child: P.PlanNode) -> P.PlanNode:
    q = type(p).__new__(type(p))
    q.children = [child]
    return q


def _rebuild_project(p: P.Project, child, m) -> P.PlanNode:
    exprs = [_remap(e, m) for e in p.exprs]
    if isinstance(child, P.ParquetScan):
        names = child.schema.names
        if p.names == names and all(
                isinstance(e, E.BoundRef) and e.index == i
                for i, e in enumerate(exprs)):
            return child  # a subset project that the narrowed scan now is
    return _clone_project(p, child, exprs)


def _rebuild_aggregate(p: P.Aggregate, child, m) -> P.Aggregate:
    q = _rebuilt(p, child)
    q.raw_group_exprs = p.raw_group_exprs
    q.group_exprs = [_remap(e, m) for e in p.group_exprs]
    q.group_names = p.group_names
    q.aggs = [a.transform(lambda n: _remap(n, m)
                          if isinstance(n, E.BoundRef) else n)
              for a in p.aggs]
    return q


def _rebuild_filter(p: P.Filter, child, m) -> P.Filter:
    q = _rebuilt(p, child)
    q.condition = _remap(p.condition, m)
    return q


def _rebuild_sort(p: P.Sort, child, m) -> P.Sort:
    q = _rebuilt(p, child)
    q.orders = [P.SortOrder(_remap(o.expr, m), o.ascending, o.nulls_first)
                for o in p.orders]
    q.global_sort = p.global_sort
    return q


def _rebuild_limit(p: P.Limit, child, m) -> P.Limit:
    q = _rebuilt(p, child)
    q.n = p.n
    return q


#: the unary nodes whose use of their child is known from their
#: expressions: (type, the child columns the node itself reads, whether
#: the child's other columns pass through to the node's output, rebuild)
_UNARY = (
    (P.Aggregate, lambda p: _refs_of(p.group_exprs)
     | _refs_of(a.fn for a in p.aggs), False, _rebuild_aggregate),
    (P.Filter, lambda p: _refs_of([p.condition]), True, _rebuild_filter),
    (P.Sort, lambda p: _refs_of(o.expr for o in p.orders), True,
     _rebuild_sort),
    (P.Limit, lambda p: set(), True, _rebuild_limit),
)


def _set_children(p: P.PlanNode, children: List[P.PlanNode]) -> None:
    if any(a is not b for a, b in zip(children, p.children)):
        p.children = children


def _narrow_scans(p: P.PlanNode, req: Optional[Set[int]]):
    """Top-down: `req` is the set of `p`'s output columns its parent
    reads (None: all of them, which is also the answer for a parent
    whose use of its child is not known here). Returns `(node, m)`.
    `m` is None when the node puts out the columns it did; the node is
    then `p`, with at most a subtree replaced in place, as the bottom-up
    rewrites do (the subtree is semantically identical). Otherwise the
    node is NEW, puts out a strict subset of `p`'s columns that holds
    `req`, in `p`'s order, and `m` maps old positions to new ones."""
    if isinstance(p, P.ParquetScan):
        return _narrow_scan(p, req)
    if isinstance(p, P.CachedRelation):
        # the cache holds the whole table for every later query
        return p, None
    if isinstance(p, P.Join):
        return _narrow_join(p, req)
    if isinstance(p, (P.Project, P.Expand)):
        return _narrow_projection(p, req)
    rule = next((r for r in _UNARY if isinstance(p, r[0])), None)
    if rule is None:
        _set_children(p, [_narrow_scans(c, None)[0] for c in p.children])
        return p, None
    _, own, through, rebuild = rule
    creq = own(p)
    if through:
        creq = None if req is None else creq | req
    child, m = _narrow_scans(p.children[0], creq)
    if m is None:
        _set_children(p, [child])
        return p, None
    return rebuild(p, child, m), m if through else None


def _narrow_projection(p, req: Optional[Set[int]]):
    """A Project or an Expand under a parent that reads `req` of its
    columns: the others are dropped (their expressions are deterministic
    and context-free, or all stay), and the child is asked for what the
    kept expressions read."""
    lists = p.projections if isinstance(p, P.Expand) else [p.exprs]
    n = len(lists[0])
    keep = list(range(n))
    if req is not None and len(req) < n and not any(
            _contains_context(e) for row in lists
            for i, e in enumerate(row) if i not in req):
        keep = sorted(req)
    child, m = _narrow_scans(p.children[0], _refs_of(
        row[i] for row in lists for i in keep))
    if m is None and len(keep) == n:
        _set_children(p, [child])
        return p, None
    if m is None:
        m = {i: i for i in range(len(child.schema.fields))}
    if isinstance(p, P.Expand):
        q = _rebuilt(p, child)
        q.projections = [[_remap(row[i], m) for i in keep] for row in lists]
        q.names = [p.names[i] for i in keep]
    else:
        sub = P.Project.__new__(P.Project)
        sub.raw_exprs = [p.raw_exprs[i] for i in keep]
        sub.exprs = [p.exprs[i] for i in keep]
        sub.names = [p.names[i] for i in keep]
        q = _rebuild_project(sub, child, m)
    return q, ({old: new for new, old in enumerate(keep)}
               if len(keep) < n else None)


def _narrow_join(j: P.Join, req: Optional[Set[int]]):
    left, right = j.children
    nl = len(left.schema.fields)
    lreq = rreq = None
    if req is not None:
        used = set(req)
        if j.condition is not None:
            _refs(j.condition, used)
        lreq = {i for i in used if i < nl} | _refs_of(j.left_keys)
        rreq = {i - nl for i in used if i >= nl} | _refs_of(j.right_keys)
    nleft, ml = _narrow_scans(left, lreq)
    nright, mr = _narrow_scans(right, rreq)
    # an input that cannot narrow itself hands on every column it has: a
    # subset Project in front of the join keeps them out of its gathers
    if ml is None and lreq is not None and len(lreq) < nl:
        ml = {old: new for new, old in enumerate(sorted(lreq))}
        nleft = _subset_project(nleft, sorted(lreq))
    if mr is None and rreq is not None \
            and len(rreq) < len(right.schema.fields):
        mr = {old: new for new, old in enumerate(sorted(rreq))}
        nright = _subset_project(nright, sorted(rreq))
    if ml is None and mr is None:
        _set_children(j, [nleft, nright])
        return j, None
    left_out = ml  # None: the left side puts out what it did
    if ml is None:
        ml = {i: i for i in range(nl)}
    if mr is None:
        mr = {i: i for i in range(len(right.schema.fields))}
    nl2 = len(nleft.schema.fields)
    mc = {**ml, **{o + nl: n + nl2 for o, n in mr.items()}}
    nj = _clone_join(j, nleft, nright, ml, mr, mc)
    # a semi or anti join puts out its left side's columns alone
    return nj, left_out if j.how in ("left_semi", "left_anti") else mc


def prune_plan(p: P.PlanNode) -> P.PlanNode:
    """Filters below joins, the bottom-up rewrites, then the scans and
    the join inputs narrowed top-down."""
    return _narrow_scans(_prune_bottom_up(push_filters(p)), None)[0]


def _prune_bottom_up(p: P.PlanNode) -> P.PlanNode:
    """Replaces children in place (a rewritten subtree is semantically
    identical, so sharing with sibling plans stays sound); returns the
    possibly-rewritten node."""
    p.children = [_prune_bottom_up(c) for c in p.children]
    if isinstance(p, P.Project):
        c = p.children[0]
        if isinstance(c, P.Join):
            return _prune_join(p, c)
        if isinstance(c, P.WindowNode):
            return _prune_window(p, c)
    if isinstance(p, P.Aggregate):
        c = p.children[0]
        if isinstance(c, P.Project) and _absorbable_project(c):
            p = _absorb_project_into_agg(p, c)
        return _counts_below_join(p)
    return p
