-- TPC-H v3 Q3, shipping priority, validation parameters SEGMENT = BUILDING,
-- DATE = 1995-03-15 (clause 2.4.3.3).
-- Departures from the spec's text, none of which changes an answer:
--   the comma joins are explicit JOIN ... ON (the parser has no comma join), left key first;
--   each table's own WHERE conjunct is written inside a derived table, which is where
--   the engine applies it (its planner pushes no filter below a join), with the columns
--   the query goes on to use;
--   date '1995-03-15' is written as its day number since 1970-01-01,
--   cast(9204 as date): sql/parser.py has no date literal, and cast('1995-03-15' as
--   date) is not folded by the engine (PERF.md, Findings);
--   decimal(15,2) columns are double.
select
    l_orderkey,
    sum(l_extendedprice * (1 - l_discount)) as revenue,
    o_orderdate,
    o_shippriority
from
    (select c_custkey from customer where c_mktsegment = 'BUILDING') c
    join (select o_orderkey, o_custkey, o_orderdate, o_shippriority
          from orders where o_orderdate < cast(9204 as date)) o
        on c_custkey = o_custkey
    join (select l_orderkey, l_extendedprice, l_discount
          from lineitem where l_shipdate > cast(9204 as date)) l
        on o_orderkey = l_orderkey
group by
    l_orderkey,
    o_orderdate,
    o_shippriority
order by
    revenue desc,
    o_orderdate
limit 10
