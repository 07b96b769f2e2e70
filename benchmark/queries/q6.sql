-- TPC-H v3 Q6, forecasting revenue change, validation parameters DATE = 1994-01-01,
-- DISCOUNT = 0.06, QUANTITY = 24 (clause 2.4.6.3).
-- Departures from the spec's text, none of which changes an answer:
--   date '1994-01-01' and date '1994-01-01' + interval '1' year (1995-01-01) are
--   written as their day numbers since 1970-01-01, cast(8766 as date) and
--   cast(9131 as date): sql/parser.py has no date literal, and cast('1994-01-01' as
--   date) is not folded by the engine (PERF.md, Findings);
--   0.06 - 0.01 and 0.06 + 0.01 are folded to 0.05 and 0.07: the columns are double,
--   not decimal, and 0.06 + 0.01 in doubles is 0.06999999999999999, which would drop
--   the discount 0.07 that the decimal arithmetic of the spec keeps.
select
    sum(l_extendedprice * l_discount) as revenue
from
    lineitem
where
    l_shipdate >= cast(8766 as date)
    and l_shipdate < cast(9131 as date)
    and l_discount between 0.05 and 0.07
    and l_quantity < 24
