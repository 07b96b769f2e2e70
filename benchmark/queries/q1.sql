-- TPC-H v3 Q1, pricing summary report, validation parameter DELTA = 90 (clause 2.4.1.3).
-- Departures from the spec's text, none of which changes an answer:
--   date '1998-12-01' - interval '90' day (3) is folded to 1998-09-02 and written as
--   its day number since 1970-01-01, cast(10471 as date): sql/parser.py has no date
--   literal, and cast('1998-09-02' as date) is not folded by the engine, which parses
--   the string once a row on the device (PERF.md, Findings);
--   decimal(15,2) columns are double.
select
    l_returnflag,
    l_linestatus,
    sum(l_quantity) as sum_qty,
    sum(l_extendedprice) as sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
    avg(l_quantity) as avg_qty,
    avg(l_extendedprice) as avg_price,
    avg(l_discount) as avg_disc,
    count(*) as count_order
from
    lineitem
where
    l_shipdate <= cast(10471 as date)
group by
    l_returnflag,
    l_linestatus
order by
    l_returnflag,
    l_linestatus
