-- TPC-H v3 Q13, customer distribution, validation parameters WORD1 = special,
-- WORD2 = requests (clause 2.4.13.3).
-- Departures from the spec's text: none. The ON clause keeps its LIKE (the
-- planner filters orders with it below the outer join), and the derived
-- table keeps its alias with a column list.
select
    c_count,
    count(*) as custdist
from
    (
        select
            c_custkey,
            count(o_orderkey)
        from
            customer left outer join orders on
                c_custkey = o_custkey
                and o_comment not like '%special%requests%'
        group by
            c_custkey
    ) as c_orders (c_custkey, c_count)
group by
    c_count
order by
    custdist desc,
    c_count desc
