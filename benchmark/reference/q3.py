"""TPC-H Q3 over customer, orders, lineitem; SEGMENT = BUILDING,
DATE = 1995-03-15. Relies on what the generator guarantees and the spec
states: o_orderkey is unique and ascending, lineitem is in order-key order."""
import datetime

import numpy as np

from . import codes_of, column, days


def answer(tables, float_type=np.float64):
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    date = days("1995-03-15")
    seg, segs = codes_of(cu, "c_mktsegment")
    buyers = column(cu, "c_custkey")[seg == segs.index("BUILDING")]
    o_date = column(od, "o_orderdate")
    o_keep = (o_date < date) & np.isin(column(od, "o_custkey"), buyers)
    o_key = column(od, "o_orderkey")[o_keep]
    o_date = o_date[o_keep]
    o_prio = column(od, "o_shippriority")[o_keep]

    l_keep = column(li, "l_shipdate") > date
    l_key = column(li, "l_orderkey")[l_keep]
    pos = np.searchsorted(o_key, l_key)
    pos[pos == len(o_key)] = 0
    hit = o_key[pos] == l_key
    price, disc = (column(li, c, float_type)[l_keep][hit] for c in (
        "l_extendedprice", "l_discount"))
    pos = pos[hit]
    # one group an order: its lines are adjacent, so sum between boundaries
    first = np.flatnonzero(np.r_[True, pos[1:] != pos[:-1]])
    revenue = np.add.reduceat(price * (float_type(1) - disc), first,
                              dtype=float_type)
    order = pos[first]
    top = np.lexsort((o_date[order], -revenue))[:10]
    epoch = datetime.date(1970, 1, 1)
    return {
        "l_orderkey": [int(k) for k in o_key[order][top]],
        "revenue": [float(r) for r in revenue[top]],
        "o_orderdate": [epoch + datetime.timedelta(days=int(d))
                        for d in o_date[order][top]],
        "o_shippriority": [int(p) for p in o_prio[order][top]],
    }
