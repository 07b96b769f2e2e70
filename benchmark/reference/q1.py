"""TPC-H Q1 over lineitem, DELTA = 90."""
import numpy as np

from . import codes_of, column, days


def answer(tables, float_type=np.float64):
    li = tables["lineitem"]
    keep = column(li, "l_shipdate") <= days("1998-09-02")
    flag, flags = codes_of(li, "l_returnflag")
    status, statuses = codes_of(li, "l_linestatus")
    qty, price, disc, tax = (column(li, c, float_type)[keep] for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    one = float_type(1)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    group = (flag.astype(np.int64) * len(statuses) + status)[keep]
    rows = []
    for g in np.unique(group):
        m = group == g
        n = int(m.sum())
        sums = [x[m].sum(dtype=float_type) for x in (
            qty, price, disc_price, charge, disc)]
        rows.append((flags[g // len(statuses)], statuses[g % len(statuses)],
                     sums[0], sums[1], sums[2], sums[3],
                     sums[0] / float_type(n), sums[1] / float_type(n),
                     sums[4] / float_type(n), n))
    rows.sort(key=lambda r: (r[0], r[1]))
    names = ("l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
             "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
             "avg_disc", "count_order")
    return {name: [float(r[i]) if i in range(2, 9) else r[i] for r in rows]
            for i, name in enumerate(names)}
