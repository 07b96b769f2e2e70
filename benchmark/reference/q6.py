"""TPC-H Q6 over lineitem, DATE = 1994-01-01, DISCOUNT = 0.06, QUANTITY = 24."""
import numpy as np

from . import column, days


def answer(tables, float_type=np.float64):
    li = tables["lineitem"]
    ship = column(li, "l_shipdate")
    qty, price, disc = (column(li, c, float_type) for c in (
        "l_quantity", "l_extendedprice", "l_discount"))
    keep = ((ship >= days("1994-01-01")) & (ship < days("1995-01-01"))
            & (disc >= float_type(0.05)) & (disc <= float_type(0.07))
            & (qty < float_type(24)))
    return {"revenue": [float((price[keep] * disc[keep]).sum(
        dtype=float_type))]}
