"""TPC-DS query 28 over store_sales: six quantity bands, each kept where
one of three ranges over the list price, the coupon and the wholesale cost
holds; the average, the count and the distinct count of the band's list
prices. The average is exact where `float_type` is the configuration's
float64 (whole cents summed in int64, divided once); a narrower
`float_type` (the tests' control) adds in that type. A null operand makes
its comparison unknown, as SQL has it: a row is kept only where its
quantity is known and one of the ranges is true."""
import numpy as np

#: (quantity from, to, list price, coupon, wholesale cost) a band
BANDS = ((0, 5, 8, 459, 57), (6, 10, 90, 2323, 31), (11, 15, 142, 12214, 79),
         (16, 20, 135, 6071, 38), (21, 25, 122, 836, 17),
         (26, 30, 154, 7326, 7))
#: widths of the three ranges: x and x + 10, x + 1000, x + 20
WIDTHS = (10, 1000, 20)


def _nullable(table, name):
    """(values with nulls as 0, validity) of a numeric column."""
    col = table[name].combine_chunks()
    return (np.asarray(col.fill_null(0)), ~np.asarray(col.is_null()))


def _within(values, valid, lo, width):
    return valid & (values >= lo) & (values <= lo + width)


def answer(tables, float_type=np.float64):
    ss = tables["store_sales"]
    qty, q_ok = _nullable(ss, "ss_quantity")
    price, p_ok = _nullable(ss, "ss_list_price")
    coupon, c_ok = _nullable(ss, "ss_coupon_amt")
    cost, w_ok = _nullable(ss, "ss_wholesale_cost")
    out = {}
    for b, (lo, hi, lp, cp, wc) in enumerate(BANDS, 1):
        keep = _within(qty, q_ok, lo, hi - lo) & (
            _within(price, p_ok, lp, WIDTHS[0])
            | _within(coupon, c_ok, cp, WIDTHS[1])
            | _within(cost, w_ok, wc, WIDTHS[2]))
        prices = price[keep & p_ok]
        n = len(prices)
        if not n:
            mean = None
        elif float_type is np.float64:
            cents = np.rint(prices * 100).astype(np.int64)
            mean = float(cents.sum() / n / 100)
        else:
            mean = float(prices.astype(float_type).sum(dtype=float_type)
                         / float_type(n))
        out[f"B{b}_LP"] = [mean]
        out[f"B{b}_CNT"] = [int(n)]
        out[f"B{b}_CNTD"] = [int(len(np.unique(prices)))]
    return out
