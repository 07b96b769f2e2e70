"""TPC-H Q13 over customer and orders; WORD1 = special, WORD2 = requests.
An order is kept unless its comment holds `special` and, after it,
`requests` (Python's str.find on the column's strings, not a regular
expression); a customer's count is of its kept orders, 0 where it has none
(the left outer join), and the answer counts customers by that count. Every
column of the answer is an integer, so `float_type` changes nothing."""
import numpy as np

from . import column

WORD1, WORD2 = "special", "requests"


def kept(comments) -> np.ndarray:
    """bool a comment: NOT LIKE '%WORD1%WORD2%'."""
    out = np.ones(len(comments), np.bool_)
    for i, s in enumerate(comments):
        at = s.find(WORD1)
        out[i] = at < 0 or s.find(WORD2, at + len(WORD1)) < 0
    return out


def answer(tables, float_type=np.float64):
    cu, od = tables["customer"], tables["orders"]
    custkey = column(cu, "c_custkey")
    keep = kept(od["o_comment"].to_pylist())
    keep &= od["o_orderkey"].is_valid().to_numpy(zero_copy_only=False)
    per_key = np.bincount(column(od, "o_custkey")[keep],
                          minlength=int(custkey.max()) + 1)
    c_count = per_key[custkey]          # one row a customer, 0 with no order
    custdist = np.bincount(c_count)
    counts = np.flatnonzero(custdist)
    order = np.lexsort((-counts, -custdist[counts]))
    return {"c_count": [int(c) for c in counts[order]],
            "custdist": [int(custdist[c]) for c in counts[order]]}
