"""TPC-DS query 67 over store_sales, date_dim, store, item; d_month_seq
between 1200 and 1211. Sums are carried exactly where `float_type` is the
configuration's float64 (integer cents times quantity in int64, turned to a
double once); a narrower `float_type` (the tests' control) multiplies and
adds in that type. Null foreign keys join nothing; a null i_category of item
shares its window partition with the rollup's own nulls."""
import numpy as np

from . import column

KEYS = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id"]
MONTH_SEQ = (1200, 1211)
TOP = 100


def _nullable(table, name):
    """(values with nulls as 0, validity) of a numeric column."""
    col = table[name].combine_chunks()
    valid = ~np.asarray(col.is_null())
    return np.asarray(col.fill_null(0)), valid


def _look_up(keys, valid, dim_keys):
    """Row of the dimension whose unique key equals each key; -1 where the
    key is null or absent."""
    lo, hi = int(dim_keys.min()), int(dim_keys.max())
    slot = np.full(hi - lo + 1, -1, np.int64)   # the keys are surrogate
    slot[dim_keys - lo] = np.arange(len(dim_keys))  # keys: a dense span
    inside = valid & (keys >= lo) & (keys <= hi)
    return np.where(inside, slot[np.where(inside, keys - lo, 0)], -1)


def _string_codes(table, name):
    """(codes of a string column in the order of its values, a null -1;
    {code: value}). Equal strings get one code whatever dictionary the
    column came with."""
    col = table[name].combine_chunks()
    if str(col.type).startswith("dictionary"):
        col = col.cast("string")
    col = col.dictionary_encode()
    values = col.dictionary.to_pylist()
    rank = np.argsort(np.argsort(np.array(values, dtype=object)))
    null = np.asarray(col.is_null())
    codes = rank[np.asarray(col.indices.fill_null(0))]
    codes[null] = -1
    return codes, dict(zip(rank.tolist(), values))


def _sum_by(group, n, amount, float_type):
    """Sums of `amount` by group: cents stay whole numbers (every partial
    sum is under 2**53, so float64's additions are exact), a narrower
    float type adds in that type."""
    if float_type is np.float64:
        return np.bincount(group, weights=amount, minlength=n)
    order = np.argsort(group, kind="stable")
    first = np.flatnonzero(np.r_[True, np.diff(group[order]) != 0])
    return np.add.reduceat(amount[order], first, dtype=float_type)


def rollup(tables, float_type=np.float64):
    """The aggregate under the window: (keys, sumsales, names, rows) with
    one entry a group of any of the nine levels. keys are the eight
    grouping columns as codes that sort as their values do (-1 a null of
    the data, -2 a null the rollup put there), names the strings behind a
    string column's codes, rows the joined rows that passed the month
    filter."""
    ss, dd = tables["store_sales"], tables["date_dim"]
    st, it = tables["store"], tables["item"]
    d_row = _look_up(*_nullable(ss, "ss_sold_date_sk"),
                     column(dd, "d_date_sk"))
    s_row = _look_up(*_nullable(ss, "ss_store_sk"), column(st, "s_store_sk"))
    i_row = _look_up(*_nullable(ss, "ss_item_sk"), column(it, "i_item_sk"))
    seq = column(dd, "d_month_seq")
    keep = (d_row >= 0) & (s_row >= 0) & (i_row >= 0)
    keep &= (seq[d_row] >= MONTH_SEQ[0]) & (seq[d_row] <= MONTH_SEQ[1])
    d_row, s_row, i_row = d_row[keep], s_row[keep], i_row[keep]

    price, p_ok = _nullable(ss, "ss_sales_price")
    qty, q_ok = _nullable(ss, "ss_quantity")
    both = (p_ok & q_ok)[keep]                  # coalesce(price * qty, 0)
    if float_type is np.float64:
        amount = np.rint(price[keep] * 100).astype(np.int64) * qty[keep]
    else:
        amount = price[keep].astype(float_type) * qty[keep].astype(float_type)
    amount = np.where(both, amount, amount.dtype.type(0))

    # the eight grouping columns as codes that sort as their values do, a
    # null (of the data) -1; the rollup's own nulls come in below as -2
    names = {}
    cols = []
    for name, table, row in (("i_category", it, i_row), ("i_class", it, i_row),
                             ("i_brand", it, i_row),
                             ("i_product_name", it, i_row),
                             ("d_year", dd, d_row), ("d_qoy", dd, d_row),
                             ("d_moy", dd, d_row), ("s_store_id", st, s_row)):
        if name.startswith("d_"):
            codes = column(table, name).astype(np.int64)
        else:
            codes, names[name] = _string_codes(table, name)
        cols.append(codes[row])

    # nine group-bys: level k keeps the first k columns. One mixed-radix
    # key over the eight codes, the first column the most significant, so
    # that a level's key is the whole key without its last digits
    spans = [int(c.max() - c.min()) + 1 if len(c) else 1 for c in cols]
    whole = np.zeros(len(amount), np.int64)
    for c, span in zip(cols, spans):
        whole = whole * span + (c - (c.min() if len(c) else 0))
    assert np.prod([float(s) for s in spans]) < 2.0 ** 62
    out_cols = [[] for _ in KEYS]
    out_sum = []
    # a level's groups are groups of the groups of the level below it:
    # `at` is a row of each of those, `of` their sums (exact: cents)
    at, of = np.arange(len(amount)), amount
    for level in range(len(KEYS), -1, -1):
        if not len(amount):
            break
        _, firsts, group = np.unique(
            whole[at] // int(np.prod(spans[level:], dtype=object)),
            return_index=True, return_inverse=True)
        at, of = at[firsts], _sum_by(group, len(firsts), of, float_type)
        out_sum.append(np.asarray(of / 100.0 if float_type is np.float64
                                  else of, np.float64))
        for k in range(len(KEYS)):
            out_cols[k].append(cols[k][at] if k < level
                               else np.full(len(at), -2, np.int64))
    if not out_sum:
        return [np.zeros(0, np.int64)] * len(KEYS), np.zeros(0), names, 0
    return ([np.concatenate(c) for c in out_cols], np.concatenate(out_sum),
            names, len(amount))


def ranks(keys, sumsales):
    """rank() over (partition by i_category order by sumsales desc) of
    every group of `rollup`: both kinds of null are one partition."""
    part = np.maximum(keys[0], -1)
    order = np.lexsort((-sumsales, part))
    p, s = part[order], sumsales[order]
    starts = np.r_[True, p[1:] != p[:-1]]
    new_value = starts | np.r_[True, s[1:] != s[:-1]]
    idx = np.arange(len(p))
    first_of_part = np.maximum.accumulate(np.where(starts, idx, 0))
    first_of_value = np.maximum.accumulate(np.where(new_value, idx, 0))
    rk = np.empty(len(p), np.int64)
    rk[order] = first_of_value - first_of_part + 1
    return rk


def answer(tables, float_type=np.float64):
    keys, sumsales, names, _ = rollup(tables, float_type)
    if not len(sumsales):
        return {name: [] for name in KEYS + ["sumsales", "rk"]}
    rk = ranks(keys, sumsales)
    top = np.flatnonzero(rk <= TOP)
    # order by the ten columns, nulls first (both kinds: -2 and -1 sort
    # before every value, and a tie between them is settled by the later
    # columns, as SQL has it since both are NULL)
    by = [rk[top], sumsales[top]] + [np.maximum(k[top], -1)
                                     for k in reversed(keys)]
    top = top[np.lexsort(by)][:TOP]

    def values(k, name):
        codes = keys[k][top]
        if name in names:
            return [None if c < 0 else names[name][int(c)] for c in codes]
        return [None if c < 0 else int(c) for c in codes]

    out = {name: values(k, name) for k, name in enumerate(KEYS)}
    out["sumsales"] = [float(v) for v in sumsales[top]]
    out["rk"] = [int(v) for v in rk[top]]
    return out
