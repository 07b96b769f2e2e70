"""TPC-H lineitem, orders and customer from a seed, in bulk with numpy.

Follows the TPC-H v3 specification, clause 1.4 (columns, types) and clause
4.2.3 (domains and correlations): sparse order keys (8 used of every 32),
customers whose key is a multiple of 3 place no order, 1 to 7 lines an
order, l_shipdate = o_orderdate + 1..121, l_commitdate = o_orderdate +
30..90, l_receiptdate = l_shipdate + 1..30, return flag and line status
from CURRENTDATE 1995-06-17, l_extendedprice = l_quantity * the part's
retail price, o_totalprice and o_orderstatus derived from the order's
lines. Where it departs from dbgen is listed in the configurations' files
under "assumed" (numpy's generator, not dbgen's streams; text from a
phrase pool; line counts a shuffled fixed multiset so that every seed has
the same number of rows).

Imports nothing of the engine. decimal(15,2) columns are float64 rounded
to cents, dates are date32.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

EPOCH_1992_01_01 = 8035          # days since 1970-01-01
ORDERDATE_MAX = 10591 - 151      # 1998-12-31 minus 151 days
CURRENTDATE = 9298               # 1995-06-17
POOL_SIZE = 8192                 # phrases in a text column's pool

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
WORDS = ("furiously quickly slyly carefully blithely regular final ironic "
         "express special pending bold even silent unusual deposits "
         "requests packages accounts instructions foxes ideas theodolites "
         "pinto beans dependencies excuses platelets asymptotes courts "
         "sleep wake haggle nag use boost affix detect integrate maintain "
         "nod cajole among about above across after against along the "
         "according to daring dogged busy close dolphins frets dinos "
         "attainments sauternes warthogs sheaves waters").split()

#: rows of each table per unit of scale factor (clause 4.2.5)
ROWS_PER_SF = {"customer": 150_000, "orders": 1_500_000}

#: independent streams that orders and lines are made in: many and small, so
#: that the threads' temporaries stay small beside the tables
CHUNKS = 128


def _chunk_bounds(n_orders: int) -> np.ndarray:
    return np.linspace(0, n_orders, CHUNKS + 1).astype(np.int64)


def row_counts(sf: float) -> dict:
    n_o = int(round(ROWS_PER_SF["orders"] * sf))
    b = _chunk_bounds(n_o)
    return {"customer": int(round(ROWS_PER_SF["customer"] * sf)),
            "orders": n_o,
            "lineitem": int(sum(_line_counts_sorted(hi - lo).sum()
                                for lo, hi in zip(b[:-1], b[1:])))}


def _line_counts_sorted(n_orders: int) -> np.ndarray:
    return (np.arange(n_orders, dtype=np.int64) % 7) + 1


def _dict(values, idx: np.ndarray) -> pa.Array:
    return pa.DictionaryArray.from_arrays(pa.array(idx),
                                          pa.array(values, pa.string()))


def _pool(rng, lo: int, hi: int) -> list:
    """POOL_SIZE phrases of lengths uniform in [lo, hi]."""
    lengths = rng.integers(lo, hi + 1, POOL_SIZE)
    picks = rng.integers(0, len(WORDS), (POOL_SIZE, hi // 3 + 2))
    return [" ".join(WORDS[j] for j in picks[i])[:lengths[i]].rstrip()
            .ljust(lengths[i], "s") for i in range(POOL_SIZE)]


def _digits(out: np.ndarray, col: int, keys: np.ndarray, width: int) -> None:
    k = keys.astype(np.int64)
    for pos in range(width - 1, -1, -1):
        out[:, col + pos] = 48 + k % 10
        k //= 10


def _numbered(prefix: str, keys: np.ndarray, width: int = 9) -> pa.Array:
    """prefix + zero-padded key ('Customer#000000042')."""
    out = np.empty((len(keys), len(prefix) + width), np.uint8)
    out[:, :len(prefix)] = np.frombuffer(prefix.encode(), np.uint8)
    _digits(out, len(prefix), keys, width)
    return pa.array(out.view(f"S{out.shape[1]}").ravel()).cast(pa.string())


def _phones(rng, nation: np.ndarray) -> pa.Array:
    n = len(nation)
    out = np.full((n, 15), ord("-"), np.uint8)
    _digits(out, 0, nation.astype(np.int64) + 10, 2)
    _digits(out, 3, rng.integers(100, 1000, n), 3)
    _digits(out, 7, rng.integers(100, 1000, n), 3)
    _digits(out, 11, rng.integers(1000, 10000, n), 4)
    return pa.array(out.view("S15").ravel()).cast(pa.string())


def _date(days: np.ndarray) -> pa.Array:
    return pa.array(days, pa.int32()).cast(pa.date32())


#: numpy dtype of each generated column; strings are indices into a pool
_L = {"l_orderkey": np.int64, "l_partkey": np.int64, "l_suppkey": np.int64,
      "l_linenumber": np.int32, "l_quantity": np.float64,
      "l_extendedprice": np.float64, "l_discount": np.float64,
      "l_tax": np.float64, "l_returnflag": np.int8, "l_linestatus": np.int8,
      "l_shipdate": np.int32, "l_commitdate": np.int32,
      "l_receiptdate": np.int32, "l_shipinstruct": np.int8,
      "l_shipmode": np.int8, "l_comment": np.int16}
_O = {"o_orderkey": np.int64, "o_custkey": np.int64,
      "o_orderstatus": np.int8, "o_totalprice": np.float64,
      "o_orderdate": np.int32, "o_orderpriority": np.int8,
      "o_clerk": np.int32, "o_shippriority": np.int32,
      "o_comment": np.int16}


def _fill_chunk(rng, sf, n_c, o_lo, o_hi, l_lo, O, L) -> None:
    """Orders [o_lo, o_hi) and their lines, written in place."""
    n_o = o_hi - o_lo
    idx = np.arange(o_lo, o_hi, dtype=np.int64)
    orderkey = (((idx >> 3) << 5) | (idx & 7)) + 1
    j = rng.integers(0, n_c - n_c // 3, n_o)       # j-th key not 0 mod 3
    orderdate = rng.integers(EPOCH_1992_01_01, ORDERDATE_MAX + 1, n_o,
                             dtype=np.int32)
    lines = rng.permutation(_line_counts_sorted(n_o))
    n_l = int(lines.sum())
    starts = np.zeros(n_o, np.int64)
    np.cumsum(lines[:-1], out=starts[1:])
    rep = np.repeat(np.arange(n_o), lines)
    n_p = max(int(round(200_000 * sf)), 1)
    n_s = max(int(round(10_000 * sf)), 1)
    partkey = rng.integers(1, n_p + 1, n_l, dtype=np.int32).astype(np.int64)
    quantity = rng.integers(1, 51, n_l, dtype=np.int32)
    # cents, exactly: a quotient by 100.0 is the double nearest the decimal
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    extprice = (quantity * retail) / 100.0
    discount = rng.integers(0, 11, n_l, dtype=np.int32) / 100.0
    tax = rng.integers(0, 9, n_l, dtype=np.int32) / 100.0
    shipdate = orderdate[rep] + rng.integers(1, 122, n_l, dtype=np.int32)
    receiptdate = shipdate + rng.integers(1, 31, n_l, dtype=np.int32)
    open_ = shipdate > CURRENTDATE
    n_open = np.add.reduceat(open_.astype(np.int64), starts)
    ls = slice(l_lo, l_lo + n_l)
    L["l_orderkey"][ls] = orderkey[rep]
    L["l_partkey"][ls] = partkey
    L["l_suppkey"][ls] = (partkey + rng.integers(0, 4, n_l, dtype=np.int32)
                          * (n_s // 4 + (partkey - 1) // n_s)) % n_s + 1
    L["l_linenumber"][ls] = np.arange(n_l) - starts[rep] + 1
    L["l_quantity"][ls] = quantity
    L["l_extendedprice"][ls] = extprice
    L["l_discount"][ls] = discount
    L["l_tax"][ls] = tax
    # returnflag: R or A once received by CURRENTDATE, else N
    L["l_returnflag"][ls] = np.where(receiptdate <= CURRENTDATE,
                                     rng.integers(0, 2, n_l, dtype=np.int8), 2)
    L["l_linestatus"][ls] = open_
    L["l_shipdate"][ls] = shipdate
    L["l_commitdate"][ls] = orderdate[rep] + rng.integers(
        30, 91, n_l, dtype=np.int32)
    L["l_receiptdate"][ls] = receiptdate
    L["l_shipinstruct"][ls] = rng.integers(0, 4, n_l, dtype=np.int8)
    L["l_shipmode"][ls] = rng.integers(0, 7, n_l, dtype=np.int8)
    L["l_comment"][ls] = rng.integers(0, POOL_SIZE, n_l, dtype=np.int16)
    os_ = slice(o_lo, o_hi)
    O["o_orderkey"][os_] = orderkey
    O["o_custkey"][os_] = j + j // 2 + 1
    O["o_orderstatus"][os_] = np.where(n_open == lines, 1,
                                       np.where(n_open == 0, 0, 2))
    O["o_totalprice"][os_] = np.round(np.add.reduceat(
        extprice * (1.0 + tax) * (1.0 - discount), starts), 2)
    O["o_orderdate"][os_] = orderdate
    O["o_orderpriority"][os_] = rng.integers(0, 5, n_o)
    O["o_clerk"][os_] = rng.integers(0, max(int(round(1000 * sf)), 1), n_o)
    O["o_shippriority"][os_] = 0
    O["o_comment"][os_] = rng.integers(0, POOL_SIZE, n_o)


def generate(sf: float, seed: int) -> dict:
    """{"customer", "orders", "lineitem"} as pyarrow tables at scale `sf`.
    Orders and their lines are made in CHUNKS independent streams (the same
    tables whatever the number of cores), in threads, in place."""
    root = np.random.SeedSequence(int(seed) % (1 << 63))
    kids = root.spawn(CHUNKS + 1)
    rng = np.random.default_rng(kids[CHUNKS])
    counts = row_counts(sf)
    n_c, n_o, n_l = counts["customer"], counts["orders"], counts["lineitem"]

    O = {k: np.empty(n_o, d) for k, d in _O.items()}
    L = {k: np.empty(n_l, d) for k, d in _L.items()}
    o_bounds = _chunk_bounds(n_o)
    l_bounds = np.concatenate([[0], np.cumsum(
        [_line_counts_sorted(hi - lo).sum()
         for lo, hi in zip(o_bounds[:-1], o_bounds[1:])])])
    with ThreadPoolExecutor(min(CHUNKS, os.cpu_count() or 1, 16)) as pool:
        list(pool.map(
            lambda c: _fill_chunk(np.random.default_rng(kids[c]), sf, n_c,
                                  int(o_bounds[c]), int(o_bounds[c + 1]),
                                  int(l_bounds[c]), O, L),
            range(CHUNKS)))

    custkey = np.arange(1, n_c + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n_c, dtype=np.int32)
    customer = pa.table({
        "c_custkey": custkey,
        "c_name": _numbered("Customer#", custkey),
        "c_address": _dict(_pool(rng, 10, 40),
                           rng.integers(0, POOL_SIZE, n_c, dtype=np.int16)),
        "c_nationkey": nation,
        "c_phone": _phones(rng, nation),
        "c_acctbal": np.round(rng.integers(-99999, 1000000, n_c) / 100.0, 2),
        "c_mktsegment": _dict(SEGMENTS, rng.integers(0, 5, n_c,
                                                     dtype=np.int8)),
        "c_comment": _dict(_pool(rng, 29, 116),
                           rng.integers(0, POOL_SIZE, n_c, dtype=np.int16)),
    })
    n_clerks = max(int(round(1000 * sf)), 1)
    O["o_orderdate"] = _date(O["o_orderdate"])
    for k in ("l_shipdate", "l_commitdate", "l_receiptdate"):
        L[k] = _date(L[k])
    O["o_orderstatus"] = _dict(["F", "O", "P"], O["o_orderstatus"])
    O["o_orderpriority"] = _dict(PRIORITIES, O["o_orderpriority"])
    O["o_clerk"] = pa.DictionaryArray.from_arrays(
        pa.array(O["o_clerk"]),
        _numbered("Clerk#", np.arange(1, n_clerks + 1)))
    O["o_comment"] = _dict(_pool(rng, 19, 78), O["o_comment"])
    L["l_returnflag"] = _dict(["R", "A", "N"], L["l_returnflag"])
    L["l_linestatus"] = _dict(["F", "O"], L["l_linestatus"])
    L["l_shipinstruct"] = _dict(INSTRUCTIONS, L["l_shipinstruct"])
    L["l_shipmode"] = _dict(MODES, L["l_shipmode"])
    L["l_comment"] = _dict(_pool(rng, 10, 43), L["l_comment"])
    return {"customer": customer, "orders": pa.table(O),
            "lineitem": pa.table(L)}


def write_parquet(tables: dict, directory: str, row_group_rows: int) -> dict:
    """One snappy file a table under `directory`; {table: path}. A
    directory that a finished earlier call left (marker file) is reused."""
    paths = {name: os.path.join(directory, f"{name}.parquet")
             for name in tables}
    marker = os.path.join(directory, "COMPLETE")
    if os.path.exists(marker):
        return paths
    os.makedirs(directory, exist_ok=True)
    for name, table in tables.items():
        # no ARROW:schema in the footer: the file is what any writer would
        # leave, strings and not pyarrow's dictionary type
        pq.write_table(table, paths[name], compression="snappy",
                       row_group_size=row_group_rows, store_schema=False)
    with open(marker, "w") as f:
        f.write("ok\n")
    return paths
