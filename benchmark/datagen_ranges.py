"""datagen's tables, handed over one row range at a time.

The same rows as `datagen.generate(sf, seed)` (it makes them), with every
column cut into RANGES chunks of consecutive rows: pyarrow holds a string
column's bytes behind 32-bit offsets, and above about SF13 one chunk of
l_comment as plain strings passes 2 GiB, so the harness's cast of the
dictionary columns to strings (run.py `plain_strings`) fails on a table
that is one chunk ("Negative offsets in binary array"). A chunk a row
range stays far below that, and the slices are views: nothing is copied
and no row is left out. For configurations whose tables one chunk cannot
hold (tpch_sf20_mesh4); imports nothing of the engine.
"""
from __future__ import annotations

import numpy as np
import pyarrow as pa

import datagen

#: row ranges a table: at SF20 a range of lineitem is 7.5 M rows, about
#: 200 MB of comment text as plain strings
RANGES = 16

write_parquet = datagen.write_parquet
row_counts = datagen.row_counts


def in_ranges(table: pa.Table, ranges: int = RANGES) -> pa.Table:
    """`table` with each column in `ranges` chunks of consecutive rows."""
    bounds = np.linspace(0, table.num_rows, ranges + 1).astype(np.int64)
    return pa.concat_tables([table.slice(int(lo), int(hi - lo))
                             for lo, hi in zip(bounds[:-1], bounds[1:])])


def generate(sf: float, seed: int) -> dict:
    return {name: in_ranges(table)
            for name, table in datagen.generate(sf, seed).items()}
