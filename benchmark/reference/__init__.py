"""Plain references: numpy over the generated tables, nothing of the engine.

Each module answers one query file of ../queries: `answer(tables, float_type)`
takes {table: pyarrow.Table} and returns {column: [values]} with the rows in
the query's ORDER BY. `float_type` is numpy.float64 for the reference; the
control of ../tests passes numpy.float32, the precision below the one the
configurations state.
"""
import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def column(table, name: str, float_type=None) -> np.ndarray:
    """One column as numpy: dates as int32 days, dictionary strings as their
    values' codes (see `codes_of`), floats cast to `float_type`."""
    col = table[name].combine_chunks()
    if str(col.type) == "date32[day]":
        return col.cast("int32").to_numpy()
    out = col.to_numpy(zero_copy_only=False)
    if float_type is not None and out.dtype.kind == "f":
        out = out.astype(float_type, copy=False)
    return out


def codes_of(table, name: str):
    """(codes, values) of a string column, whatever its arrow encoding."""
    col = table[name].combine_chunks()
    if not str(col.type).startswith("dictionary"):
        col = col.dictionary_encode()
    return col.indices.to_numpy(), col.dictionary.to_pylist()
