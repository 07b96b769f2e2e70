"""The live rows' positions found 32 rows at a time
(ops/kernels._compact_indices): the mask packed into u32 words, each
word's first output slot a prefix sum of popcounts, one scatter a word,
and each slot's row by a popcount descent within its word.

Held here against numpy: the positions of the set rows among the first
`num_rows`, in order, -1 behind them, at capacities on and off a word's
32 rows, at densities from none to all, with the output cut below, at and
above the live count; a masked batch of doubles, dictionary strings and
nulls compacted by `compact_batch` row for row; and the trace counter
`compact_words_traced`, which rises once a trace and not once a call.
"""
import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, from_arrow, to_arrow,
)
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.runtime import compile_cache as CC


def _reference(mask, num_rows, out_cap):
    live = np.flatnonzero(mask[:num_rows])[:out_cap]
    out = np.full(out_cap, -1, np.int32)
    out[:len(live)] = live
    return out


@pytest.mark.parametrize("density", [0.0, 0.009, 0.5, 1.0])
@pytest.mark.parametrize("cap", [1, 31, 32, 33, 1000, 4099, 65536])
def test_compact_indices_matches_numpy(cap, density):
    rng = np.random.default_rng(cap * 7 + int(density * 1000))
    mask = rng.random(cap) < density
    for num_rows in sorted({cap, cap - cap // 3}):
        count = int(mask[:num_rows].sum())
        for out_cap in sorted({max(count // 2, 1), max(count, 1),
                               count + 5}):
            got = np.asarray(K._compact_indices(jnp.asarray(mask),
                                                num_rows, out_cap))
            np.testing.assert_array_equal(
                got, _reference(mask, num_rows, out_cap),
                err_msg=f"cap={cap} num_rows={num_rows} out_cap={out_cap}")


def test_compact_batch_rows_match_numpy():
    n = 3000
    rng = np.random.default_rng(42)
    x = rng.normal(size=n)
    x_null = rng.random(n) < 0.1
    words = np.array(["red", "green", "blue", "a longer word"])
    s = words[rng.integers(0, len(words), n)]
    s_null = rng.random(n) < 0.2
    table = pa.table({
        "x": pa.array(x, mask=x_null),
        "s": pa.array(s, mask=s_null),
        "i": pa.array(np.arange(n, dtype=np.int64)),
    })
    batch = from_arrow(table)
    assert batch.columns[1].is_dict
    keep = rng.random(batch.capacity) < 0.3
    masked = ColumnarBatch(batch.columns, int(keep[:n].sum()),
                           jnp.asarray(keep))
    out = K.compact_batch(masked)
    assert out.row_mask is None and out.num_rows == int(keep[:n].sum())
    got = to_arrow(out, ["x", "s", "i"]).to_pylist()
    want = table.take(np.flatnonzero(keep[:n])).to_pylist()
    assert got == want


def test_compact_words_traced_counts_traces_not_calls():
    mask = jnp.asarray(np.arange(1237) % 3 == 0)
    before = CC.stats()["compact_words_traced"]
    first = K._compact_indices(mask, 1237, 29)
    assert CC.stats()["compact_words_traced"] == before + 1
    again = K._compact_indices(mask, 1000, 29)
    assert CC.stats()["compact_words_traced"] == before + 1
    np.testing.assert_array_equal(np.asarray(first), np.arange(29) * 3)
    np.testing.assert_array_equal(np.asarray(again), np.arange(29) * 3)
