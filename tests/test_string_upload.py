"""How a string column is laid out on upload (columnar/batch.py): a
dictionary where its vocabulary is half its rows or fewer, flat otherwise;
a long column is sampled first, so that a column of distinct values is not
hashed whole to learn it should not have been; flat batches concatenate by
one copy a part and keep the host's count of their bytes."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import batch as B
from spark_rapids_tpu.sql.session import TpuSession

_N = (1 << 17) + 4096       # just past the sampling bound


def _column(kind: str, n: int):
    rng = np.random.default_rng(5)
    if kind == "distinct":
        return [f"row{i:07d}" for i in range(n)]
    if kind == "half":      # a vocabulary of half the rows, drawn evenly
        return [f"v{int(v)}" for v in rng.integers(0, n // 2, n)]
    if kind == "pairs":     # every value twice, side by side
        return [f"v{i // 2}" for i in range(n)]
    if kind == "far_pairs":     # every value twice, so far apart that no
        h = n // 2              # two runs of the sample hold the same one
        return [f"v{i}" for i in range(h)] + [
            f"v{(i + B._SAMPLE_RUN_ROWS + 100) % h}" for i in range(n - h)]
    return [f"v{int(v)}" for v in rng.integers(0, 500, n)]


@pytest.mark.parametrize("kind,n,layout,known_distinct", [
    ("distinct", _N, "flat", False),        # sampled: not all seen
    ("distinct", 5000, "flat", True),       # encoded whole: all seen
    ("half", _N, "dict", False),
    ("pairs", _N, "dict", False),
    # the sample sees no repeat: flat, where the whole vocabulary (half
    # the rows) would have made it a dictionary; correct, only larger
    ("far_pairs", _N, "flat", False),
    ("far_pairs", 5000, "dict", False),
    ("few", _N, "dict", False),
    ("few", 5000, "dict", False),
])
def test_layout_rule(kind, n, layout, known_distinct):
    values = _column(kind, n)
    arr = pa.array(values, pa.string())
    col = B.column_from_arrow(arr, T.STRING, B.round_capacity(n))
    assert ("dict" if col.is_dict else "flat") == layout
    assert col.flat_distinct is known_distinct
    assert col.str_width == max(len(v) for v in values)
    if layout == "flat":
        assert col.str_bytes == sum(len(v) for v in values)


def test_a_long_distinct_column_is_not_encoded_whole(monkeypatch):
    seen = []
    real = B._mostly_distinct

    def spy(arr, n):
        out = real(arr, n)
        seen.append((n, out))
        return out

    monkeypatch.setattr(B, "_mostly_distinct", spy)
    arr = pa.array(_column("distinct", _N), pa.string())
    B.column_from_arrow(arr, T.STRING, B.round_capacity(_N))
    assert seen == [(_N, True)]
    sample_rows = B._SAMPLE_RUNS * B._SAMPLE_RUN_ROWS
    assert sample_rows * 2 <= _N    # the sample is a fraction of the column


def test_flat_batches_concatenate_and_keep_their_byte_count():
    rng = np.random.default_rng(3)
    n = 5000
    vals = [None if rng.random() < 0.05 else "".join(
        chr(97 + int(x)) for x in rng.integers(0, 26, rng.integers(0, 40)))
        + f"#{i}" for i in range(n)]
    s = TpuSession({"spark.rapids.sql.reader.batchSizeRows": 700})
    df = s.create_dataframe(pa.table({"s": pa.array(vals, pa.string())}))
    cached = df.cache()
    assert cached.to_pydict()["s"] == vals
    col = cached.plan.materialized[0][0].get_batch().columns[0]
    assert not col.is_dict
    assert col.str_bytes == sum(len(v) for v in vals if v is not None)
    assert col.str_width == max(len(v) for v in vals if v is not None)
