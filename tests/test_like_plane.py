"""A LIKE of literal runs between `%` (and startswith, endswith, contains,
which are its cases) is matched by passes over the whole byte plane
(ops/strmatch.match_runs) and agrees with a chain of Python's str.find,
flat and dictionary columns alike; a `_` still goes to the NFA."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.expr.strings import Like, _LiteralMatch
from spark_rapids_tpu.runtime import compile_cache as CC
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession

_WORDS = ["special", "requests", "spec", "re", "quests", "ab", "a", "b", "é",
          "東京", "", " ", "%", "ial"]


def _find_chain(s: str, pattern: str):
    """LIKE with `%` alone, by str.find: the oracle."""
    if s is None:
        return None
    runs = pattern.split("%")
    if len(runs) == 1:
        return s == pattern
    first, last, mids = runs[0], runs[-1], [r for r in runs[1:-1] if r]
    if len(s) < len(first) + len(last) or not s.startswith(first) \
            or not s.endswith(last):
        return False
    pos, stop = len(first), len(s) - len(last)
    for m in mids:
        i = s.find(m, pos, stop)
        if i < 0:
            return False
        pos = i + len(m)
    return True


def _rows(seed: int, n: int = 1200):
    rng = np.random.default_rng(seed)
    rows = [None if rng.random() < 0.08 else "".join(
        _WORDS[j] + (" " if rng.random() < 0.6 else "")
        for j in rng.integers(0, len(_WORDS), rng.integers(0, 7)))
        for _ in range(n)]
    # a match that would straddle two rows, an empty row between them, a
    # run longer than the row, a multi-byte row, the words out of order
    rows += ["speci", "al requests", "", "special", "", "requests",
             "requests special", "specialrequests", "é", "東", "ab"]
    return rows


_PATTERNS = ["%special%requests%", "special%", "%requests", "%a%b%a%",
             "a%b", "spec%ial%re%quests%", "%ab%ab%ab%ab%", "%é%", "%東京",
             "a%a", "%special requests and then some more, longer than any%",
             "%%", "%", "%a%%b%", "ab%", "% %"]


@pytest.mark.parametrize("layout", ["flat", "dictionary"])
@pytest.mark.parametrize("pattern", _PATTERNS)
def test_like_runs_against_find_chain(pattern, layout):
    rows = _rows(len(pattern))
    if layout == "flat":    # distinct a row: the upload keeps it flat
        rows = [r if r is None else f"{r}#{i}" for i, r in enumerate(rows)]
        pattern = pattern + "%" if not pattern.endswith("%") else pattern
    else:                   # few values, many rows: a dictionary
        rows = rows[-120:] * 10
    s = TpuSession()
    df = s.create_dataframe(pa.table({"s": pa.array(rows, pa.string())}))
    batch_col = df.cache()
    s.create_or_replace_temp_view("t", batch_col)
    before = CC.stats()
    got = s.sql(f"select s like '{pattern}' as m, s not like '{pattern}' "
                "as n from t").to_pydict()
    want = [_find_chain(r, pattern) for r in rows]
    assert got["m"] == want
    assert got["n"] == [None if w is None else not w for w in want]
    after = CC.stats()
    assert after["like_nfa_traced"] == before["like_nfa_traced"]
    if pattern.replace("%", ""):
        assert after["like_plane_traced"] > before["like_plane_traced"]
    layouts = {c.is_dict for c in
               batch_col.plan.materialized[0][0].get_batch().columns}
    assert layouts == {layout == "dictionary"}


def test_startswith_endswith_contains_are_the_same_kernel():
    rows = _rows(3)
    s = TpuSession()
    df = s.create_dataframe(pa.table({"s": pa.array(rows, pa.string())}))
    got = df.select(F.startswith(col("s"), "spec").alias("a"),
                    F.endswith(col("s"), "quests").alias("b"),
                    F.contains(col("s"), "al re").alias("c"),
                    F.contains(col("s"), "").alias("d")).to_pydict()
    assert got["a"] == [None if r is None else r.startswith("spec")
                        for r in rows]
    assert got["b"] == [None if r is None else r.endswith("quests")
                        for r in rows]
    assert got["c"] == [None if r is None else "al re" in r for r in rows]
    assert got["d"] == [None if r is None else True for r in rows]
    for fn in (F.startswith, F.endswith, F.contains):
        assert isinstance(fn(col("s"), "x"), _LiteralMatch)


def test_underscore_still_takes_the_nfa():
    rows = ["special requests", "specialXrequests", "special", None]
    s = TpuSession()
    df = s.create_dataframe(pa.table({"s": pa.array(rows, pa.string())}))
    before = CC.stats()
    got = df.select(Like(col("s"), "special_requests").alias("m")).to_pydict()
    assert got["m"] == [True, True, False, None]
    after = CC.stats()
    assert after["like_nfa_traced"] > before["like_nfa_traced"]
    assert after["like_plane_traced"] == before["like_plane_traced"]


@pytest.mark.parametrize("shape", ["filter", "project"])
def test_the_kernel_gets_the_hosts_bound_on_the_longest_row(monkeypatch,
                                                            shape):
    """Host stats do not cross a jit boundary: a Filter or a Project that
    matches strings takes the column's width into its program's key and
    hands it to the kernel, rounded up to the doubling window; without it
    the kernel would size its planes by the whole byte plane."""
    from spark_rapids_tpu.ops import strmatch
    seen = []
    real = strmatch.match_runs

    def spy(offsets, raw, width, *runs):
        seen.append(width)
        return real(offsets, raw, width, *runs)

    monkeypatch.setattr(strmatch, "match_runs", spy)
    rows = [f"{r}#{i}" for i, r in enumerate(_rows(9)) if r is not None]
    longest = max(len(r.encode()) for r in rows)
    s = TpuSession()
    s.create_or_replace_temp_view("w", s.create_dataframe(
        pa.table({"s": pa.array(rows, pa.string())})).cache())
    pattern = f"%special%{shape}%"      # a program of this test's own
    got = s.sql(f"select s like '{pattern}' as m from w" if shape == "project"
                else f"select s from w where s like '{pattern}'").to_pydict()
    want = [_find_chain(r, pattern) for r in rows]
    assert got == ({"m": want} if shape == "project"
                   else {"s": [r for r, w in zip(rows, want) if w]})
    assert seen == [(1 << longest.bit_length()) - 1]
