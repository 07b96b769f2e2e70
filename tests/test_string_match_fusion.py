"""A Filter that matches strings fuses like any other (into the aggregate's
update kernel, a stage chain, an absorbed chain). What the stage does with
it is decided from the batches: over a dictionary column the match stays
in the stage's one program; from the first batch that brings a flat string
column the operators run apart, so that the Filter alone takes the
column's width from the host and is timed as the match
(exec/tpu_nodes.meets_flat_string)."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.expr.strings import Like, plane_matches
from spark_rapids_tpu.runtime import obs
from spark_rapids_tpu.sql.session import TpuSession

_N = 420
_LIKE = "s like '%special%requests%'"

#: shape -> (query, where the Filter lands in the exec tree, the answer
#: from the kept rows' (k, v))
_SHAPES = {
    "aggregate_pre_filter": (
        f"select k, count(*) as n from t where {_LIKE} group by k",
        "HashAggregateExec <- Aggregate[keys=[k], aggs=[n]] "
        "[string match: apart over a flat column]",
        lambda kv: sorted((k, sum(1 for k2, _ in kv if k2 == k))
                          for k in {k for k, _ in kv})),
    "stage_chain": (
        f"select v + 1 as w, k from t where {_LIKE} and v > 3",
        "FusedStageExec(Project+Filter)",
        lambda kv: sorted((v + 1, k) for k, v in kv if v > 3)),
    "absorbed_chain": (
        f"select sum(v * 2) as w from (select v, k from t where {_LIKE}) "
        "where k > 2",
        "FilterExec <- Filter[Like('%special%requests%';"
        "BoundRef(0:string;))] [fused]",
        lambda kv: [(sum(2 * v for k, v in kv if k > 2),)]),
}


def _table(layout: str):
    rng = np.random.default_rng(11)
    words = ["special", "requests", "ab", "cd", "ef"]
    base = [" ".join(words[j] for j in rng.integers(0, 5, 4))
            for _ in range(_N)]
    rows = [f"{r}#{i}" for i, r in enumerate(base)] if layout == "flat" \
        else base[:20] * (_N // 20)
    return pa.table({"s": pa.array(rows),
                     "k": pa.array([i % 7 for i in range(_N)], pa.int64()),
                     "v": pa.array(range(_N), pa.int64())})


@pytest.mark.parametrize("layout", ["flat", "dictionary"])
@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_a_fused_match_runs_apart_only_over_a_flat_column(shape, layout):
    sql, node, answer = _SHAPES[shape]
    t = _table(layout)
    s = TpuSession()
    cached = s.create_dataframe(t).cache()
    s.create_or_replace_temp_view("t", cached)
    got = s.sql(sql).to_pydict()
    kept = [(k, v) for r, k, v in zip(*(t[c].to_pylist() for c in "skv"))
            if 0 <= r.find("special") < r.find("requests", r.find("special"))]
    assert kept and sorted(zip(*got.values())) == answer(kept)
    # the planner fused it either way
    assert node in s._last_exec.tree_string()
    assert {c.is_dict for c in cached.plan.materialized[0][0].get_batch()
            .columns if c.is_string} == {layout == "dictionary"}
    rec = obs.recent_queries(1)[0]
    fused_dispatches = sum(m.get("stageDispatches", 0)
                           for m in s.last_metrics().values())
    if layout == "flat":    # apart: the match is timed and counted
        assert rec["timers_ns"]["stringMatchTime"] > 0
        assert rec["counters"]["string_match_bytes"] == sum(
            len(r.encode()) for r in t["s"].to_pylist()) + 4 * (_N + 1)
        assert fused_dispatches == 0
    else:                   # one program, as before there was a kernel
        assert rec["timers_ns"].get("stringMatchTime", 0) == 0
        assert rec["counters"]["string_match_bytes"] == 0
        assert fused_dispatches == (0 if shape == "aggregate_pre_filter"
                                    else 1)


@pytest.mark.parametrize("layout", ["flat", "dictionary"])
def test_a_chain_rooted_at_a_device_decode_scan_sees_the_hosts_strings(
        layout, tmp_path):
    """The batch is still encoded when the stage looks at it: its strings
    are the columns the host decoded, riding along."""
    import pyarrow.parquet as pq
    t = _table(layout)
    pq.write_table(t, tmp_path / "t.parquet")
    s = TpuSession()
    s.create_or_replace_temp_view("t", s.read_parquet(
        str(tmp_path / "t.parquet")))
    sql, _node, answer = _SHAPES["stage_chain"]
    got = s.sql(sql).to_pydict()
    kept = [(k, v) for r, k, v in zip(*(t[c].to_pylist() for c in "skv"))
            if 0 <= r.find("special") < r.find("requests", r.find("special"))]
    assert sorted(zip(*got.values())) == answer(kept)
    assert "FusedStageExec(Project+Filter+DeviceDecodeScan)" in \
        s._last_exec.tree_string()
    rec = obs.recent_queries(1)[0]
    assert (rec["timers_ns"].get("stringMatchTime", 0) > 0) is \
        (layout == "flat")
    assert sum(m.get("stageDispatches", 0) for m in
               s.last_metrics().values()) == (layout == "dictionary")


@pytest.mark.parametrize("pattern,plane", [
    ("%special%requests%", True), ("special%", True), ("spec_al", True),
    ("special", False), ("%", False), ("%%", False)])
def test_a_like_that_is_an_equality_is_no_plane_match(pattern, plane):
    assert bool(plane_matches(Like(col("s"), pattern))) is plane
