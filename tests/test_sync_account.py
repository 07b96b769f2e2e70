"""The host-sync account (ISSUE 38): every device->host read of a query
is a `device_wait(site)` block, the span `sync.<site>`, counted and timed
by site into the query's record (`syncs`, `counters.host_syncs`,
`timers_ns.deviceWaitTime`); a scan pipeline's stall is the span
`pipeline.stall`, counted as `pipelineStalls`."""
import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, LazyRowCount, from_arrow, host_int,
)
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.runtime import obs, trace
from spark_rapids_tpu.runtime.metrics import ESSENTIAL, GpuMetric
from spark_rapids_tpu.runtime.obs import flight, live
from spark_rapids_tpu.runtime.obs import phases as PH
from spark_rapids_tpu.runtime.pipeline import PipelinedIterator
from spark_rapids_tpu.sql.session import TpuSession

JOIN = ("select f.g, sum(f.v * d.w) as s from f join d on f.k = d.k "
        "group by f.g order by s desc")
LIMIT = "select k, v from f where v > 5 limit 10"
TOPN = "select k, v from f order by v desc limit 10"


def _session(conf=None):
    rng = np.random.default_rng(3)
    s = TpuSession(conf or {})
    n = 5000
    s.create_or_replace_temp_view("f", s.create_dataframe(pa.table({
        "k": rng.integers(0, 300, n), "v": rng.uniform(0, 10, n),
        "g": rng.integers(0, 7, n)})))
    s.create_or_replace_temp_view("d", s.create_dataframe(pa.table({
        "k": np.repeat(np.arange(300), 2), "w": rng.uniform(0, 1, 600)})))
    return s


def _record(s, text):
    s.sql(text).collect()
    return obs.recent_queries(1)[0]


def _added(before):
    """{site: (syncs, ns)} since `before` (phases.syncs_now())."""
    return {site: (n - before[site][0], ns - before[site][1])
            for site, (n, ns) in PH.syncs_now().items()
            if n != before[site][0]}


@pytest.mark.parametrize("text, sites", [
    # the join's expansion size and the dense build's key range, the
    # group-by's key range, the result's download
    (JOIN, {"size", "range", "fetch"}),
    # the LIMIT's remaining budget, a LazyRowCount-like carry
    (LIMIT, {"count", "fetch"}),
    # the top-n's candidate count sizes its sort
    (TOPN, {"size", "fetch"}),
])
def test_a_query_reads_its_syncs_site_by_site(text, sites):
    s = _session()
    s.sql(text).collect()                       # cold: compiles
    rec, again = _record(s, text), _record(s, text)
    assert set(rec["syncs"]) == sites
    assert rec["syncs"]["fetch"][0] == 1
    # a fixed plan makes the same syncs every run
    assert {k: n for k, (n, _) in rec["syncs"].items()} == \
        {k: n for k, (n, _) in again["syncs"].items()}
    assert all(ns > 0 for _, ns in rec["syncs"].values())


@pytest.mark.parametrize("text", [JOIN, LIMIT, TOPN])
def test_the_sites_are_the_wait_and_the_count_exactly(text):
    s = _session()
    rec = _record(s, text)
    assert sum(ns for _, ns in rec["syncs"].values()) == \
        rec["timers_ns"]["deviceWaitTime"]
    assert sum(n for n, _ in rec["syncs"].values()) == \
        rec["counters"]["host_syncs"]


def test_an_explicit_lazy_count_is_one_count_sync():
    before = PH.syncs_now()
    n = LazyRowCount(jnp.sum(jnp.arange(10) > 3))
    assert int(n) == 6 and int(n) == 6
    got = _added(before)
    assert list(got) == ["count"] and got["count"][0] == 1


def test_a_host_scalar_is_no_sync():
    before = PH.syncs_now()
    assert host_int(np.int64(4), "size") == 4
    assert int(LazyRowCount(np.int32(3))) == 3
    assert _added(before) == {}


def test_a_site_outside_the_roster_is_refused():
    with pytest.raises(KeyError):
        PH.device_wait("elsewhere")


def test_every_sync_span_reaches_the_tracer_with_the_query_id(tmp_path):
    s = _session({"spark.rapids.sql.trace.enabled": "true",
                  "spark.rapids.sql.trace.path": str(tmp_path),
                  "spark.rapids.sql.trace.level": "ESSENTIAL"})
    rec = _record(s, JOIN)
    with open(s.last_trace_paths["trace"]) as f:
        events = json.load(f)["traceEvents"]
    syncs = [e for e in events if e.get("cat") == "sync"]
    assert sorted({e["name"] for e in syncs}) == \
        sorted("sync." + site for site in rec["syncs"])
    for site, (n, ns) in rec["syncs"].items():
        mine = [e for e in syncs if e["name"] == "sync." + site]
        assert len(mine) == n
        # the span IS the clock: one timed block feeds both
        assert sum(e["dur"] for e in mine) == pytest.approx(ns / 1e3)
    assert {e["args"]["query_id"] for e in syncs} == {rec["query_id"]}


def test_every_sync_span_enters_the_flight_ring(tmp_path):
    rec_ = flight.install(capacity=4096, out_dir=str(tmp_path))
    s = _session()
    rec = _record(s, JOIN)
    doc = json.load(open(rec_.dump("test")))
    syncs = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"].startswith("sync.")]
    mine = [e for e in syncs if e["args"].get("query_id") == rec["query_id"]]
    assert len(mine) == rec["counters"]["host_syncs"]


def test_with_no_sink_the_door_still_counts(monkeypatch):
    monkeypatch.setattr(trace, "_TRACER", None)
    monkeypatch.setattr(trace._flight, "_REC", None)
    monkeypatch.setattr(trace._reqtrace, "_REC", None)
    assert trace._sinks(ESSENTIAL) is None   # the bare timer's path
    before = PH.syncs_now()
    assert host_int(jnp.sum(jnp.arange(5)), "width") == 10
    got = _added(before)
    assert list(got) == ["width"] and got["width"][0] == 1
    assert got["width"][1] > 0


def _slow(n, pause):
    for i in range(n):
        time.sleep(pause)
        yield i


def test_a_slow_producer_stalls_its_consumer_as_spans(tmp_path):
    tr = trace.start_query(C.RapidsConf({
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.path": str(tmp_path)}))
    stall, stalls = GpuMetric("pipelineStallTime"), GpuMetric("pipelineStalls")
    prev = live.bind(4242)
    try:
        pit = PipelinedIterator(_slow(4, 0.02), depth=1, label="slow",
                                stall_metric=stall, stalls_metric=stalls)
        try:
            assert list(pit) == [0, 1, 2, 3]
        finally:
            pit.close()
    finally:
        live.bind(prev)
        paths = trace.end_query(tr)
    assert stalls.value >= 1 and stall.value > 0
    with open(paths["trace"]) as f:
        spans = [e for e in json.load(f)["traceEvents"]
                 if e.get("name") == "pipeline.stall"]
    assert len(spans) == stalls.value
    assert sum(e["dur"] for e in spans) == pytest.approx(stall.value / 1e3)
    assert all(e["args"] == {"label": "slow", "query_id": 4242}
               for e in spans)


def test_a_fed_consumer_never_stalls():
    stall, stalls = GpuMetric("pipelineStallTime"), GpuMetric("pipelineStalls")
    gate = threading.Event()

    def src():
        yield 0
        gate.wait(5)
        yield 1

    pit = PipelinedIterator(src(), depth=4, stall_metric=stall,
                            stalls_metric=stalls)
    try:
        it = iter(pit)
        time.sleep(0.05)          # the first item is queued by now
        assert next(it) == 0
        assert stalls.value == 0
        gate.set()
        assert list(it) == [1]
    finally:
        pit.close()


def test_a_scan_query_counts_its_stalls_in_its_record(tmp_path):
    """A Parquet scan behind a pipeline: each stall of the query's thread
    is counted, and timed into pipelineStallTime."""
    import pyarrow.parquet as pq
    rng = np.random.default_rng(1)
    n = 200_000
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": rng.integers(0, 50, n),
                             "v": rng.uniform(0, 1, n)}), path,
                   row_group_size=25_000)
    s = TpuSession()
    s.create_or_replace_temp_view("t", s.read_parquet(path))
    rec = _record(s, "select k, sum(v) from t group by k")
    stalls = rec["counters"]["pipeline_stalls"]
    assert stalls >= 1
    assert (rec["timers_ns"]["pipelineStallTime"] > 0) == (stalls > 0)


@pytest.mark.parametrize("k", [1, 3, 5])
def test_concat_reads_every_flat_string_column_at_once(k):
    """concat_batches over k flat string columns: their byte lengths are
    ONE read (`width`), not one a column."""
    words = [f"word{i:06d}" for i in range(400)]   # all distinct: flat
    tb = pa.table({f"s{j}": words for j in range(k)})
    a, b = from_arrow(tb), from_arrow(tb.slice(0, 250))
    assert all(not c.is_dict for c in a.columns)
    a = ColumnarBatch(a.columns, LazyRowCount(jnp.asarray(400)))
    b = ColumnarBatch(b.columns, LazyRowCount(jnp.asarray(250)))
    before = PH.syncs_now()
    out = K.concat_batches([a, b])
    got = _added(before)
    assert got["width"][0] == 1 and got["count"][0] == 1
    assert int(out.num_rows) == 650
    for j in range(k):
        assert out.columns[j].str_bytes == 650 * 10
