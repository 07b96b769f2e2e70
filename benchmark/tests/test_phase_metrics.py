"""The readers of the engine's per-query phase account: each against a
made-up run and ring, on a program without the ring, and through the
harness's CPU rehearsal (where there is no device trace to pair them with,
so the line leaves them out)."""
import json

import pytest

import run as harness
from metrics import (host_dispatch_ms, keyed_dispatches_per_query,
                     phase_account, plan_ms, query_overhead_ms,
                     result_wait_ms, scan_parse_ms, upload_mb_per_query)
from spark_rapids_tpu.runtime import obs

NEW = {"plan_ms": plan_ms, "host_dispatch_ms": host_dispatch_ms,
       "result_wait_ms": result_wait_ms,
       "query_overhead_ms": query_overhead_ms,
       "keyed_dispatches_per_query": keyed_dispatches_per_query,
       "scan_parse_ms": scan_parse_ms,
       "upload_mb_per_query": upload_mb_per_query}


def record(k, seq=0, t0_ms=0, wall_ms=400):
    """Query k's made-up record: every number a multiple of k."""
    ms = 1_000_000 * k
    return {"seq": seq, "status": "ok", "t0_ns": t0_ms * 1_000_000,
            "wall_ns": wall_ms * 1_000_000,
            "phases_ns": {"parse": ms, "admit": 2 * ms, "plan": 3 * ms,
                          "execute": 20 * ms, "fetch": 5 * ms,
                          "epilogue": 4 * ms, "unspanned": ms},
            "timers_ns": {"tpuDecodeTime": 7 * ms, "opTime": 9 * ms,
                          "deviceWaitTime": 6 * ms,
                          "pipelineStallTime": 4 * ms},
            "counters": {"keyed_dispatches": 10 * k,
                         "upload_bytes": 8_000_000 * k}}


def made_up_ring():
    """Five earlier queries, a pause (the profiler starting), then two
    traced passes of two queries: 2.0 s from the first pass's start at
    10.0 s to the last one's end, each record a little inside its query's
    annotation."""
    early = [record(9, seq=i + 1, t0_ms=500 * i + 1) for i in range(5)]
    traced = [record(k, seq=5 + k, t0_ms=10_000 + 500 * (k - 1) + 1,
                     wall_ms=498) for k in (1, 2, 3, 4)]
    return early + traced


@pytest.fixture
def traced_run(monkeypatch):
    ring = made_up_ring()
    monkeypatch.setattr(
        obs, "recent_queries",
        lambda n=None: ring if n is None else ring[len(ring) - n:],
        raising=False)
    run = harness.Run()
    run.queries_per_pass = 2
    run.trace = {"passes": 2, "busy_s": 1.0, "window_s": 2.0, "programs": 8}
    run.ring = ring  # for the tests that spoil it
    return run


def test_each_reader_takes_the_mean_over_the_traced_queries(traced_run):
    # queries 1..4: the mean k is 2.5
    want = {"plan_ms": 7.5, "host_dispatch_ms": 25.0, "result_wait_ms": 15.0,
            "query_overhead_ms": 15.0, "keyed_dispatches_per_query": 25.0,
            "scan_parse_ms": 17.5, "upload_mb_per_query": 20.0}
    assert {n: m.read(traced_run) for n, m in NEW.items()} == \
        pytest.approx(want)


def test_the_records_are_picked_by_the_traced_window(traced_run):
    recs = phase_account.records(traced_run)
    assert [r["seq"] for r in recs] == [6, 7, 8, 9]


def _intruder(ring):     # another top-level action inside the window
    ring.insert(-1, record(9, seq=9, t0_ms=11_400, wall_ms=50))
    ring[-1]["seq"] = 10


def _failed(ring):
    ring[-2]["status"] = "failed"


def _renumbered(ring):   # a record between them fell out of the ring
    ring[-1]["seq"] += 1


def _longer_window(ring):  # the trace saw a pass the ring has no record of
    del ring[4:6]


@pytest.mark.parametrize("spoil", [_intruder, _failed, _renumbered,
                                   _longer_window])
def test_a_window_that_is_not_the_traced_queries_reads_none(traced_run,
                                                            spoil):
    """Whatever breaks the one-to-one match of records and traced queries
    silences every reader; none averages over the wrong queries."""
    spoil(traced_run.ring)
    assert phase_account.records(traced_run) is None
    assert {m.read(traced_run) for m in NEW.values()} == {None}


def test_parallel_partitions_cannot_take_host_dispatch_below_zero(
        traced_run):
    for r in traced_run.ring:  # waits summed over threads pass the wall
        r["timers_ns"]["deviceWaitTime"] = 3 * r["phases_ns"]["execute"]
    assert host_dispatch_ms.read(traced_run) == 0
    assert result_wait_ms.read(traced_run) == pytest.approx(150.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none(traced_run, monkeypatch, name):
    read = NEW[name].read
    traced_run.trace = None                      # an untraced run
    assert read(traced_run) is None
    traced_run.trace = {"passes": 5, "window_s": 2.0}  # more queries than
    assert read(traced_run) is None                    # records
    traced_run.trace = {"passes": 2, "window_s": 2.0}
    assert read(traced_run) is not None
    monkeypatch.setattr(obs, "recent_queries", lambda n=None: [{}] * 4)
    assert read(traced_run) is None              # records of another shape
    monkeypatch.setattr(obs, "recent_queries", lambda n=None: [])
    assert read(traced_run) is None              # obs off: an empty ring
    monkeypatch.delattr(obs, "recent_queries")   # the parent: no ring at all
    assert read(traced_run) is None


def test_a_scanless_query_has_no_scan_metrics(traced_run, monkeypatch):
    for resident in traced_run.ring:
        del resident["timers_ns"]["tpuDecodeTime"]
        del resident["timers_ns"]["pipelineStallTime"]
        resident["counters"]["upload_bytes"] = 0
    assert scan_parse_ms.read(traced_run) is None
    assert upload_mb_per_query.read(traced_run) is None
    assert plan_ms.read(traced_run) == pytest.approx(7.5)
    assert host_dispatch_ms.read(traced_run) == pytest.approx(35.0)


def test_the_real_ring_feeds_the_readers(capsys):
    """A rehearsal leaves the engine's real records behind (the ring
    outlives the session); paired with a made-up trace of its last two
    passes every reader of the cell finds its number."""
    cell = "tpch_sf1_parquet.q6"
    rc = harness.main(["--workload", cell, "--seed", "11", "--seconds", "0.3",
                       "--trace", "1", "--rehearse-rows", "6000"])
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    # the CPU has no device plane: no trace, so the line leaves them out
    assert not set(NEW) & set(line["metrics"])
    run = harness.Run()
    run.queries_per_pass = 1
    first, last = obs.recent_queries(2)
    # the passes' annotations start a parse before their first record
    run.trace = {"passes": 2, "window_s": 1e-3 + 1e-9 * (
        last["t0_ns"] + last["wall_ns"] - first["t0_ns"])}
    recs = phase_account.records(run)
    assert recs == [first, last]
    values = {n: m.read(run) for n, m in NEW.items()}
    assert all(v is not None and v > 0 for v in values.values()), values
    # host dispatch, the waits for the device and the stalls on the scan
    # are query.execute, whole
    stall_ms = sum(r["timers_ns"]["pipelineStallTime"] for r in recs) / 2e6
    assert values["host_dispatch_ms"] + values["result_wait_ms"] + stall_ms \
        == pytest.approx(sum(r["phases_ns"]["execute"] for r in recs) / 2e6)


def test_the_new_names_are_listed_for_their_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        listed = {m["name"] for m in
                  harness.metrics_of(bench, "per_layer", cell["name"])}
        scan = {"scan_parse_ms", "upload_mb_per_query"}
        want = set(NEW) if "parquet" in cell["name"] else set(NEW) - scan
        assert listed & set(NEW) == want
