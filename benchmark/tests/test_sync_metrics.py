"""`host_syncs_per_query`, `step_sync_ms` and `scan_stall_ms` (ISSUE 38)
over hand-built phase-account records: each reads the mean over the traced
queries; `fetch` is no step; a record without `syncs` (the parent's
account) and a process without the ring read nothing and do not raise."""
import pytest

import run as harness
from metrics import host_syncs_per_query, scan_stall_ms, step_sync_ms
from spark_rapids_tpu.runtime import obs

MS = 1_000_000


def record(seq, k):
    """Query k's record: k size syncs of 10 ms, one fetch of k ms and a
    pipeline stall of 3k ms."""
    return {"seq": seq, "status": "ok", "t0_ns": (10_000 + 500 * seq) * MS,
            "wall_ns": 498 * MS,
            "timers_ns": {"deviceWaitTime": 11 * k * MS,
                          "pipelineStallTime": 3 * k * MS},
            "counters": {"host_syncs": k + 1, "pipeline_stalls": k},
            "syncs": {"size": [k, 10 * k * MS], "fetch": [1, k * MS]}}


@pytest.fixture
def traced_run(monkeypatch):
    """Two traced passes of two queries, k = 1..4."""
    ring = [record(seq, seq) for seq in (1, 2, 3, 4)]
    monkeypatch.setattr(
        obs, "recent_queries",
        lambda n=None: ring if n is None else ring[len(ring) - n:],
        raising=False)
    run = harness.Run()
    run.queries_per_pass = 2
    run.trace = {"passes": 2, "busy_s": 0.1, "window_s": 2.0, "programs": 8}
    run.ring = ring
    return run


def test_each_reads_the_mean_over_the_traced_queries(traced_run):
    assert host_syncs_per_query.read(traced_run) == pytest.approx(3.5)
    assert step_sync_ms.read(traced_run) == pytest.approx(25.0)  # no fetch
    assert scan_stall_ms.read(traced_run) == pytest.approx(7.5)


def test_the_sites_split_the_wait(traced_run):
    """What the engine holds of a record: its sites' ns are its wait."""
    for r in traced_run.ring:
        assert sum(ns for _, ns in r["syncs"].values()) == \
            r["timers_ns"]["deviceWaitTime"]
        assert sum(n for n, _ in r["syncs"].values()) == \
            r["counters"]["host_syncs"]


def test_only_fetch_reads_zero_steps(traced_run):
    for r in traced_run.ring:
        del r["syncs"]["size"]
    assert step_sync_ms.read(traced_run) == 0


@pytest.mark.parametrize("gone", ["syncs", "host_syncs", "stall", "trace",
                                  "ring"])
def test_nothing_to_read_is_none(traced_run, monkeypatch, gone):
    if gone == "syncs":           # the parent's account
        for r in traced_run.ring:
            del r["syncs"]
        assert step_sync_ms.read(traced_run) is None
        return
    if gone == "host_syncs":
        for r in traced_run.ring:
            del r["counters"]["host_syncs"]
        assert host_syncs_per_query.read(traced_run) is None
        return
    if gone == "stall":           # a query with no pipeline
        for r in traced_run.ring:
            del r["timers_ns"]["pipelineStallTime"]
        assert scan_stall_ms.read(traced_run) is None
        return
    if gone == "trace":
        traced_run.trace = None
    else:
        monkeypatch.delattr(obs, "recent_queries")
    assert {m.read(traced_run) for m in (
        host_syncs_per_query, step_sync_ms, scan_stall_ms)} == {None}
