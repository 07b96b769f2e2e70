"""`exchange_bypass_per_query` (ISSUE 37) against recorded phase-account
records: [Q1, Q6] on a mesh reads 0.5; a program whose account lacks the
counter (the parent), one that runs no mesh, an untraced run and a process
without the ring read nothing and do not raise."""
import pytest

import run as harness
from metrics import exchange_bypass_per_query
from spark_rapids_tpu.runtime import obs


def record(seq, bypassed):
    return {"seq": seq, "status": "ok", "t0_ns": (10_000 + 500 * seq) * 10**6,
            "wall_ns": 498 * 10**6, "timers_ns": {},
            "counters": {"shard_waves": 1, "exchange_bypassed": bypassed},
            "mesh": {"devices": 4, "shard_rows": [[10, 10, 10, 10]]}}


@pytest.fixture
def traced_run(monkeypatch):
    """Two traced passes of [Q1, Q6] on a mesh of four."""
    ring = [record(seq, bypassed) for seq, bypassed in
            enumerate((1, 0, 1, 0), start=1)]
    monkeypatch.setattr(
        obs, "recent_queries",
        lambda n=None: ring if n is None else ring[len(ring) - n:],
        raising=False)
    run = harness.Run()
    run.queries_per_pass = 2
    run.trace = {"passes": 2, "busy_s": 0.1, "window_s": 2.0, "programs": 8}
    run.ring = ring
    return run


def test_q1_bypasses_and_q6_has_nothing_to(traced_run):
    assert exchange_bypass_per_query.read(traced_run) == 0.5


@pytest.mark.parametrize("gone", ["the_counter", "the_mesh", "the_trace",
                                  "the_ring"])
def test_nothing_to_read_is_none(traced_run, monkeypatch, gone):
    if gone == "the_counter":     # the parent's account
        for r in traced_run.ring:
            del r["counters"]["exchange_bypassed"]
    elif gone == "the_mesh":      # the one-chip cells
        for r in traced_run.ring:
            del r["mesh"]
    elif gone == "the_trace":
        traced_run.trace = None
    else:
        monkeypatch.delattr(obs, "recent_queries")
    assert exchange_bypass_per_query.read(traced_run) is None
