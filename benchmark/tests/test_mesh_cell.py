"""The four-chip cell's own files: the generator that hands tables over in
row ranges, the cell rehearsed end to end over a mesh of four virtual CPU
devices (a child process: the device count is fixed when jax starts), and
each of its five readers against a recorded phase-account record, on a
program that runs no mesh, and on one without the ring."""
import json
import os
import subprocess
import sys

import pytest

import datagen
import datagen_ranges
import run as harness
from metrics import (mesh_exchange_ms, mesh_hbm_roofline,
                     mesh_put_mb_per_query, shard_skew,
                     shard_waves_per_query)
from spark_rapids_tpu.runtime import obs

CELL = "tpch_sf20_mesh4.q1q6"
MESH = {"shard_waves_per_query": shard_waves_per_query,
        "mesh_exchange_ms": mesh_exchange_ms,
        "mesh_put_mb_per_query": mesh_put_mb_per_query,
        "shard_skew": shard_skew, "mesh_hbm_roofline": mesh_hbm_roofline}


def test_the_configuration_is_the_one_chip_cells_sibling():
    mesh4 = harness.load_json(harness.HERE, "configs", "tpch_sf20_mesh4.json")
    sf5 = harness.load_json(harness.HERE, "configs", "tpch_sf5_hbm.json")
    for same in ("placement", "precision", "guarantees", "limits", "tables"):
        assert mesh4[same] == sf5[same], same
    assert mesh4["chips"] == 4 and mesh4["scale_factor"] == 20
    assert mesh4["reduced"] == ["scale_factor"] and len(mesh4["source"]) <= 200
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["name"] == CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "q1q6"
    assert datagen.row_counts(20)["lineitem"] == 119_999_616


def test_row_ranges_hold_the_generator_rows_in_chunks_that_cast():
    sf, seed = 6000 / harness.LINEITEM_ROWS_PER_SF, 2**31 + 28
    whole, ranged = datagen.generate(sf, seed), datagen_ranges.generate(sf,
                                                                        seed)
    for name, table in whole.items():
        assert ranged[name].equals(table)
        assert {c.num_chunks for c in ranged[name].columns} == \
            {datagen_ranges.RANGES}
        assert harness.plain_strings(ranged[name]).num_rows == table.num_rows


def test_cell_over_a_mesh_of_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               SPARK_RAPIDS_TPU_SPARK_RAPIDS_SQL_MULTICHIP_ENABLED="true")
    r = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 28), "--seconds", "0.5", "--trace", "1",
         "--rehearse-rows", "6000"], env=env, capture_output=True, text=True,
        timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # one sharded stage a query dispatched through the engine's counter
    assert "stage dispatches a query [1]" in r.stderr
    assert not set(MESH) & set(line["metrics"])  # a CPU has no device plane


def mesh_record(k, seq, t0_ms, rows):
    ms = 1_000_000 * k
    return {"seq": seq, "status": "ok", "t0_ns": t0_ms * 1_000_000,
            "wall_ns": 498 * 1_000_000,
            "phases_ns": {"execute": 20 * ms},
            "timers_ns": {"iciExchangeTime": 2 * ms,
                          "shardDispatchTime": 3 * ms},
            "counters": {"keyed_dispatches": 10 * k, "upload_bytes": 0,
                         "shard_waves": k, "mesh_put_bytes": 4_000_000 * k},
            "mesh": {"devices": 4, "shard_rows": [rows]}}


@pytest.fixture
def traced_run(monkeypatch):
    """Two traced passes of two queries on a mesh of four."""
    ring = [mesh_record(9, i + 1, 500 * i + 1, [1, 1, 1, 1])
            for i in range(5)]
    ring += [mesh_record(k, 5 + k, 10_000 + 500 * (k - 1) + 1, rows)
             for k, rows in ((1, [10, 10, 10, 10]), (2, [12, 10, 10, 8]),
                             (3, [10, 10, 10, 10]), (4, [20, 10, 5, 5]))]
    monkeypatch.setattr(
        obs, "recent_queries",
        lambda n=None: ring if n is None else ring[len(ring) - n:],
        raising=False)
    run = harness.Run()
    run.queries_per_pass = 2
    run.pass_bytes = 4 * 819_000_000      # the mesh's HBM needs 1 ms for it
    run.peaks = {"hbm_bytes_per_s": 819e9}
    run.trace = {"passes": 2, "busy_s": 0.1, "window_s": 2.0, "programs": 8}
    run.ring = ring
    return run


def test_each_reader_on_the_recorded_queries(traced_run):
    want = {"shard_waves_per_query": 2.5, "mesh_exchange_ms": 5.0,
            "mesh_put_mb_per_query": 10.0,
            "shard_skew": (1.0 + 1.2 + 1.0 + 2.0) / 4,
            # 1 ms the least, 50 ms busy a device and pass
            "mesh_hbm_roofline": 2.0}
    assert {n: m.read(traced_run) for n, m in MESH.items()} == \
        pytest.approx(want)
    # the accepted reader divides by one chip's peak: four times as much
    from metrics import query_hbm_roofline
    assert query_hbm_roofline.read(traced_run) == pytest.approx(8.0)


def test_a_query_with_no_exchange_adds_zero(traced_run):
    for r in traced_run.ring:
        del r["timers_ns"]["iciExchangeTime"]
    assert mesh_exchange_ms.read(traced_run) == 0.0


@pytest.mark.parametrize("name", sorted(MESH))
def test_nothing_to_read_is_none(traced_run, monkeypatch, name):
    read = MESH[name].read
    assert read(traced_run) is not None
    trace = traced_run.trace
    traced_run.trace = None                      # an untraced run
    assert read(traced_run) is None
    traced_run.trace = trace
    for r in traced_run.ring:                    # a program that runs no
        del r["mesh"]                            # mesh: the one-chip cells
    assert read(traced_run) is None
    monkeypatch.setattr(obs, "recent_queries", lambda n=None: [{}] * 4)
    assert read(traced_run) is None              # records of another shape
    monkeypatch.setattr(obs, "recent_queries", lambda n=None: [])
    assert read(traced_run) is None              # obs off: an empty ring
    monkeypatch.delattr(obs, "recent_queries")   # the parent: no ring at all
    assert read(traced_run) is None
