"""Multi-chip sharded execution (round 19): mesh validation, the
compile-cache mesh fingerprint, planner eligibility, sharded-vs-single
parity (masked rows + ANSI corners), shard-skew observability, and the
failure paths (trace-failure fallback, retry-on-OOM, cancellation).

The suite conftest forces 8 virtual CPU devices for every test process,
so these drive the REAL shard_map / all_to_all path in-process. The
heavier end-to-end gates live in tools/multichip_smoke.py (ci_check).
"""
import numpy as np
import pytest

import jax

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.parallel import mesh as MESH
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession


def _sorted(tbl):
    return tbl.sort_by([(c, "ascending") for c in tbl.column_names])


def _data(rows=4000):
    # v carries nulls so the sharded path exercises masked planes
    return {"g": [i % 23 for i in range(rows)],
            "v": [i if i % 7 else None for i in range(rows)],
            "d": [float(i % 13) * 0.5 for i in range(rows)]}


def _narrow(s, data):
    return (s.create_dataframe(data, num_partitions=8)
            .filter(col("v") % lit(5) != lit(0))
            .select(col("g"), (col("v") * lit(3)).alias("v3"),
                    (col("d") * lit(2.0)).alias("d2")))


# -- mesh construction / validation -----------------------------------------

def test_make_mesh_validates_axis_names():
    with pytest.raises(ValueError):
        MESH.make_mesh(1, axis_names=())
    with pytest.raises(ValueError):
        MESH.make_mesh(1, axis_names=("part", "part"))
    with pytest.raises(ValueError):
        MESH.make_mesh(1, axis_names=("part", 7))


def test_make_mesh_rejects_oversubscription_and_bad_dp():
    with pytest.raises(ValueError):
        MESH.make_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        MESH.make_mesh(4, dp=3)  # dp must divide n_devices
    with pytest.raises(ValueError):
        MESH.make_mesh(4, dp=2, axis_names=("part",))


def test_check_mesh_devices_raises_typed_error_on_stale(monkeypatch):
    mesh = MESH.make_mesh(2, axis_names=(MESH.PART_AXIS,))
    MESH.check_mesh_devices(mesh)  # live mesh passes
    # simulate a backend restart: device 0 leaves jax.devices()
    live = jax.devices()
    monkeypatch.setattr(MESH.jax, "devices", lambda *a: live[1:])
    with pytest.raises(MESH.MeshDeviceError):
        MESH.check_mesh_devices(mesh)


def test_multichip_devices_clamps():
    s_all = TpuSession({C.MULTICHIP_ENABLED.key: "true"})
    assert MESH.multichip_devices(s_all.conf) == len(jax.devices())
    s_big = TpuSession({C.MULTICHIP_ENABLED.key: "true",
                        C.MULTICHIP_DEVICES.key: 10_000})
    assert MESH.multichip_devices(s_big.conf) == len(jax.devices())
    s_two = TpuSession({C.MULTICHIP_ENABLED.key: "true",
                        C.MULTICHIP_DEVICES.key: 2})
    assert MESH.multichip_devices(s_two.conf) == 2


# -- compile-cache fingerprint isolation ------------------------------------

def test_compile_fingerprint_isolates_mesh_shape():
    from spark_rapids_tpu.runtime.compile_cache import _fp_of
    off = TpuSession({}).conf
    on2 = TpuSession({C.MULTICHIP_ENABLED.key: "true",
                      C.MULTICHIP_DEVICES.key: 2}).conf
    on8 = TpuSession({C.MULTICHIP_ENABLED.key: "true",
                      C.MULTICHIP_DEVICES.key: 8}).conf
    assert _fp_of(on2) != _fp_of(on8)
    # disabled conf keeps the pre-multichip fingerprint: no mesh component
    assert not any("mesh" in str(part) for part in _fp_of(off))
    assert _fp_of(off) != _fp_of(on8)


# -- planner eligibility ----------------------------------------------------

def test_planner_shards_narrow_chain():
    s = TpuSession({C.MULTICHIP_ENABLED.key: "true"})
    out = _narrow(s, _data(2000)).collect()
    assert out.num_rows > 0
    assert "ShardedStageExec" in s._last_exec.tree_string()
    snaps = s.last_metrics()
    assert sum(v.get("shardWaves", 0) for v in snaps.values()) >= 1


def test_fallback_reasons_cover_wide_types_and_carry():
    from spark_rapids_tpu.exec import sharded as SH

    class _Field:
        def __init__(self, name, dtype):
            self.name, self.dtype = name, dtype

    class _Schema:
        def __init__(self, fields):
            self.fields = fields

    class _Body:
        has_carry = False
        exhausts = False
        name = "project"
        key = ("stage",)

    class _Child:
        schema = _Schema([_Field("s", T.StringType())])

    class _Node:
        bodies = [_Body()]
        members = []
        children = [_Child()]

    reason = SH._fallback_reason(_Node())
    assert reason is not None and "StringType" in reason

    class _CarryBody(_Body):
        has_carry = True
        name = "limit"

    class _CarryNode(_Node):
        bodies = [_CarryBody()]

    reason = SH._fallback_reason(_CarryNode())
    assert reason is not None and "loop state" in reason

    class _IntChild:
        schema = _Schema([_Field("v", T.Int64Type())])

    class _OkNode(_Node):
        children = [_IntChild()]

    assert SH._fallback_reason(_OkNode()) is None


# -- parity: sharded results byte-identical to single-device ----------------

@pytest.mark.parametrize("ansi", ["false", "true"])
def test_sharded_parity_masked_and_ansi(ansi):
    data = _data(3000)
    outs = {}
    for flag in ("true", "false"):
        s = TpuSession({C.MULTICHIP_ENABLED.key: flag,
                        C.ANSI_ENABLED.key: ansi})
        outs[flag] = _sorted(_narrow(s, data).collect())
        engaged = "ShardedStageExec" in s._last_exec.tree_string()
        assert engaged == (flag == "true")
    assert outs["true"].equals(outs["false"])


def test_shuffle_agg_parity_and_ici_metric():
    data = _data(3000)
    outs = {}
    for flag in ("true", "false"):
        s = TpuSession({C.MULTICHIP_ENABLED.key: flag})
        df = (s.create_dataframe(data, num_partitions=8)
              .group_by(col("g")).agg(F.sum("v").alias("sv"),
                                      F.count().alias("n")))
        outs[flag] = _sorted(df.collect())
        ici = sum(v.get("iciExchangeTime", 0)
                  for v in s.last_metrics().values())
        assert (ici > 0) == (flag == "true")
    assert outs["true"].equals(outs["false"])


# -- shard-skew observability -----------------------------------------------

def test_resolve_shards_folds_skew():
    from spark_rapids_tpu.analysis.kernel_audit import _resolve_shards
    doc = _resolve_shards([(4, np.array([100, 300, 100, 100])),
                           (4, np.array([100, 100, 100, 100]))])
    assert doc["n_shards"] == 4
    assert doc["waves"] == 2
    assert doc["rows_per_shard"] == [200, 400, 200, 200]
    assert doc["skew"] == 1.6  # 400 / mean(250)
    assert _resolve_shards([]) is None


def test_roofline_reports_seeded_skew():
    s = TpuSession({C.MULTICHIP_ENABLED.key: "true",
                    C.OBS_AUDIT_ENABLED.key: "true"})
    rows = 4000
    # round-robin partitioning + a v-range filter concentrates the
    # surviving rows in a value band, not a partition: instead seed skew
    # through filter selectivity that differs across the g stripes the
    # 8 partitions receive
    data = {"g": [i % 8 for i in range(rows)],
            "v": list(range(rows))}
    df = (s.create_dataframe(data, num_partitions=8)
          .filter(col("v") % lit(8) == lit(0))
          .select(col("g"), (col("v") + lit(1)).alias("v1")))
    df.collect()
    roof = s.last_roofline()
    shards = (roof or {}).get("shards")
    assert shards is not None
    assert shards["n_shards"] == 8
    assert shards["waves"] >= 1
    assert len(shards["rows_per_shard"]) == 8
    assert shards["skew"] >= 1.0


# -- failure paths ----------------------------------------------------------

def test_trace_failure_falls_back_to_single_device(monkeypatch):
    from spark_rapids_tpu.exec import fuse
    data = _data(2000)
    expect = _sorted(_narrow(TpuSession({}), data).collect())

    orig = fuse.fused

    def boom(key, builder):
        if key and key[0] == "sharded_stage":
            raise RuntimeError("synthetic shard_map trace failure")
        return orig(key, builder)

    monkeypatch.setattr(fuse, "fused", boom)
    s = TpuSession({C.MULTICHIP_ENABLED.key: "true"})
    got = _sorted(_narrow(s, data).collect())
    assert got.equals(expect)  # per-slot replay through the fused path


def test_wave_retry_on_injected_oom():
    from spark_rapids_tpu.runtime.retry import OomInjector, set_backoff
    data = _data(2000)
    expect = _sorted(_narrow(TpuSession({}), data).collect())
    s = TpuSession({C.MULTICHIP_ENABLED.key: "true"})
    set_backoff(0.0, 0.0)
    OomInjector.configure(num_ooms=1)
    try:
        got = _sorted(_narrow(s, data).collect())
    finally:
        OomInjector.configure(num_ooms=0)
    assert got.equals(expect)
    assert "ShardedStageExec" in s._last_exec.tree_string()


def test_cancellation_not_swallowed_by_fallback(monkeypatch):
    from spark_rapids_tpu.exec import fuse
    from spark_rapids_tpu.runtime.lifecycle import QueryCancelledError

    orig = fuse.fused

    def cancelled(key, builder):
        if key and key[0] == "sharded_stage":
            raise QueryCancelledError("cancelled by user")
        return orig(key, builder)

    monkeypatch.setattr(fuse, "fused", cancelled)
    s = TpuSession({C.MULTICHIP_ENABLED.key: "true"})
    with pytest.raises(QueryCancelledError):
        _narrow(s, _data(1000)).collect()
