"""chip_smoke.py rehearsed on the CPU simulator: every section of the
on-chip smoke runs in-process at a few thousand rows (Pallas interpreted,
the multi-chip section on the 8 virtual devices), and without
--allow-cpu a CPU backend is a failure that prints no result."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def fresh_obs():
    """The served section binds the obs endpoint; give it a fresh obs
    singleton and free the port afterwards (tests/test_serving.py)."""
    from spark_rapids_tpu.runtime import obs
    obs.shutdown_for_tests()
    yield
    obs.shutdown_for_tests()


def test_all_sections_pass_on_cpu_rehearsal(fresh_obs, capsys):
    rc = chip_smoke.main(["--allow-cpu", "--rows", "4000"])
    out = capsys.readouterr().out.strip().splitlines()
    # two lines: the full report, then the verdict with exactly the keys
    # the chip check reads
    report, verdict = (json.loads(line) for line in out[-2:])
    assert rc == 0 and report["ok"], report["failures"]
    assert verdict == {"ok": True, "device": report["device"]}
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert isinstance(verdict["device"]["count"], int)
    assert report["device"]["platform"] == "cpu"
    assert report["rehearsal_on_cpu"] and report["kernels"]["interpret"]
    assert {n: s["status"] for n, s in report["sections"].items()} == {
        n: "ok" for n in chip_smoke.SECTIONS}  # 8 virtual devices
    for name in chip_smoke.RESIDENT:
        rec = report["resident"][name]
        assert rec["ok"] and rec["n"] == chip_smoke.WARM_REPS
    # device decode took q6's four numeric columns; q1's two string
    # columns are the expected per-column host fallback
    assert report["scan"]["q6"]["decode_fallback_columns"] == 0
    assert report["scan"]["q6"]["encoded_bytes"] > 0
    assert report["scan"]["q1"]["decode_fallback_columns"] == 2
    for name in chip_smoke.SERVED_SQL:
        assert report["served"][name]["http"] == [200] * 3
    assert all(v["ok"] for k, v in report["kernels"].items()
               if k != "interpret")
    mc = report["multichip"]
    assert mc["ran"] and mc["narrow"]["sharded_in_plan"]
    assert mc["narrow"]["shard_waves"] >= 1
    assert mc["q72shfl"]["ici_exchange_ns"] > 0
    assert mc["q72shfl"]["equals_one_chip"]
    assert mc["narrow"]["equals_one_chip"]
    # the data directory is generated and removed by the run
    assert not os.path.exists(chip_smoke.DATA_DIR)


def test_cpu_backend_without_the_flag_fails_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_a_stage_fallback_fails_the_query_check(fresh_obs):
    """A fused stage that fell back to its replay path still returns the
    right rows; the smoke's hidden-state check must fail on it."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.exec import stage_fusion
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.runtime import obs
    from spark_rapids_tpu.sql.session import TpuSession
    sess = TpuSession()
    df = sess.create_dataframe(pa.table({"v": np.arange(4096)}))
    q = df.filter(col("v") % lit(7) == lit(3)).select(
        (col("v") * lit(5)).alias("w"))
    smoke = chip_smoke.Smoke(None)
    before = obs.exec_fallbacks()
    assert q.collect().num_rows == 585
    assert smoke._hidden(sess, before) == []

    orig = stage_fusion.fuse.fused

    def refuse(key, builder):
        if key and key[0] == "fused_stage":
            def boom(*a, **k):
                raise RuntimeError("injected compile refusal")
            return boom
        return orig(key, builder)

    stage_fusion.fuse.fused = refuse
    try:
        before = obs.exec_fallbacks()
        assert q.collect().num_rows == 585  # the replay path is correct
    finally:
        stage_fusion.fuse.fused = orig
    bad = smoke._hidden(sess, before)
    assert any("_failed" in b for b in bad), bad
    assert any("rapids_stage_fallbacks_total" in b for b in bad), bad


def test_importing_the_package_initialises_no_backend():
    """One process per chip: a parent that only imports the package
    (nds_probe's per-query driver, the pyworker pool's children) must
    not take the device."""
    code = ("import spark_rapids_tpu, spark_rapids_tpu.sql.session\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
