"""NDS harness regression: every translated query runs, matches the CPU
interpreter, and plans without device fallback (tiny SF on the CPU sim)."""
import importlib.util
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "nds_probe", os.path.join(os.path.dirname(__file__), "..", "tools",
                              "nds_probe.py"))
nds = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(nds)

from spark_rapids_tpu.sql.session import TpuSession  # noqa: E402


@pytest.fixture(scope="module")
def dfs():
    sess = TpuSession()
    tables = nds.gen_tables(0.002, seed=7)
    out = {name: sess.create_dataframe(t).cache()
           for name, t in tables.items()}
    return sess, out


# The 98-query sweep is the suite's single heaviest parametrization (~7-8min
# on the CPU sim). Tier-1 keeps the probe anchors q1/q3/q6/q67/q72;
# the every-7th spread joined them until the round-18 headroom squeeze and
# now rides tools/slow_rehomed.txt (ci_check runs it), with the full sweep
# under @slow and audit_smoke's golden cost-signature replay in ci_check
# still executing all 98 against byte-identical goldens.
_ALL_QN = sorted(nds.QUERIES)
_TIER1_QN = {1, 3, 6, 67, 72} & set(_ALL_QN)


@pytest.mark.parametrize(
    "qn", [q if q in _TIER1_QN else pytest.param(q, marks=pytest.mark.slow)
           for q in _ALL_QN])
def test_nds_query(dfs, qn):
    sess, d = dfs
    df = nds.QUERIES[qn](sess, d)
    explain = df.explain()
    assert "cannot run on TPU" not in explain, explain
    assert nds._canon_rows(df.collect()) == \
        nds._canon_rows(df.collect_cpu())
