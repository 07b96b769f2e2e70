"""Kernel cost auditor regression (analysis/kernel_audit.py).

Covers the round-14 acceptance surface: signature determinism across
thread order and cold restarts, padding-waste math at bucket
boundaries, the roofline join reconciling against attribution's
device_compute bucket (<1%, the PR 9 pattern), golden cost-signature
diffs naming the regressed dimension per query, the disabled /
steady-state paths adding zero per-dispatch audit work, and the
deterministic 2-query NDS cold prefix against the committed golden
(tier-1; the full 98-query pass is @slow and lives in
tools/audit_smoke.py for CI)."""
import importlib.util
import json
import os

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
GOLDEN_SIG = os.path.join(os.path.dirname(__file__), "golden_plans",
                          "cost_signatures.json")

_spec = importlib.util.spec_from_file_location(
    "nds_probe", os.path.join(REPO, "tools", "nds_probe.py"))
nds = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(nds)

from spark_rapids_tpu.analysis import kernel_audit as KA  # noqa: E402
from spark_rapids_tpu.expr.core import col, lit  # noqa: E402
from spark_rapids_tpu.runtime import compile_cache as CC  # noqa: E402
from spark_rapids_tpu.sql import functions as F  # noqa: E402
from spark_rapids_tpu.sql.session import TpuSession  # noqa: E402


def _table(rows=30000, seed=13):
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(0, 7, rows),
                     "v": rng.random(rows)})


def _query(sess, t, num_partitions=1):
    df = sess.create_dataframe(t, num_partitions=num_partitions)
    return (df.filter(col("v") > lit(0.3)).group_by("k")
            .agg(F.sum(col("v")).alias("s"),
                 F.count(col("v")).alias("c")))


def _audited(**conf):
    base = {"spark.rapids.obs.audit.enabled": "true"}
    base.update(conf)
    return TpuSession(base)


# ---------------------------------------------------------------------------
# padding-waste math at bucket boundaries
# ---------------------------------------------------------------------------

def test_padding_waste_math_at_bucket_boundaries():
    from spark_rapids_tpu.runtime import shapes
    for cap in (1024, 2048, 8192, 1 << 16, 1 << 20):
        assert shapes.is_bucketed(cap, 1)
        floor = KA.bucket_floor_live(cap)
        # floor is the exact bucket threshold: it maps to cap, its
        # predecessor maps below
        assert shapes.bucket_rows(floor, 1) == cap
        assert floor == 1 or shapes.bucket_rows(floor - 1, 1) < cap
        # exact boundary: a full bucket wastes nothing
        assert KA.padding_waste(cap, cap) == 0.0
        # just past the previous bucket: the ladder's worst case
        assert KA.max_padding_waste(cap) == pytest.approx(
            (cap - floor) / cap)
        assert 0.0 <= KA.max_padding_waste(cap) < 1.0
        # monotone within the bucket
        assert KA.padding_waste(floor, cap) >= \
            KA.padding_waste(cap // 2 + cap // 4, cap) >= 0.0


def test_padding_waste_off_ladder_capacity():
    from spark_rapids_tpu.runtime import shapes
    assert not shapes.is_bucketed(1000, 1)
    assert KA.bucket_floor_live(1000) is None
    assert KA.max_padding_waste(1000) == 0.0
    assert KA.max_padding_waste(0) == 0.0


def test_padding_waste_tracks_growth_factor():
    """A tighter ladder (growth 1.25) must expose LESS worst-case waste
    than the power-of-two ladder at comparable capacities."""
    from spark_rapids_tpu.runtime import shapes
    w2 = KA.max_padding_waste(1 << 16)
    try:
        shapes.configure(1.25, True)
        cap = shapes.bucket_rows(50000, 1)
        w125 = KA.max_padding_waste(cap)
    finally:
        shapes.configure(2.0, True)
    assert w125 < w2


# ---------------------------------------------------------------------------
# determinism: thread order and cold restarts
# ---------------------------------------------------------------------------

def test_signature_deterministic_across_cold_runs_and_threads():
    """Two cold audited runs of a MULTI-PARTITION query (4 task-wave
    threads racing to trace shared entries) produce identical
    signatures — the shape-complete accounting property; a second run
    also stands in for a process restart (records + cache dropped)."""
    t = _table()
    sigs = []
    for _ in range(2):
        sess = _audited()
        q = _query(sess, t, num_partitions=4)
        KA.clear_for_cold_audit()
        KA.reset_for_tests(drop_records=True)
        KA.set_enabled(True)
        q.collect()
        sig = KA.query_signature(sess.last_audit())
        assert sig, "no signature from an audited cold run"
        sigs.append(json.dumps(sig, sort_keys=True))
    assert sigs[0] == sigs[1]
    assert not KA.findings()


def test_steady_state_adds_no_audit_work():
    """Warm dispatches of audited entries never re-audit: no new
    shapes, nothing pending — the trace-time hook is structurally
    absent at steady state. Dispatch tallies still count, so the warm
    signature equals the cold one."""
    sess = _audited()
    t = _table(rows=20000, seed=5)
    q = _query(sess, t)
    KA.clear_for_cold_audit()
    q.collect()
    cold_sig = KA.query_signature(sess.last_audit())
    shapes_after_cold = KA.stats()["shapes"]
    q.collect()
    assert KA.stats()["shapes"] == shapes_after_cold
    assert KA.stats()["pending"] == 0
    warm_sig = KA.query_signature(sess.last_audit())
    assert warm_sig == cold_sig
    assert not KA.findings()


def test_disabled_path_zero_per_dispatch_work():
    """Audit off: compile_cache carries no auditor (get() pays one
    module-global None check), no records accrue, no audit/roofline
    docs exist."""
    sess = TpuSession()
    before = KA.stats()["shapes"]
    _query(sess, _table(rows=8000, seed=3)).collect()
    assert CC._AUDITOR is None
    assert KA.stats()["shapes"] == before
    assert KA.stats()["pending"] == 0
    assert sess.last_audit() is None
    assert sess.last_roofline() is None


def test_warm_unaudited_entry_is_a_finding():
    """Entries traced BEFORE the audit armed are flagged when an
    audited query dispatches them: incomplete accounting must be loud
    (the golden generator aborts on it), never silent."""
    t = _table(rows=9000, seed=9)
    cold = TpuSession()  # audit off: traces land unaudited
    _query(cold, t).collect()
    warm = _audited()
    _query(warm, t).collect()  # same keys -> warm hits, no records
    assert any("unaudited entry" in f for f in KA.findings())


# ---------------------------------------------------------------------------
# the roofline join + surfaces
# ---------------------------------------------------------------------------

def test_roofline_reconciles_and_reaches_every_surface(tmp_path):
    """The roofline's device_compute seconds must reconcile with the
    attribution bucket within 1% (same classification + compile
    cascade by construction); the doc reaches explain(mode="analyze"),
    the history record, the rapids_roofline_* gauges, and the console
    state — one audited collect serves all assertions (tier-1 wall
    time is tight; every cold audited session costs seconds)."""
    sess = _audited(**{"spark.rapids.obs.historyDir": str(tmp_path)})
    q = _query(sess, _table(rows=40000, seed=21))
    KA.clear_for_cold_audit()
    q.collect()
    roof = sess.last_roofline()
    attr = sess.last_attribution()
    assert roof and attr
    dev = roof["groups"]["device_compute"]["seconds"]
    a_dev = (attr["buckets"]["device_compute"]
             * attr.get("concurrency_factor", 1.0))
    assert abs(dev - a_dev) <= 0.01 * max(dev, a_dev, 1e-9)
    assert roof["groups"]["device_compute"]["bound"] in (
        "memory", "compute", "dispatch_overhead")
    text = sess.explain_analyze()
    assert "-- roofline (audit" in text
    assert "device_compute" in text
    # history carries the full doc
    from spark_rapids_tpu.runtime import obs
    recs = obs.state().history.read_all()
    assert recs and recs[-1].get("roofline")
    assert recs[-1]["roofline"]["groups"]["device_compute"][
        "achieved_gbps"] == roof["groups"]["device_compute"][
        "achieved_gbps"]
    # /metrics gauges + the console's last-roofline state
    st = obs.state()
    prom = st.registry.render_prometheus()
    assert "rapids_roofline_achieved_gbps" in prom
    assert 'rapids_roofline_pct{group="total"}' in prom
    assert st.last_roofline is not None


def test_module_kernel_audited_via_jit_wrapper():
    """compile_cache.jit kernels audit at trace time too (the armed
    check rides inside the traced body, so decoration-at-import still
    works), keyed kernel:<module>.<qualname>."""
    import jax.numpy as jnp
    KA.set_enabled(True)

    @CC.jit(static_argnums=(1,))
    def _smoke_kernel(x, n):
        return jnp.zeros((n,), x.dtype) + x.sum()

    _smoke_kernel(jnp.arange(2048.0), 8)
    KA.resolve_pending()
    fams = [r["family"] for r in KA.records_doc()]
    mine = [f for f in fams if f.startswith("kernel:") and
            "_smoke_kernel" in f]
    assert mine, fams
    rec = [r for r in KA.records_doc() if r["family"] == mine[0]][0]
    assert rec["flops"] is not None and rec["bytes_accessed"] > 0


def test_compare_signature_names_the_dimension():
    golden = {"fused_stage": {"dispatches": 4, "entries": 1, "shapes": 2,
                              "flops": 1000, "bytes_accessed": 5000,
                              "in_bytes": 100, "out_bytes": 50},
              "gone": {"dispatches": 1, "entries": 1, "shapes": 1,
                       "flops": 1, "bytes_accessed": 1, "in_bytes": 1,
                       "out_bytes": 1}}
    got = {"fused_stage": dict(golden["fused_stage"],
                               bytes_accessed=10000, dispatches=6),
           "novel": {"dispatches": 1, "entries": 1, "shapes": 1,
                     "flops": 1, "bytes_accessed": 1, "in_bytes": 1,
                     "out_bytes": 1}}
    diffs = KA.compare_signature("q7", golden, got)
    assert any("q7: fused_stage bytes_accessed regressed 5000 -> 10000"
               in d for d in diffs)
    assert any("q7: fused_stage dispatches regressed 4 -> 6" in d
               for d in diffs)
    assert any("vanished" in d and "gone" in d for d in diffs)
    assert any("new kernel class" in d and "novel" in d for d in diffs)
    # tolerance admits float-dimension drift but never count drift
    tol = KA.compare_signature("q7", golden, got, rel_tol=2.0)
    assert not any("bytes_accessed regressed" in d for d in tol)
    assert any("dispatches regressed" in d for d in tol)


# ---------------------------------------------------------------------------
# golden cost signatures: the deterministic NDS cold prefix
# ---------------------------------------------------------------------------

def _golden_doc():
    assert os.path.exists(GOLDEN_SIG), \
        "regenerate: python tools/gen_dispatch_budgets.py"
    with open(GOLDEN_SIG) as f:
        return json.load(f)


def _replay_prefix(count):
    """The generator's exact cost-pass recipe (fresh session + tables,
    cold cache, sorted order) over the first `count` queries."""
    doc = _golden_doc()
    assert doc["_sf"] == 0.002 and doc["_seed"] == 7
    sess = _audited()
    tables = nds.gen_tables(0.002, seed=7)
    d = {name: sess.create_dataframe(t).cache()
         for name, t in tables.items()}
    KA.clear_for_cold_audit()
    problems = []
    for i, qn in enumerate(sorted(nds.QUERIES)[:count]):
        if i and i % doc["_release_every"] == 0:
            import jax
            jax.clear_caches()  # the generator's release points
        nds.QUERIES[qn](sess, d).collect()
        sig = KA.query_signature(sess.last_audit())
        problems += KA.compare_signature(
            f"q{qn}", doc["cost_signatures"][str(qn)], sig)
    problems += [f"finding: {f}" for f in KA.findings()]
    return doc, problems


@pytest.mark.parametrize(
    "prefix", [1, pytest.param(2, marks=pytest.mark.slow)])
def test_golden_cost_signature_cold_prefix(prefix):
    """Tier-1's deterministic cold prefix: replay the golden recipe
    for the first sorted NDS query (the 2-query prefix re-homed to
    @slow in the round-18 headroom squeeze — ci_check runs it via
    tools/slow_rehomed.txt) and diff its cost signature against the
    committed pin. A kernel that silently starts moving 2x the bytes
    fails HERE with the dimension named — the full 98-query pass lives
    in tools/audit_smoke.py (CI) and the @slow test below. Regenerate
    after intended kernel/plan changes: python tools/gen_dispatch_budgets.py"""
    doc, problems = _replay_prefix(prefix)
    assert not problems, "\n".join(problems)
    assert doc["kernel_primitives"] == sorted(KA.KERNEL_PRIMITIVES), \
        "KERNEL_PRIMITIVES roster drifted — regenerate the goldens"


@pytest.mark.slow
def test_golden_cost_signatures_full():
    """The full audited NDS pass (~340-490s) against every committed
    signature — CI runs the equivalent via tools/audit_smoke.py."""
    doc, problems = _replay_prefix(len(nds.QUERIES))
    assert not problems, "\n".join(problems[:50])
