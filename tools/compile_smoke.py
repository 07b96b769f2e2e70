"""Compile-cache smoke: the compile tax must actually die, for free.

Three CI gates over the ISSUE-10 subsystem (runtime/shapes.py +
runtime/compile_cache.py + runtime/warmup.py):

Gate 1 (steady-state overhead, the trace_overhead bar): the warm-hit
path of the sanctioned compile choke point — what every fused dispatch
now passes through instead of a bare dict probe — must add under
--tolerance (2%) to a representative query drive. Same methodology as
tools/sanitizer_smoke.py: count choke-point passes in one drive, measure
the per-pass delta versus the pre-change equivalent (a plain dict.get)
over tight-loop iterations, multiply.

Gate 2 (cross-process persistent cache): a SECOND process running the
same queries against the same spark.rapids.compile.cacheDir must record
persistent-cache HITS (jax.monitoring's cache_hits events, surfaced in
compile_cache.stats) and spend measurably less backend-compile time than
the first. This is the conf actually working, not just being set.

Gate 3 (warm-history AOT warmup, the ROADMAP item 4 acceptance bar): on
a history warmed by a prior process (two runs of each probe query, SQL
recorded), a fresh process with spark.rapids.compile.warmup.enabled must
replay the hot set at table-registration time and then serve the user's
first run of those queries with an attribution `compile` bucket total at
least --min-drop (5x) below the cold process's first-run total — the
exact compile_seconds methodology tools/nds_probe.py scorecards use,
driven over probe-shaped join/agg/window SQL.

Run:  python tools/compile_smoke.py [--tolerance 0.02] [--min-drop 5]
Internal: --worker cold|warm --dir D (subprocess modes).

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: probe-shaped SQL (join+agg, filter+groupby, windowed rank): SQL-born
#: plans record their text in history, which is what warmup replays
QUERIES = (
    "SELECT d.grp, SUM(f.price * (1.0 - f.disc)) AS rev "
    "FROM fact f JOIN dim d ON f.key = d.key "
    "WHERE f.qty < 40 GROUP BY d.grp",
    "SELECT f.qty AS b, SUM(f.price) AS p, COUNT(*) AS c "
    "FROM fact f WHERE f.price > 10.0 GROUP BY f.qty",
    "SELECT grp, MAX(r) AS mr FROM (SELECT d.grp AS grp, RANK() OVER "
    "(PARTITION BY d.grp ORDER BY f.price) AS r FROM fact f "
    "JOIN dim d ON f.key = d.key) t GROUP BY grp",
)


def _make_data(d: str) -> None:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(20260804)
    n, k = 60_000, 500
    pq.write_table(pa.table({
        "key": rng.integers(0, k, n).astype(np.int64),
        "qty": rng.integers(1, 50, n).astype(np.int64),
        "price": np.round(rng.uniform(1.0, 100.0, n), 2),
        "disc": np.round(rng.uniform(0.0, 0.1, n), 2),
    }), os.path.join(d, "fact.parquet"))
    pq.write_table(pa.table({
        "key": np.arange(k, dtype=np.int64),
        "grp": rng.integers(0, 8, k).astype(np.int64),
    }), os.path.join(d, "dim.parquet"))


def _session(d: str, warmup_on: bool):
    from spark_rapids_tpu.sql.session import TpuSession
    conf = {
        "spark.rapids.obs.historyDir": os.path.join(d, "hist"),
        "spark.rapids.compile.cacheDir": os.path.join(d, "xla_cache"),
    }
    if warmup_on:
        conf["spark.rapids.compile.warmup.enabled"] = "true"
    return TpuSession(conf)


def _register(sess, d: str) -> None:
    sess.create_or_replace_temp_view(
        "fact", sess.read_parquet(os.path.join(d, "fact.parquet")))
    sess.create_or_replace_temp_view(
        "dim", sess.read_parquet(os.path.join(d, "dim.parquet")))


def _attr_compile(sess) -> float:
    attr = sess.last_attribution()
    return float(attr["buckets"]["compile"]) if attr else 0.0


def worker_cold(d: str) -> dict:
    """First process: seed history (two runs per query — recurrence for
    warmup) and the persistent cache; report first-run compile totals
    and the in-process determinism check (second runs build nothing)."""
    from spark_rapids_tpu.runtime import compile_cache as CC
    sess = _session(d, warmup_on=False)
    _register(sess, d)
    first_compile = 0.0
    second_misses = 0
    for q in QUERIES:
        sess.sql(q).collect()
        first_compile += _attr_compile(sess)
        before = CC.stats()["misses"]
        sess.sql(q).collect()
        second_misses += CC.stats()["misses"] - before
    s = CC.stats()
    return {"first_compile_seconds": first_compile,
            "second_run_new_misses": second_misses,
            "xla_compile_ns": s["xla_compile_ns"],
            "persistent_hits": s["persistent_hits"],
            "persistent_misses": s["persistent_misses"]}


def worker_warm(d: str) -> dict:
    """Second process: same cache dir + warm history + AOT warmup. The
    user-visible first run of each query is measured AFTER warmup
    drains."""
    from spark_rapids_tpu.runtime import compile_cache as CC
    from spark_rapids_tpu.runtime import warmup as WU
    sess = _session(d, warmup_on=True)
    mgr = WU.manager()
    armed = mgr is not None and mgr.doc()["plans"] > 0
    _register(sess, d)
    drained = mgr.wait(180) if mgr is not None else False
    warm_doc = mgr.doc() if mgr is not None else None
    user_compile = 0.0
    user_misses = 0
    before = CC.stats()["misses"]
    for q in QUERIES:
        sess.sql(q).collect()
        user_compile += _attr_compile(sess)
    user_misses = CC.stats()["misses"] - before
    s = CC.stats()
    return {"armed": armed, "drained": drained, "warmup": warm_doc,
            "user_compile_seconds": user_compile,
            "user_new_misses": user_misses,
            "xla_compile_ns": s["xla_compile_ns"],
            "persistent_hits": s["persistent_hits"],
            "persistent_misses": s["persistent_misses"]}


# ---------------------------------------------------------------------------
# Gate 1: steady-state choke-point overhead
# ---------------------------------------------------------------------------

def overhead_gate(d: str, tolerance: float) -> dict:
    """Count warm choke-point passes in one query drive, measure the
    per-pass cost delta vs a plain dict probe (the pre-change fused()
    body) over tight loops, and bound count x delta against the drive
    wall (the sanitizer_smoke methodology — an A/B wall-clock diff
    would drown in shared-CI noise)."""
    from spark_rapids_tpu.runtime import compile_cache as CC
    sess = _session(d, warmup_on=False)
    _register(sess, d)
    dfs = [sess.sql(q) for q in QUERIES]
    for df in dfs:
        df.collect()  # warm every entry so the drive is all hits

    passes = [0]
    real_get = CC.get

    def counting_get(exec_class, key, builder):
        passes[0] += 1
        return real_get(exec_class, key, builder)

    CC.get = counting_get
    try:
        t0 = time.perf_counter()
        for df in dfs:
            df.collect()
        drive_s = time.perf_counter() - t0
    finally:
        CC.get = real_get

    # per-pass: the warm CC.get path vs the pre-change equivalent
    # (one dict.get on a tuple key)
    key = ("smoke", ("k", 1, 2), ())
    CC.get("smoke", ("k", 1, 2), lambda: (lambda: None))
    baseline_cache = {(("smoke", ("k", 1, 2), ())): lambda: None}
    n = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        CC.get("smoke", ("k", 1, 2), None)
    per_new = (time.perf_counter_ns() - t0) / n
    t0 = time.perf_counter_ns()
    for _ in range(n):
        baseline_cache.get(key)
    per_old = (time.perf_counter_ns() - t0) / n
    delta_ns = max(per_new - per_old, 0.0)
    overhead = passes[0] * delta_ns / (drive_s * 1e9)
    return {"passes": passes[0], "per_pass_ns": round(per_new, 1),
            "delta_ns": round(delta_ns, 1),
            "drive_s": round(drive_s, 3),
            "overhead_fraction": overhead,
            "ok": overhead < tolerance}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _run_worker(mode: str, d: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", mode,
         "--dir", d],
        capture_output=True, text=True, timeout=600, env=env)
    if out.returncode != 0:
        print(out.stdout)
        print(out.stderr, file=sys.stderr)
        raise SystemExit(f"compile_smoke {mode} worker failed")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance", type=float, default=0.02)
    ap.add_argument("--min-drop", type=float, default=5.0)
    ap.add_argument("--worker", choices=("cold", "warm"))
    ap.add_argument("--dir")
    args = ap.parse_args()

    if args.worker:
        fn = worker_cold if args.worker == "cold" else worker_warm
        print(json.dumps(fn(args.dir)))
        return 0

    import tempfile
    fails = []
    with tempfile.TemporaryDirectory(prefix="compile_smoke_") as d:
        _make_data(d)

        print("[gate 2+3] cold process (seeds history + persistent "
              "cache)...", flush=True)
        cold = _run_worker("cold", d)
        print(f"  cold: first-run compile {cold['first_compile_seconds']:.3f}s, "
              f"second-run new misses {cold['second_run_new_misses']}, "
              f"persistent misses {cold['persistent_misses']}")
        if cold["second_run_new_misses"] != 0:
            fails.append("cold process second runs built new entries "
                         "(warm-trace cache not deterministic)")
        if cold["persistent_misses"] == 0:
            fails.append("cold process recorded no persistent-cache "
                         "traffic (cacheDir conf not applied?)")

        print("[gate 2+3] warm process (persistent hits + AOT warmup)...",
              flush=True)
        warm = _run_worker("warm", d)
        print(f"  warm: armed={warm['armed']} drained={warm['drained']} "
              f"warmup={warm['warmup']}")
        print(f"  warm: user first-run compile "
              f"{warm['user_compile_seconds']:.3f}s, new misses "
              f"{warm['user_new_misses']}, persistent hits "
              f"{warm['persistent_hits']}")
        if not warm["armed"]:
            fails.append("warmup never armed from the warm history")
        if not warm["drained"]:
            fails.append("warmup did not drain within the deadline")
        if (warm["warmup"] or {}).get("replayed", 0) < len(QUERIES):
            fails.append("warmup replayed fewer plans than recorded")
        if warm["persistent_hits"] == 0:
            fails.append("no cross-process persistent-cache hits")
        if warm["user_new_misses"] != 0:
            fails.append("user queries after warmup still built entries")
        drop = cold["first_compile_seconds"] / max(
            warm["user_compile_seconds"], 1e-3)
        print(f"  compile_seconds drop: {drop:.1f}x "
              f"(gate >= {args.min_drop}x)")
        if drop < args.min_drop:
            fails.append(
                f"warm-history compile_seconds dropped only {drop:.1f}x")

        print("[gate 1] steady-state choke-point overhead...", flush=True)
        oh = overhead_gate(d, args.tolerance)
        print(f"  {oh['passes']} passes x {oh['delta_ns']}ns delta over "
              f"{oh['drive_s']}s drive -> "
              f"{oh['overhead_fraction'] * 100:.3f}% "
              f"(gate < {args.tolerance * 100:.0f}%)")
        if not oh["ok"]:
            fails.append("steady-state choke-point overhead over budget")

    if fails:
        print("compile_smoke: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    print("compile_smoke: PASS")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
