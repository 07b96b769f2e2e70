"""Serving smoke (round 17): the CI gate for the query-server layer.

1. Disabled-path overhead: with serving.enabled OFF (the default) the
   only new site an ordinary workload executes is the one
   serving.maybe_install read at session construction. Count x delta
   methodology (tools/aqe_smoke.py): count the site's firings during a
   drive, measure its per-call cost in a tight loop, bound the product
   under --tolerance (2%) of the drive. Runs FIRST, before this process
   installs any server.
2. Concurrency parity: N=4 concurrent clients hammering POST /sql over
   a real HTTP endpoint (cache hits, misses, single-flight collisions
   and forced re-executions) must each receive results byte-identical
   to the solo run of the same query.
3. Seeded admission + cancel: with maxInflight saturated by two slow
   queries (scan-delay faults on an overlay session) a third request is
   refused with HTTP 429 and a typed doc; POST /queries/<id>/cancel
   lands both slow requests as HTTP 499 cancelled within the checkpoint
   bound.
4. Replica warm-boot (subprocess): a fresh process sharing the seed
   process's historyDir + persistent compile cacheDir serves its FIRST
   hot-digest request with ZERO backend compiles (the response doc's
   xla_compiles delta and rapids_xla_compiles_total both flat) and
   byte-identical to the seed's result.

Usage: python tools/serving_smoke.py [--clients 4] [--tolerance 0.02]
Internal: --worker seed|replica --dir D (subprocess modes).

CPU gate: runs on the CPU backend (JAX_PLATFORMS defaults to cpu here);
no time it prints is a measurement of the chip.
"""
from __future__ import annotations

import argparse
import base64
import http.client
import json
import os
import socket
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

QUERIES = {
    "agg": "SELECT k, SUM(v) AS sv, COUNT(*) AS n FROM t GROUP BY k",
    "filter": "SELECT k, v FROM t WHERE v > 700",
    "proj": "SELECT k, v * 2 AS v2 FROM t WHERE k < 5",
}

#: the warm-boot hot query (seed records it twice -> warmup replays it)
HOT_SQL = ("SELECT d.grp, SUM(f.price) AS rev FROM fact f "
           "JOIN dim d ON f.key = d.key GROUP BY d.grp")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port: int, path: str, payload: dict, timeout: float = 120.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(payload).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _get(port: int, path: str):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _probe_table(n=40_000, seed=17):
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(0, 12, n),
                     "v": rng.integers(1, 1000, n)})


# ---------------------------------------------------------------------------
# gate 1: disabled-path overhead (count x delta) — MUST run before any
# serving-enabled session exists in this process
# ---------------------------------------------------------------------------

def disabled_overhead(reps: int) -> dict:
    from spark_rapids_tpu.runtime import serving
    from spark_rapids_tpu.sql.session import TpuSession
    assert not serving.installed(), \
        "gate 1 must run before a server is installed"

    t = _probe_table(20_000)

    def drive():
        sess = TpuSession()
        sess.create_or_replace_temp_view("t", sess.create_dataframe(t))
        sess.sql(QUERIES["agg"]).collect()
        return sess

    sess = drive()  # warm the trace cache out of the timed drives

    counts = [0]
    real_install = serving.maybe_install

    def counting_install(s):
        counts[0] += 1
        return real_install(s)

    serving.maybe_install = counting_install
    try:
        drive()
    finally:
        serving.maybe_install = real_install

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        drive()
        best = min(best, time.perf_counter() - t0)

    iters = 100_000
    t0 = time.perf_counter()
    for _ in range(iters):
        serving.maybe_install(sess)
    per_call = (time.perf_counter() - t0) / iters

    added = counts[0] * per_call
    return {"install_reads": counts[0],
            "per_call_ns": round(per_call * 1e9, 1),
            "drive_best_s": round(best, 6),
            "disabled_overhead_pct": round(added / best * 100, 5)}


# ---------------------------------------------------------------------------
# gates 2+3: concurrency parity, admission rejection, HTTP cancel
# ---------------------------------------------------------------------------

def concurrency_parity(port: int, clients: int, result: dict) -> list:
    fails = []
    solo = {}
    for name, sql in QUERIES.items():
        code, doc = _post(port, "/sql", {"sql": sql})
        if code != 200:
            return [f"solo {name} returned {code}: {doc}"]
        solo[name] = doc["result"]

    names = list(QUERIES)
    mismatches = []
    statuses = []

    def client(i):
        for j in range(6):
            name = names[(i + j) % len(names)]
            # every third request forces a re-execution: parity must
            # hold for fresh executions too, not just cached replays
            payload = {"sql": QUERIES[name]}
            if j % 3 == 2:
                payload["cache"] = False
            code, doc = _post(port, "/sql", payload)
            statuses.append(code)
            if code != 200:
                mismatches.append(f"client{i} req{j} {name}: HTTP {code}")
            elif doc["result"] != solo[name]:
                mismatches.append(
                    f"client{i} req{j} {name} ({doc['cache']}): result "
                    f"differs from solo run")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(300)
    fails.extend(mismatches[:5])
    if len(statuses) != clients * 6:
        fails.append(f"only {len(statuses)}/{clients * 6} requests "
                     f"completed")
    _, sv = _get(port, "/serving")
    result["concurrency"] = {
        "clients": clients, "requests": len(statuses),
        "cache": sv["result_cache"]}
    if sv["result_cache"]["hits"] == 0:
        fails.append("concurrent drive recorded no cache hits")
    return fails


def admission_and_cancel(port: int, result: dict) -> list:
    from spark_rapids_tpu.runtime import serving
    fails = []
    srv = serving.server()
    old_inflight = srv.max_inflight
    srv.max_inflight = 2
    slow_payload = {
        "sql": "SELECT k, SUM(v) AS sv FROM t GROUP BY k",
        "session": "slow", "cache": False,
        "conf": {"spark.rapids.sql.reader.batchSizeRows": "512",
                 "spark.rapids.debug.faults": "scan.decode:delay:400",
                 "spark.rapids.debug.faults.delayMs": "40"}}
    boxes = [{}, {}]

    def slow_client(box):
        box["resp"] = _post(port, "/sql", slow_payload)

    try:
        threads = [threading.Thread(target=slow_client, args=(b,))
                   for b in boxes]
        for th in threads:
            th.start()
        t0 = time.monotonic()
        while time.monotonic() - t0 < 30:
            _, sv = _get(port, "/serving")
            if sv["active_requests"] >= 2:
                break
            time.sleep(0.05)
        else:
            fails.append("slow requests never both went active")
        # saturated: the third request is refused with a typed 429
        code, doc = _post(port, "/sql", {"sql": QUERIES["proj"]})
        if code != 429 or doc.get("error_type") != "QueryRejectedError":
            fails.append(f"saturated server answered {code} {doc}")
        # cancel both via the HTTP surface -> 499 within the bound
        _, qdoc = _get(port, "/queries")
        running = [q["query_id"] for q in qdoc.get("running", [])]
        t0 = time.monotonic()
        for qid in running:
            _post(port, f"/queries/{qid}/cancel", {})
        for th in threads:
            th.join(30)
        cancel_s = time.monotonic() - t0
        codes = sorted(b.get("resp", (0, None))[0] for b in boxes)
        if codes != [499, 499]:
            fails.append(f"cancelled slow requests answered {codes}")
        for b in boxes:
            d = (b.get("resp") or (0, {}))[1] or {}
            if d.get("error_type") != "QueryCancelledError":
                fails.append(f"cancel doc not typed: {d}")
                break
        if cancel_s > 10.0:
            fails.append(f"cancel->terminal took {cancel_s:.1f}s")
        result["admission_cancel"] = {
            "rejected_code": code, "cancelled_codes": codes,
            "cancel_to_terminal_s": round(cancel_s, 3)}
    finally:
        srv.max_inflight = old_inflight
    return fails


# ---------------------------------------------------------------------------
# gate 4: replica warm-boot (subprocess pair)
# ---------------------------------------------------------------------------

def _make_join_data(d: str) -> None:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(20260807)
    n, k = 50_000, 400
    pq.write_table(pa.table({
        "key": rng.integers(0, k, n).astype(np.int64),
        "price": np.round(rng.uniform(1.0, 100.0, n), 2),
    }), os.path.join(d, "fact.parquet"))
    pq.write_table(pa.table({
        "key": np.arange(k, dtype=np.int64),
        "grp": rng.integers(0, 8, k).astype(np.int64),
    }), os.path.join(d, "dim.parquet"))


def _register_join(sess, d: str) -> None:
    sess.create_or_replace_temp_view(
        "fact", sess.read_parquet(os.path.join(d, "fact.parquet")))
    sess.create_or_replace_temp_view(
        "dim", sess.read_parquet(os.path.join(d, "dim.parquet")))


def worker_seed(d: str) -> dict:
    """First process: record the hot query twice (warmup recurrence)
    against a shared historyDir + persistent compile cache."""
    from spark_rapids_tpu.runtime.serving.server import serialize_table
    from spark_rapids_tpu.sql.session import TpuSession
    sess = TpuSession({
        "spark.rapids.obs.historyDir": os.path.join(d, "hist"),
        "spark.rapids.compile.cacheDir": os.path.join(d, "xla_cache"),
    })
    _register_join(sess, d)
    sess.sql(HOT_SQL).collect()
    tbl = sess.sql(HOT_SQL).collect()
    return {"result_b64":
            base64.b64encode(serialize_table(tbl)).decode("ascii")}


def worker_replica(d: str) -> dict:
    """Fresh serving replica on the shared state: the first hot-digest
    request must execute with zero backend compiles."""
    from spark_rapids_tpu.runtime import compile_cache as CC
    from spark_rapids_tpu.runtime import obs, serving
    from spark_rapids_tpu.sql.session import TpuSession
    sess = TpuSession({
        "spark.rapids.obs.historyDir": os.path.join(d, "hist"),
        "spark.rapids.compile.cacheDir": os.path.join(d, "xla_cache"),
        "spark.rapids.compile.warmup.enabled": "true",
        "spark.rapids.serving.enabled": "true",
    })
    _register_join(sess, d)
    # drain the replay BEFORE the baseline: its persistent-cache loads
    # fire backend-compile events of their own and must not be charged
    # to the request (a client sees the same thing — the serving layer
    # holds the first request until the replay drains)
    from spark_rapids_tpu.runtime import warmup
    mgr = warmup.manager()
    drained = mgr.wait(180) if mgr is not None else False
    st = obs.state()
    ctr0 = st.registry.counter("rapids_xla_compiles_total").value \
        if st is not None else 0
    stats0 = CC.stats()["xla_compiles"]
    code, doc = serving.handle_sql({"sql": HOT_SQL})
    ctr1 = st.registry.counter("rapids_xla_compiles_total").value \
        if st is not None else 0
    return {"code": code,
            "drained": drained,
            "cache": doc.get("cache"),
            "doc_xla_compiles": doc.get("xla_compiles"),
            "counter_delta": ctr1 - ctr0,
            "stats_delta": CC.stats()["xla_compiles"] - stats0,
            "warm_boot": serving.server().warm_boot,
            "persistent_hits": CC.stats()["persistent_hits"],
            "result_b64": doc.get("result")}


def _run_worker(mode: str, d: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", mode,
         "--dir", d],
        capture_output=True, text=True, timeout=600, env=env)
    if out.returncode != 0:
        print(out.stdout)
        print(out.stderr, file=sys.stderr)
        raise SystemExit(f"serving_smoke {mode} worker failed")
    return json.loads(out.stdout.strip().splitlines()[-1])


def warm_boot_gate(result: dict) -> list:
    import tempfile
    fails = []
    with tempfile.TemporaryDirectory(prefix="serving_smoke_") as d:
        _make_join_data(d)
        seed = _run_worker("seed", d)
        rep = _run_worker("replica", d)
        wb = rep.get("warm_boot") or {}
        result["warm_boot"] = {k: v for k, v in rep.items()
                              if k != "result_b64"}
        if rep["code"] != 200 or rep["cache"] != "miss":
            fails.append(f"replica first request: code={rep['code']} "
                         f"cache={rep['cache']}")
        if not wb.get("warmed"):
            fails.append(f"replica warm boot did not complete: {wb}")
        if rep["doc_xla_compiles"] != 0 or rep["counter_delta"] != 0 \
                or rep["stats_delta"] != 0:
            fails.append(
                f"replica first hot request compiled: doc="
                f"{rep['doc_xla_compiles']} counter={rep['counter_delta']}"
                f" stats={rep['stats_delta']}")
        if rep["result_b64"] != seed["result_b64"]:
            fails.append("replica result not byte-identical to seed")
    return fails


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.02)
    ap.add_argument("--worker", choices=("seed", "replica"))
    ap.add_argument("--dir")
    args = ap.parse_args()

    if args.worker:
        fn = worker_seed if args.worker == "seed" else worker_replica
        print(json.dumps(fn(args.dir)))
        return 0

    fails = []
    result = {}

    print("[gate 1] disabled-path overhead (count x delta)...",
          flush=True)
    oh = disabled_overhead(args.reps)
    result["disabled"] = oh
    print(f"  {oh['install_reads']} install reads x "
          f"{oh['per_call_ns']}ns over {oh['drive_best_s']}s drive -> "
          f"{oh['disabled_overhead_pct']}% "
          f"(gate < {args.tolerance * 100:.0f}%)")
    if oh["disabled_overhead_pct"] > args.tolerance * 100:
        fails.append("disabled-path serving overhead over budget")

    print("[gates 2+3] serving HTTP surface...", flush=True)
    from spark_rapids_tpu.sql.session import TpuSession
    port = _free_port()
    sess = TpuSession({
        "spark.rapids.serving.enabled": "true",
        "spark.rapids.obs.port": str(port),
    })
    sess.create_or_replace_temp_view(
        "t", sess.create_dataframe(_probe_table()))
    from spark_rapids_tpu.runtime import obs
    port = obs.state().server.port

    f2 = concurrency_parity(port, args.clients, result)
    c = result.get("concurrency", {})
    print(f"  parity: {c.get('requests', 0)} requests from "
          f"{args.clients} clients, cache {c.get('cache')}")
    fails.extend(f2)

    f3 = admission_and_cancel(port, result)
    ac = result.get("admission_cancel", {})
    print(f"  admission+cancel: {ac}")
    fails.extend(f3)

    print("[gate 4] replica warm-boot (subprocess pair)...", flush=True)
    f4 = warm_boot_gate(result)
    print(f"  {result.get('warm_boot')}")
    fails.extend(f4)

    print(json.dumps(result, sort_keys=True))
    if fails:
        print("serving_smoke: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    print(f"serving_smoke: PASS ({args.clients} concurrent clients "
          f"byte-identical to solo; saturated intake 429; HTTP cancel "
          f"499 in {ac.get('cancel_to_terminal_s')}s; replica warm boot "
          f"zero-compile; disabled path "
          f"{oh['disabled_overhead_pct']}%)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
