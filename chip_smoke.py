#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the engine's main path runs on
the TPU as it is today.

One process (the only one that touches jax; the pyarrow/pandas reference
runs in it on the host) drives, through the entry points a user calls:

  device    fail unless jax's default device is a TPU; print the header
            every later number is under
  load      TPC-H-shaped tables made from --seed (30M-row lineitem,
            3M-row orders) cached into HBM through a default-conf session
  resident  q6, q1, q3join, q67win, q72shfl: once cold, then warm, each
            answer checked against the host reference (validate)
  scan      the same lineitem as a real Parquet file, q6 and q1 from
            read_parquet under the default conf (device decode on)
  served    q6, q1 and the q3 join as SQL text over POST /sql
  kernels   the four Pallas entry points, compiled, equal to their twins
  multichip with >= 4 devices: the shuffle and a narrow chain sharded over
            four chips, answers equal to the one-chip ones

After every query nothing may be hidden: no CPU operator in the plan, no
degraded action, no exec node on its fallback path, no compile in a warm
repetition. Any of these fails the run.

Stdout is two lines of JSON: the full report (versions, row counts, per
query {ok, cold_s, warm_median_s, n}, compile and cache counts, peak HBM),
then, LAST, the verdict alone: {"ok": ..., "device": {"platform": ...,
"kind": ..., "count": ...}} with exactly those keys. The exit code is 0
only if every section that ran passed. With no TPU the script exits
non-zero and prints no result, unless --allow-cpu (the rehearsal at a tiny
--rows, Pallas interpreted) says otherwise. The times in the report are
observations, never claims.
"""
from __future__ import annotations

import argparse
import base64
import http.client
import json
import os
import shutil
import socket
import statistics
import sys
import threading
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
#: the Parquet file is generated here from --seed, and removed again
DATA_DIR = os.path.join(REPO, ".chip_smoke_data")
SECTIONS = ("load", "resident", "scan", "served", "kernels", "multichip")
RESIDENT = ("q6", "q1", "q3join", "q67win", "q72shfl")
#: lineitem rows at the full size (~SF5); --rows cuts it for a rehearsal
FULL_ROWS = 30_000_000
WARM_REPS = 3
SERVED_REPS = 3

SERVED_SQL = {
    "q6": """
        SELECT SUM(l_extendedprice * l_discount) AS revenue FROM lineitem
        WHERE l_shipdate >= 8766 AND l_shipdate < 9131
          AND l_discount >= 0.05 AND l_discount <= 0.07
          AND l_quantity < 24.0""",
    "q1": """
        SELECT l_returnflag, l_linestatus, SUM(l_quantity) AS sq,
               SUM(l_extendedprice) AS sp, AVG(l_quantity) AS mq,
               AVG(l_discount) AS md, COUNT(l_quantity) AS cnt
        FROM lineitem WHERE l_shipdate <= 10471
        GROUP BY l_returnflag, l_linestatus""",
    "q3join": """
        SELECT l_orderkey,
               SUM(l_extendedprice * (1.0 - l_discount)) AS rev
        FROM lineitem JOIN orders ON l_orderkey = o_orderkey
        WHERE l_shipdate > 9100 AND o_orderdate < 9500
        GROUP BY l_orderkey
        ORDER BY rev DESC, l_orderkey ASC LIMIT 10""",
}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the workload: TPC-H-shaped tables from --seed, five queries over them
# through the DataFrame API, and the pyarrow/pandas reference of each
#
#   q6      filter + sum(price*discount)          scan/filter/reduce
#   q1      group by 2 string keys, 5 aggregates  segmented aggregation
#   q3join  lineitem x orders hash join + topN    build/probe join, sort
#   q67win  rank over (partition, order) + agg    window family
#   q72shfl 4-partition high-card group-by        hash shuffle exchange
# ---------------------------------------------------------------------------

SHUFFLE_PARTS = 4
LO, HI = 8766, 9131  # [1994-01-01, 1995-01-01) in days since epoch
#: q6's four columns: all numeric, so all device-decodable
Q6_COLUMNS = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]


def set_scale(rows: int) -> None:
    """Size every table from the lineitem row count (--rows)."""
    global ROWS, ORDERS, WIN_ROWS
    ROWS = int(rows)
    ORDERS = max(ROWS // 10, 1000)
    #: the window query runs on a slice (engine and reference alike): a
    #: 30M-row groupby-rank costs minutes on the pandas reference alone
    WIN_ROWS = min(ROWS, 10_000_000)


def make_tables(seed: int):
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    flags = np.array(["A", "N", "R"])[rng.integers(0, 3, ROWS)]
    status = np.array(["F", "O"])[rng.integers(0, 2, ROWS)]
    lineitem = pa.table({
        "l_orderkey": rng.integers(0, ORDERS, ROWS).astype(np.int64),
        "l_returnflag": flags,
        "l_linestatus": status,
        "l_quantity": rng.integers(1, 51, ROWS).astype(np.float64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105000.0, ROWS), 2),
        "l_discount": np.round(rng.uniform(0.0, 0.10, ROWS), 2),
        "l_shipdate": rng.integers(8400, 10600, ROWS).astype(np.int32),
    })
    orders = pa.table({
        "o_orderkey": np.arange(ORDERS, dtype=np.int64),
        "o_orderdate": rng.integers(8400, 10600, ORDERS).astype(np.int32),
        "o_custkey": rng.integers(0, max(ORDERS // 10, 10), ORDERS).astype(np.int64),
    })
    return lineitem, orders


def cpu_queries(t, orders):
    import pyarrow.compute as pc

    def q6():
        m = pc.and_(
            pc.and_(
                pc.and_(pc.greater_equal(t["l_shipdate"], LO),
                        pc.less(t["l_shipdate"], HI)),
                pc.and_(pc.greater_equal(t["l_discount"], 0.05),
                        pc.less_equal(t["l_discount"], 0.07))),
            pc.less(t["l_quantity"], 24.0))
        f = t.filter(m)
        return pc.sum(pc.multiply(f["l_extendedprice"], f["l_discount"])).as_py()

    def q1():
        f = t.filter(pc.less_equal(t["l_shipdate"], 10471))
        g = f.group_by(["l_returnflag", "l_linestatus"]).aggregate([
            ("l_quantity", "sum"), ("l_extendedprice", "sum"),
            ("l_quantity", "mean"), ("l_discount", "mean"),
            ("l_quantity", "count"),
        ])
        return {(rf, ls): (sq, sp, mq, md, cnt) for rf, ls, sq, sp, mq, md, cnt
                in zip(g["l_returnflag"].to_pylist(),
                       g["l_linestatus"].to_pylist(),
                       g["l_quantity_sum"].to_pylist(),
                       g["l_extendedprice_sum"].to_pylist(),
                       g["l_quantity_mean"].to_pylist(),
                       g["l_discount_mean"].to_pylist(),
                       g["l_quantity_count"].to_pylist())}

    def q3join():
        li = t.select(["l_orderkey", "l_shipdate", "l_extendedprice",
                       "l_discount"])
        li = li.filter(pc.greater(li["l_shipdate"], 9100))
        od = orders.filter(pc.less(orders["o_orderdate"], 9500))
        j = li.join(od, keys="l_orderkey", right_keys="o_orderkey",
                    join_type="inner")
        rev = pc.multiply(j["l_extendedprice"],
                          pc.subtract(1.0, j["l_discount"]))
        j = j.append_column("rev", rev)
        g = j.group_by(["l_orderkey"]).aggregate([("rev", "sum")])
        idx = pc.select_k_unstable(g, 10, [("rev_sum", "descending")])
        top = g.take(idx)
        return {k: round(v, 2) for k, v in
                zip(top["l_orderkey"].to_pylist(), top["rev_sum"].to_pylist())}

    def q67win():
        import pandas as pd
        tw = t.slice(0, WIN_ROWS)
        df = pd.DataFrame({
            "rf": tw["l_returnflag"].to_pandas(),
            "ls": tw["l_linestatus"].to_pandas(),
            "sd": tw["l_shipdate"].to_pandas(),
        })
        rk = df.groupby(["rf", "ls"])["sd"].rank(method="min").astype(np.int64)
        df["rk"] = rk
        out = df.groupby(["rf", "ls"])["rk"].max()
        return {k: int(v) for k, v in out.items()}

    def q72shfl():
        import pyarrow as pa
        key = pa.chunked_array([
            np.mod(c.to_numpy(), 100_000) for c in t["l_orderkey"].chunks])
        tt = t.select(["l_quantity"]).append_column("k", key)
        g = tt.group_by(["k"]).aggregate([("l_quantity", "sum"),
                                          ("l_quantity", "count")])
        return (g.num_rows,
                round(pc.sum(g["l_quantity_sum"]).as_py(), 2),
                int(pc.sum(g["l_quantity_count"]).as_py()))

    return {"q6": q6, "q1": q1, "q3join": q3join, "q67win": q67win,
            "q72shfl": q72shfl}


def cache_tables(sess, t, orders) -> dict:
    """Upload the working set and pin it in HBM with df.cache(): the
    frames tpu_queries runs over."""

    def _mat(df, what):
        log(f"uploading {what}...")
        df.count()  # force HBM materialization
        return df

    cached = _mat(sess.create_dataframe(t).cache(), "lineitem")
    return {
        "lineitem": cached,
        "orders": _mat(sess.create_dataframe(orders).cache(), "orders"),
        "sharded": _mat(sess.create_dataframe(
            t.select(["l_orderkey", "l_quantity"]),
            num_partitions=SHUFFLE_PARTS).cache(),
            f"sharded {ROWS} rows x {SHUFFLE_PARTS} parts (2 cols)"),
        "window": (cached if WIN_ROWS >= ROWS
                   else _mat(sess.create_dataframe(
                       t.slice(0, WIN_ROWS)).cache(),
                       f"window slice {WIN_ROWS}")),
    }


def tpu_queries(frames: dict) -> dict:
    """The five queries over `frames` (cache_tables' dict, or any subset
    of it: a query only needs its own frames when it is called — the
    scan-from-disk passes hand in a read_parquet lineitem alone)."""
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.expr.window import Window

    cached = frames.get("lineitem")
    ocached = frames.get("orders")
    sharded = frames.get("sharded")
    wcached = frames.get("window")

    def q6():
        cond = ((col("l_shipdate") >= lit(LO)) & (col("l_shipdate") < lit(HI))
                & (col("l_discount") >= lit(0.05)) & (col("l_discount") <= lit(0.07))
                & (col("l_quantity") < lit(24.0)))
        out = (cached.filter(cond)
               .agg(F.sum(col("l_extendedprice") * col("l_discount"))))
        return shape_answer("q6", out.to_pydict())

    def q1():
        out = (cached.filter(col("l_shipdate") <= lit(10471))
               .group_by("l_returnflag", "l_linestatus")
               .agg(F.sum(col("l_quantity")).alias("sq"),
                    F.sum(col("l_extendedprice")).alias("sp"),
                    F.avg(col("l_quantity")).alias("mq"),
                    F.avg(col("l_discount")).alias("md"),
                    F.count(col("l_quantity")).alias("cnt")))
        return shape_answer("q1", out.to_pydict())

    def q3join():
        li = cached.filter(col("l_shipdate") > lit(9100))
        od = ocached.filter(col("o_orderdate") < lit(9500))
        j = li.join(od, on=[(col("l_orderkey"), col("o_orderkey"))],
                    how="inner")
        g = (j.select(col("l_orderkey"),
                      (col("l_extendedprice")
                       * (lit(1.0) - col("l_discount"))).alias("rev"))
             .group_by(col("l_orderkey")).agg(F.sum("rev").alias("rev")))
        top = g.order_by(col("rev").desc(), col("l_orderkey").asc()).limit(10)
        return shape_answer("q3join", top.to_pydict())

    def q67win():
        w = Window.partition_by(col("l_returnflag"), col("l_linestatus")) \
                  .order_by(col("l_shipdate"))
        out = (wcached.select(col("l_returnflag"), col("l_linestatus"),
                              F.rank().over(w).alias("rk"))
               .group_by(col("l_returnflag"), col("l_linestatus"))
               .agg(F.max("rk").alias("mx")))
        return shape_answer("q67win", out.to_pydict())

    def q72shfl():
        g = (sharded.select((col("l_orderkey") % lit(100_000)).alias("k"),
                            col("l_quantity"))
             .group_by(col("k"))
             .agg(F.sum("l_quantity").alias("s"),
                  F.count("l_quantity").alias("c")))
        # final reduction of the grouped result stays on device (the
        # reference reduces its grouped table on the host the same way):
        # the query exercises the exchange + aggregation, not the
        # download of 100k grouped rows
        out = g.agg(F.count(col("k")).alias("n"), F.sum(col("s")).alias("ts"),
                    F.sum(col("c")).alias("tc"))
        return shape_answer("q72shfl", out.to_pydict())

    return {"q6": q6, "q1": q1, "q3join": q3join, "q67win": q67win,
            "q72shfl": q72shfl}


def shape_answer(name, d):
    """An engine result's columns (to_pydict) as the value `validate`
    compares with the host reference's — shared by the DataFrame queries
    above and by the served section's SQL requests, which alias alike."""
    if name == "q6":
        return list(d.values())[0][0]
    if name == "q1":
        return {(rf, ls): (sq, sp, mq, md, cnt) for rf, ls, sq, sp, mq, md, cnt
                in zip(d["l_returnflag"], d["l_linestatus"], d["sq"], d["sp"],
                       d["mq"], d["md"], d["cnt"])}
    if name == "q3join":
        return {k: round(v, 2) for k, v in zip(d["l_orderkey"], d["rev"])}
    if name == "q67win":
        return {(rf, ls): int(mx) for rf, ls, mx in
                zip(d["l_returnflag"], d["l_linestatus"], d["mx"])}
    if name == "q72shfl":
        return (int(d["n"][0]), round(float(d["ts"][0]), 2), int(d["tc"][0]))
    raise KeyError(name)


def _close(a, b, tol=1e-6):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


def validate(name, tpu_val, cpu_val) -> bool:
    if name == "q6":
        return _close(tpu_val, cpu_val)
    if name == "q1":
        return (set(tpu_val) == set(cpu_val) and all(
            all(_close(a, b) for a, b in zip(tpu_val[k][:4], cpu_val[k][:4]))
            and int(tpu_val[k][4]) == int(cpu_val[k][4]) for k in cpu_val))
    if name == "q3join":
        return (set(tpu_val) == set(cpu_val)
                and all(_close(tpu_val[k], cpu_val[k], 1e-9) for k in cpu_val))
    if name == "q67win":
        return tpu_val == cpu_val
    if name == "q72shfl":
        return (tpu_val[0] == cpu_val[0] and _close(tpu_val[1], cpu_val[1])
                and tpu_val[2] == cpu_val[2])
    return False


def write_lineitem_parquet(t, path: str) -> None:
    """`t` as a REAL parquet file: snappy, data-page v1, 1M-row groups.
    Dictionary only where cardinality warrants it: pyarrow switches a
    chunk's remaining pages to PLAIN when the dict overflows, and
    mixed-encoding chunks host-fall-back per column (supported matrix)
    — high-entropy columns are written PLAIN outright."""
    import pyarrow.parquet as pq
    pq.write_table(t, path, row_group_size=1 << 20,
                   use_dictionary=["l_shipdate", "l_quantity",
                                   "l_returnflag", "l_linestatus"],
                   compression="snappy", data_page_version="1.0")


class SectionFailed(Exception):
    """A check of this section did not hold."""


class Smoke:
    def __init__(self, args):
        self.args = args
        self.report: dict = {"ok": False, "device": None}
        self.failures: list = []
        self.sess = None      # the default-conf session
        self.tpu = None       # tpu_queries over the cached frames
        self.ref = {}         # query name -> host reference answer
        self.answers = {}     # query name -> one-chip engine answer

    # -- device ------------------------------------------------------------
    def device(self) -> None:
        import jax
        import jaxlib
        from importlib import metadata
        dev = jax.devices()[0]
        if dev.platform != "tpu" and not self.args.allow_cpu:
            log(f"no TPU: jax's default device is {dev.platform!r} "
                f"({dev.device_kind}); nothing was run and no result is "
                f"written (--allow-cpu --rows N rehearses on the CPU)")
            raise SystemExit(2)
        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = None
        from spark_rapids_tpu import native
        self.report["device"] = {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}
        self.report["versions"] = {
            "python": sys.version.split()[0], "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu}
        self.report["env"] = {k: os.environ.get(k) for k in (
            "JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
        #: SERIALIZED-shuffle serializer core, built with g++ on first
        #: use; off the resident path, reported so a silent Python
        #: fallback is visible
        self.report["native_kudo_loaded"] = native.kudo_lib() is not None
        self.report["rehearsal_on_cpu"] = dev.platform != "tpu"
        self.report["f64"] = self._f64_probe()
        log(f"device {self.report['device']} "
            f"versions {self.report['versions']} f64 {self.report['f64']}")

    @staticmethod
    def _f64_probe() -> dict:
        """What the device's f64 is (docs/architecture.md: emulated on
        the v5e, ~48 mantissa bits, f32 exponent range, subnormals
        flushed) — the comparison tolerances rest on it."""
        import jax
        import jax.numpy as jnp
        import spark_rapids_tpu  # noqa: F401 - turns jax's x64 mode on
        x = np.array([1e300, 1e-310, 1 / 3, np.pi, np.e, 2 / 7])
        d = jnp.asarray(x)
        back, sq, dbl = jax.device_get((d, d * d, d + d))

        def bits(got, want):  # agreeing leading bits of dense mantissas
            err = np.max(np.abs(got - want) / np.abs(want))
            return 53 if err == 0 else int(np.floor(-np.log2(err)))

        return {"1e300_stays_finite": bool(np.isfinite(back[0])),
                "subnormal_flushed": bool(dbl[1] == 0.0),
                "roundtrip_mantissa_bits": bits(back[2:], x[2:]),
                "multiply_mantissa_bits": bits(sq[2:], x[2:] * x[2:])}

    # -- the checks every query passes through -----------------------------
    def _hidden(self, sess, fallbacks_before: int) -> list:
        """Everything that could make a run look healthy when it is not."""
        from spark_rapids_tpu.runtime import obs
        from spark_rapids_tpu.runtime.metrics import walk_exec_tree
        bad = []
        if "cannot run on TPU" in sess.last_plan_explain():
            bad.append("plan has an operator that cannot run on TPU")
        if sess.last_action_status != ("ok", None):
            bad.append(f"last_action_status={sess.last_action_status}")
        for key, node, *_ in walk_exec_tree(sess._last_exec):
            for flag in ("_failed", "_chain_failed"):
                if getattr(node, flag, False):
                    bad.append(f"{key} fell back ({flag})")
        n = obs.exec_fallbacks() - fallbacks_before
        if n:
            bad.append(f"{n} stage fallback(s) counted in "
                       f"rapids_stage_fallbacks_total")
        return bad

    def _run_query(self, sess, name: str, fn, ref, validate_as=None):
        """`fn` once cold then WARM_REPS warm, checked against `ref` and
        against _hidden each time; warm reps must not compile. Returns
        (record, answer)."""
        from spark_rapids_tpu.runtime import compile_cache as CC
        from spark_rapids_tpu.runtime import obs
        rec: dict = {"ok": False}
        problems = []
        fb0 = obs.exec_fallbacks()
        seg0 = obs.pallas_segsum_traces()
        c0 = CC.stats()
        t0 = time.perf_counter()
        val = fn()
        rec["cold_s"] = time.perf_counter() - t0
        c1 = CC.stats()
        rec["cold_xla_compiles"] = c1["xla_compiles"] - c0["xla_compiles"]
        rec["cold_compile_s"] = (c1["xla_compile_ns"]
                                 - c0["xla_compile_ns"]) / 1e9
        seg1 = obs.pallas_segsum_traces()
        rec["pallas_segsum"] = {p: seg1[p] - seg0[p] for p in seg1}
        problems += self._hidden(sess, fb0)
        warm = []
        for _ in range(WARM_REPS):
            t0 = time.perf_counter()
            wval = fn()
            warm.append(time.perf_counter() - t0)
            if not validate(validate_as or name, wval, val):
                problems.append(f"warm answer differs: {wval} vs {val}")
        n_warm = CC.stats()["xla_compiles"] - c1["xla_compiles"]
        if n_warm:
            problems.append(f"{n_warm} XLA compile(s) in warm repetitions")
        problems += self._hidden(sess, fb0)
        if not validate(validate_as or name, val, ref):
            problems.append(f"MISMATCH engine={val} reference={ref}")
        rec["warm_median_s"] = statistics.median(warm)
        rec["n"] = len(warm)
        rec["ok"] = not problems
        if problems:
            rec["problems"] = problems
            self.failures.append(f"{name}: " + "; ".join(problems))
        log(f"{name}: ok={rec['ok']} cold={rec['cold_s']:.3f}s "
            f"warm_median={rec['warm_median_s']:.4f}s "
            f"compiles={rec['cold_xla_compiles']} "
            f"pallas_segsum={rec['pallas_segsum']}"
            + (f" PROBLEMS {problems}" if problems else ""))
        return rec, val

    # -- load --------------------------------------------------------------
    def load(self) -> None:
        from spark_rapids_tpu.sql.session import TpuSession
        set_scale(self.args.rows)
        self.report["rows"] = {
            "lineitem": ROWS, "orders": ORDERS,
            "window_slice": WIN_ROWS, "shuffle": ROWS,
            "shuffle_partitions": SHUFFLE_PARTS,
            "cut_from_30M": round(1 - ROWS / FULL_ROWS, 4)}
        t0 = time.perf_counter()
        self.tables = make_tables(self.args.seed)
        self.cpu = cpu_queries(*self.tables)
        gen_s = time.perf_counter() - t0
        self.sess = TpuSession()  # default conf
        t0 = time.perf_counter()
        self.frames = cache_tables(self.sess, *self.tables)
        self.tpu = tpu_queries(self.frames)
        self.report["load"] = {"ok": True, "generate_s": gen_s,
                               "upload_and_cache_s":
                                   time.perf_counter() - t0}
        log(f"load {self.report['rows']} {self.report['load']}")

    def _reference(self, name: str):
        if name not in self.ref:
            self.ref[name] = self.cpu[name]()
        return self.ref[name]

    # -- resident queries --------------------------------------------------
    def resident(self) -> None:
        out = self.report["resident"] = {}
        for name in RESIDENT:
            out[name], self.answers[name] = self._run_query(
                self.sess, name, self.tpu[name], self._reference(name))

    # -- scan from disk ----------------------------------------------------
    def scan(self) -> None:
        from spark_rapids_tpu.sql.session import TpuSession
        out = self.report["scan"] = {}
        shutil.rmtree(DATA_DIR, ignore_errors=True)
        os.makedirs(DATA_DIR)
        path = os.path.join(DATA_DIR, "lineitem.parquet")
        try:
            t0 = time.perf_counter()
            write_lineitem_parquet(self.tables[0], path)
            out["write_s"] = time.perf_counter() - t0
            out["file_bytes"] = os.path.getsize(path)
            sess = TpuSession()  # default conf: device decode on
            for name, cols in (("q6", Q6_COLUMNS), ("q1", None)):
                df = sess.read_parquet(path, columns=cols)
                fn = tpu_queries({"lineitem": df})[name]
                rec, _ = self._run_query(sess, f"scan_{name}", fn,
                                         self._reference(name),
                                         validate_as=name)
                snaps = sess.last_metrics().values()
                rec["decode_fallback_columns"] = int(sum(
                    v.get("numDecodeFallbackColumns", 0) for v in snaps))
                rec["encoded_bytes"] = int(sum(
                    v.get("encodedBytes", 0) for v in snaps))
                out[name] = rec
            # q6's four columns are numeric: none may host-decode. q1's
            # two string columns are an expected per-column fallback,
            # reported above
            if out["q6"]["decode_fallback_columns"]:
                out["q6"]["ok"] = False
                raise SectionFailed(
                    f"q6 scan: {out['q6']['decode_fallback_columns']} "
                    f"numeric column(s) fell back to host decode")
            if not out["q6"]["encoded_bytes"]:
                out["q6"]["ok"] = False
                raise SectionFailed("q6 scan: device decode read no "
                                    "encoded bytes (decode path not taken)")
        finally:
            shutil.rmtree(DATA_DIR, ignore_errors=True)

    # -- served path -------------------------------------------------------
    def served(self) -> None:
        from spark_rapids_tpu.runtime import obs, serving
        from spark_rapids_tpu.runtime.serving.server import deserialize_table
        from spark_rapids_tpu.sql.session import TpuSession
        out = self.report["served"] = {}
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        sess = TpuSession({"spark.rapids.serving.enabled": "true",
                           "spark.rapids.obs.port": str(port)})
        for view in ("lineitem", "orders"):
            sess.create_or_replace_temp_view(view, self.frames[view])
        if not serving.installed() or obs.state().server is None:
            raise SectionFailed("serving layer did not install")
        port = obs.state().server.port

        results: dict = {}

        def client():
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=600)
                for name, sql in SERVED_SQL.items():
                    recs = results[name] = []
                    for _ in range(SERVED_REPS):
                        t0 = time.perf_counter()
                        conn.request(
                            "POST", "/sql",
                            body=json.dumps({"sql": sql}).encode(),
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        doc = json.loads(resp.read())
                        recs.append((resp.status, doc,
                                     time.perf_counter() - t0))
                conn.close()
            except Exception as e:  # noqa: BLE001 - handed to the
                results["error"] = e  # section, which fails on it

        fb0 = obs.exec_fallbacks()
        th = threading.Thread(target=client, name="chip-smoke-client")
        th.start()
        th.join(timeout=900)
        if th.is_alive():
            raise SectionFailed("POST /sql client did not finish in 900s")
        if "error" in results:
            raise SectionFailed(f"POST /sql client failed: "
                                f"{results['error']!r}")
        problems = []
        for name in SERVED_SQL:
            recs = results[name]
            rec = out[name] = {"ok": False, "n": len(recs),
                               "http": [r[0] for r in recs],
                               "cache": [r[1].get("cache") for r in recs],
                               "xla_compiles": [r[1].get("xla_compiles")
                                                for r in recs],
                               "first_s": recs[0][2],
                               "rest_median_s": statistics.median(
                                   r[2] for r in recs[1:])}
            mine = []
            for status, doc, _ in recs:
                if status != 200 or doc.get("status") != "ok":
                    mine.append(f"HTTP {status}: "
                                f"{json.dumps(doc)[:300]}")
                    continue
                got = shape_answer(name, deserialize_table(
                    base64.b64decode(doc["result"])).to_pydict())
                want = self.answers.get(name)
                if want is None:  # resident section not run
                    want = self.answers[name] = self.tpu[name]()
                # rows equal to the in-process answers, and the
                # in-process answer matches the host reference
                if not validate(name, got, want) or \
                        not validate(name, got, self._reference(name)):
                    mine.append(f"MISMATCH served={got} in-process={want}")
            if any(rec["xla_compiles"][1:]):
                mine.append(f"compiles in repeated requests: "
                            f"{rec['xla_compiles']}")
            rec["ok"] = not mine
            if mine:
                rec["problems"] = mine
                problems.append(f"served {name}: " + "; ".join(mine))
            log(f"served {name}: {rec}")
        n = obs.exec_fallbacks() - fb0
        if n:
            problems.append(f"served: {n} stage fallback(s)")
        self.failures += problems

    # -- kernels -----------------------------------------------------------
    def kernels(self) -> None:
        """Each Pallas entry point directly on 2^20-element device
        arrays, compiled (not interpreted) on the chip, exactly equal to
        its lax twin — independent of the engine's eligibility gates."""
        import jax
        import jax.numpy as jnp
        from spark_rapids_tpu.ops import kernels as K
        from spark_rapids_tpu.ops import pallas_decode as PD
        from spark_rapids_tpu.ops import pallas_kernels as PK
        from spark_rapids_tpu.ops import pallas_segsum as PS
        out = self.report["kernels"] = {}
        out["interpret"] = PK._interpret()
        if out["interpret"] != self.report["rehearsal_on_cpu"]:
            raise SectionFailed(f"Pallas interpret={out['interpret']} on "
                                f"{self.report['device']}")
        # the interpreter is slow: the rehearsal checks the same code at
        # a smaller plane
        n = 1 << (14 if self.report["rehearsal_on_cpu"] else 20)
        rng = np.random.default_rng(self.args.seed)

        def check(name, got, want):
            got, want = jax.device_get((got, want))
            ok = bool(np.array_equal(got, want))
            out[name] = {"ok": ok, "elements": int(np.size(want))}
            log(f"kernel {name}: ok={ok}")
            if not ok:
                bad = int(np.sum(got != want))
                self.failures.append(
                    f"kernel {name}: {bad} of {np.size(want)} elements "
                    f"differ from the lax twin")

        v = jnp.asarray(rng.integers(-2**31, 2**31, n, dtype=np.int64)
                        .astype(np.int32))
        seed = jnp.uint32(42)
        check("murmur3_int32_pallas",
              PK.murmur3_int32_pallas(v, seed),
              K._mm3_fmix(K._mm3_mix_h1(
                  seed, K._mm3_mix_k1(v.astype(jnp.uint32))), 4))

        raw = jnp.asarray(rng.integers(0, 256, n).astype(np.uint8))
        for upper in (True, False):
            want = (jnp.where((raw >= 97) & (raw <= 122), raw - 32, raw)
                    if upper else
                    jnp.where((raw >= 65) & (raw <= 90), raw + 32, raw))
            check(f"ascii_case_map_pallas[{'upper' if upper else 'lower'}]",
                  PK.ascii_case_map_pallas(raw, upper), want)

        w0, w1 = (jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64)
                              .astype(np.uint32)) for _ in range(2))
        sh = jnp.asarray(rng.integers(0, 32, n).astype(np.uint32))
        width = rng.integers(1, 33, n).astype(np.uint64)
        mask = jnp.asarray(((np.uint64(1) << width) - np.uint64(1))
                           .astype(np.uint32))
        check("bitslice_u32_pallas",
              PD.bitslice_u32_pallas(w0, w1, sh, mask),
              PD.bitslice_u32_lax(w0, w1, sh, mask))

        # sorted dense ids (groups of 1..64 rows, far under
        # MAX_GROUP_ROWS) and 8-bit digit payloads: bf16-exact, and every
        # f32 per-id sum is an exact integer on both sides
        gid_np = np.cumsum(rng.random(n) < 0.25).astype(np.int32)
        outcap = -(-(int(gid_np[-1]) + 1) // (2 * PS.TILE)) * 2 * PS.TILE
        gid = jnp.asarray(gid_np)
        for P in (8, 16):
            pay = jnp.asarray(rng.integers(-128, 129, (n, P))
                              .astype(np.float32)).astype(jnp.bfloat16)
            check(f"segsum_window[P={P}]",
                  PS.segsum_window(gid, pay, outcap),
                  jax.ops.segment_sum(pay.astype(jnp.float32), gid,
                                      num_segments=outcap))

    # -- four chips --------------------------------------------------------
    def multichip(self):
        import jax
        from spark_rapids_tpu.expr.core import col, lit
        from spark_rapids_tpu.runtime import obs
        from spark_rapids_tpu.sql.dataframe import DataFrame
        from spark_rapids_tpu.sql.session import TpuSession
        out = self.report["multichip"] = {}
        devs = jax.devices()
        if len(devs) < 4:
            out["ran"] = False
            out["why_not"] = (f"{len(devs)} device(s) visible; the "
                              f"section needs 4. Not a pass of it.")
            log(f"multichip: not run ({out['why_not']})")
            return "not_run"
        out["ran"] = True

        def peaks():  # None where the backend keeps no memory stats
            stats = [d.memory_stats() for d in devs[:4]]
            return None if any(s is None for s in stats) else \
                [s["peak_bytes_in_use"] for s in stats]

        def narrow(df):
            return (df.filter(col("l_orderkey") % lit(1000) == lit(7))
                    .select(col("l_orderkey"),
                            (col("l_quantity") * lit(2.0)).alias("q2")))

        def sorted_tbl(df):
            t = df.collect()
            return t.sort_by([(c, "ascending") for c in t.column_names])

        one_q72 = self.answers.get("q72shfl") or self.tpu["q72shfl"]()
        one_narrow = sorted_tbl(narrow(self.frames["sharded"]))
        before = peaks()
        sess = TpuSession({"spark.rapids.sql.multichip.enabled": "true",
                           "spark.rapids.sql.multichip.devices": "4"})
        # the SAME cached 4-partition relation, bound to this session
        sharded = DataFrame(self.frames["sharded"].plan, sess)
        q72 = tpu_queries({"sharded": sharded})["q72shfl"]
        rec, got = self._run_query(sess, "multichip_q72shfl", q72,
                                   self._reference("q72shfl"),
                                   validate_as="q72shfl")
        snaps = sess.last_metrics()
        rec["ici_exchange_ns"] = int(sum(v.get("iciExchangeTime", 0)
                                         for v in snaps.values()))
        rec["equals_one_chip"] = got == one_q72
        out["q72shfl"] = rec
        fb0 = obs.exec_fallbacks()
        t0 = time.perf_counter()
        got_narrow = sorted_tbl(narrow(sharded))
        nrec = out["narrow"] = {"first_s": time.perf_counter() - t0}
        plan = sess._last_exec.tree_string()
        snaps = sess.last_metrics()
        nrec["sharded_in_plan"] = "ShardedStageExec" in plan
        nrec["shard_waves"] = int(sum(v.get("shardWaves", 0)
                                      for v in snaps.values()))
        nrec["rows"] = got_narrow.num_rows
        nrec["equals_one_chip"] = got_narrow.equals(one_narrow)
        after = peaks()
        out["peak_bytes_in_use_before"] = before
        out["peak_bytes_in_use_after"] = after
        problems = self._hidden(sess, fb0)
        if not nrec["sharded_in_plan"]:
            problems.append("narrow chain did not plan as ShardedStageExec")
        if nrec["shard_waves"] < 1:
            problems.append("no shardWaves recorded")
        if rec["ici_exchange_ns"] <= 0:
            problems.append("iciExchangeTime is 0: the in-program "
                            "all_to_all did not run")
        if not rec["equals_one_chip"]:
            problems.append(f"q72shfl differs: 4 chips {got} vs one chip "
                            f"{one_q72}")
        if not nrec["equals_one_chip"]:
            problems.append("narrow chain differs between 4 chips and one")
        if before is not None:
            idle = [i for i in range(4) if after[i] <= before[i]]
            out["devices_with_new_bytes"] = 4 - len(idle)
            if idle:
                problems.append(f"devices {idle} hold no new bytes: the "
                                f"shards did not land on four chips")
        else:
            out["devices_with_new_bytes"] = None  # backend reports none
        nrec["ok"] = not problems
        if problems:
            self.failures.append("multichip: " + "; ".join(problems))
        log(f"multichip: {out}")

    # -- driver ------------------------------------------------------------
    def run(self) -> int:
        from spark_rapids_tpu.runtime import compile_cache as CC
        import jax
        t_start = time.perf_counter()
        self.device()
        wanted = [s for s in SECTIONS if s in self.args.sections]
        self.report["sections"] = {}
        for name in wanted:
            t0 = time.perf_counter()
            known = len(self.failures)
            try:
                status = getattr(self, name)() or "ok"
            except Exception as e:  # noqa: BLE001 - a section boundary:
                # the failure is recorded with its traceback and fails
                # the run; later sections still say what they can
                traceback.print_exc(file=sys.stderr)
                self.failures.append(f"{name}: {type(e).__name__}: {e}")
            if len(self.failures) > known:
                status = "failed"
            self.report["sections"][name] = {
                "status": status, "seconds": time.perf_counter() - t0}
            if name == "load" and status == "failed":
                break
        st = CC.stats()
        self.report["compile"] = {
            "xla_compiles": st["xla_compiles"],
            "xla_compile_s": st["xla_compile_ns"] / 1e9,
            "warm_trace_entries": st["entries"],
            "persistent_dir": st["persistent_dir"],
            "persistent_hits": st["persistent_hits"],
            "persistent_misses": st["persistent_misses"]}
        stats = jax.devices()[0].memory_stats()
        self.report["peak_bytes_in_use"] = \
            None if stats is None else stats["peak_bytes_in_use"]
        self.report["wall_s"] = time.perf_counter() - t_start
        self.report["failures"] = self.failures
        self.report["ok"] = not self.failures
        return 0 if self.report["ok"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--rows", type=int, default=FULL_ROWS,
                    help="lineitem rows (orders is a tenth); cut only "
                         "when a time limit forces it")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU backend (tiny --rows, "
                         "Pallas interpreted): never a chip result")
    ap.add_argument("--sections", default=",".join(SECTIONS),
                    type=lambda s: s.split(","),
                    help=f"comma list out of {','.join(SECTIONS)}")
    args = ap.parse_args(argv)
    unknown = set(args.sections) - set(SECTIONS)
    if unknown:
        ap.error(f"unknown sections {sorted(unknown)}")
    sys.path.insert(0, REPO)
    smoke = Smoke(args)
    rc = smoke.run()
    sys.stderr.flush()
    print(json.dumps(smoke.report, default=str), flush=True)
    # the verdict line: exactly these keys, nothing after it
    print(json.dumps({"ok": smoke.report["ok"],
                      "device": smoke.report["device"]}), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
