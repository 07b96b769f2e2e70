#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

Set-up: the configuration's tables from --seed (datagen.py), placed as the
configuration says (cached in HBM, or Parquet files read in every query),
the cell's own queries warmed to a steady state. Then the window: the
traffic file's queries in order, back to back, through TpuSession.sql(text)
.to_pydict(), pass after pass, until --seconds have gone by and the pass in
flight is done. Then the peak memory, with --trace 1 a few traced passes,
and the comparison of every answer of the window with the plain reference
(reference/<query>.py, numpy on the host). The last line of stdout is the
result; the numbers compared stand beside their limits at the end of
stderr and under the result's last key.

Nothing here lists cells, queries or metrics: the cell is looked up in
BENCHMARK.json, and its configuration, traffic, queries, references and
metric readers are files found by name (README.md). Needs a TPU;
--rehearse-rows N runs the same path on the CPU at about N lineitem rows,
and its line names the CPU as its device.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python shows it

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

#: what a traffic file may say, and what this harness can drive today
TRAFFIC_KNOWN = {"loop": ("closed", "open"), "entry": ("session", "http"),
                 "substitution": ("validation", "stream")}
TRAFFIC_DRIVEN = {"loop": "closed", "entry": "session",
                  "substitution": "validation", "clients": 1, "rate": None}
WARM_CAP_S = 15.0        # warm passes stop near this
WARM_AGREE = 0.03        # ... or once the last three agree this closely
TRACE_TARGET_S = 4.0     # traced passes: at least two, about this long
LINEITEM_ROWS_PER_SF = 6_000_000


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Run:
    """What a run has measured; the metric readers take what they need."""

    def __init__(self):
        self.pass_s: list = []        # wall time of each pass of the window
        self.window_s = 0.0           # window start to last completion
        self.attempted = 0
        self.failed = 0
        self.setup_s = 0.0
        self.setup_compile_s = 0.0
        self.compiles_in_window = 0
        self.parse_s: list = []       # --trace 1: sess.sql() of each query
        self.stage_dispatches: list = []  # --trace 1: the engine's counter
        self.encoded_bytes: list = []  # --trace 1: Parquet bytes sent encoded
        self.fallback_columns: list = []
        self.pass_bytes = 0           # bytes a pass has to read (scanbytes)
        self.pass_rows = 0            # rows of the tables a pass reads
        self.queries_per_pass = 0
        self.peak_bytes = 0
        self.peaks = None             # this device's row of peaks.json
        self.trace = None             # trace_reduce.reduce_trace's result
        self.answers: list = []       # (query, pydict) of the whole window


def resolve(workload: str) -> tuple:
    """(cell, benchmark) of BENCHMARK.json, by the cell's name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell, bench
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    traffic = load_json(HERE, "traffic", f"{name}.json")
    for key, known in TRAFFIC_KNOWN.items():
        if traffic.get(key) not in known:
            raise SystemExit(f"traffic {name}: {key}={traffic.get(key)!r} "
                             f"is none of {known}")
    for key, driven in TRAFFIC_DRIVEN.items():
        if traffic.get(key) != driven:
            raise SystemExit(f"traffic {name}: {key}={traffic.get(key)!r} "
                             f"is not implemented (only {driven!r})")
    return traffic


def load_query(name: str) -> str:
    with open(os.path.join(HERE, "queries", f"{name}.sql")) as f:
        lines = [ln for ln in f if not ln.lstrip().startswith("--")]
    return " ".join("".join(lines).split())


def metrics_of(bench: dict, kind: str, cell: str) -> list:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def require_device(chips: int, rehearsal: bool) -> tuple:
    import jax
    devs = jax.devices()
    dev = devs[0]
    if rehearsal:
        if dev.platform == "tpu":
            raise SystemExit("--rehearse-rows is for the CPU; run the cell "
                             "itself on a TPU")
    elif dev.platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); jax found {len(devs)} x "
            f"{dev.platform!r} ({dev.device_kind}). No result written.")
        raise SystemExit(2)
    peaks = load_json(HERE, "peaks.json").get(dev.device_kind)
    if peaks is None and not rehearsal:
        raise SystemExit(f"device kind {dev.device_kind!r} is not in "
                         f"peaks.json")
    return dev, {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(devs)}, peaks


def plain_strings(table):
    """Dictionary columns as plain strings: the engine's schema takes no
    dictionary type (it encodes strings itself on upload)."""
    import pyarrow as pa
    return table.cast(pa.schema([
        pa.field(f.name, pa.string() if pa.types.is_dictionary(f.type)
                 else f.type) for f in table.schema]))


def place(sess, config: dict, tables: dict, data_dir: str, datagen) -> None:
    """Tables into the session's views, as the configuration places them;
    `datagen` is the configuration's generator module."""
    if config["placement"] == "hbm_cache":
        for name, table in tables.items():
            df = sess.create_dataframe(plain_strings(table)).cache()
            df.count()  # materialise in HBM
            sess.create_or_replace_temp_view(name, df)
    elif config["placement"] == "parquet":
        paths = datagen.write_parquet(tables, data_dir,
                                      config["parquet"]["row_group_rows"])
        for name, path in paths.items():
            sess.create_or_replace_temp_view(name, sess.read_parquet(path))
    else:
        raise SystemExit(f"unknown placement {config['placement']!r}")


def data_directory(config_name: str, seed: int, rehearsal: bool) -> str:
    """Where Parquet files go: a fixed place in the checkout, one seed kept;
    a temporary directory in a rehearsal."""
    if rehearsal:
        return tempfile.mkdtemp(prefix="bench-rehearsal-")
    base = os.path.join(HERE, ".data")
    mine = os.path.join(base, f"{config_name}-{seed}")
    if os.path.isdir(base):
        for other in os.listdir(base):
            if other.startswith(config_name + "-") and \
                    os.path.join(base, other) != mine:
                shutil.rmtree(os.path.join(base, other), ignore_errors=True)
    return mine


class Client:
    """The one client: submits the traffic's queries, in order."""

    def __init__(self, sess, texts: dict, order: list):
        self.sess, self.texts, self.order = sess, texts, order

    def run_pass(self, run: Run | None = None, detail: bool = False,
                 annotate: bool = False) -> float:
        """One pass; its wall time. With `run`, answers and failures are
        recorded; `detail` adds the per-query counters (--trace 1 only)."""
        import contextlib
        import jax
        note = jax.profiler.TraceAnnotation if annotate else \
            (lambda name: contextlib.nullcontext())
        t_pass = time.perf_counter()
        with note("bench.pass"):
            for q in self.order:
                if run is not None:
                    run.attempted += 1
                try:
                    with note(f"bench.{q}"):
                        t0 = time.perf_counter()
                        df = self.sess.sql(self.texts[q])
                        t1 = time.perf_counter()
                        answer = df.to_pydict()
                except Exception as e:  # noqa: BLE001 - a failed query
                    if run is None:     # is counted, the stream goes on
                        raise
                    run.failed += 1
                    log(f"query {q} failed: {type(e).__name__}: {e}")
                    continue
                if run is not None:
                    run.answers.append((q, answer))
                    if detail:
                        run.parse_s.append(t1 - t0)
                        self._counters(run)
        return time.perf_counter() - t_pass

    def _counters(self, run: Run) -> None:
        from spark_rapids_tpu.runtime.metrics import exec_rollup
        snaps = list(self.sess.last_metrics().values())
        run.stage_dispatches.append(sum(exec_rollup(s)["dispatches"]
                                        for s in snaps))
        run.encoded_bytes.append(sum(s.get("encodedBytes", 0)
                                     for s in snaps))
        run.fallback_columns.append(sum(
            s.get("numDecodeFallbackColumns", 0) for s in snaps))


def warm_up(client: Client, compiles) -> list:
    """Passes until the cell is steady: the first (cold) pass, then at
    least two with no XLA compile, and more while the last three disagree
    by more than WARM_AGREE, as long as one more fits under WARM_CAP_S."""
    cold = client.run_pass()
    log(f"cold pass {cold:.3f}s")
    warm, warm_clock = [], time.perf_counter()
    for _ in range(1000):
        before = compiles()
        t = client.run_pass()
        if compiles() != before:
            log(f"pass compiled ({compiles() - before}); not warm yet")
            warm, warm_clock = [], time.perf_counter()
            continue
        warm.append(t)
        last = warm[-3:]
        agree = len(last) == 3 and \
            (max(last) - min(last)) / min(last) <= WARM_AGREE
        spent = time.perf_counter() - warm_clock
        if len(warm) >= 2 and (agree or spent + t > WARM_CAP_S):
            return warm
    raise SystemExit("warm-up never reached a pass without a compile")


def traced_passes(client: Client, run: Run, cell_name: str,
                  rehearsal: bool) -> None:
    """A few passes under the profiler, reduced into run.trace. A trace
    with nothing to read is described on stderr and taken once more."""
    import jax
    import trace_reduce
    pass_s = sorted(run.pass_s)[len(run.pass_s) // 2]
    n = max(2, min(64, math.ceil(TRACE_TARGET_S / pass_s)))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if rehearsal else \
        os.path.join(HERE, ".trace", cell_name)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    for attempt in (1, 2):
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            traced = [client.run_pass(annotate=True) for _ in range(n)]
        finally:
            jax.profiler.stop_trace()
        log(f"traced {n} passes, median {sorted(traced)[n // 2] * 1e3:.1f} "
            f"ms against {pass_s * 1e3:.1f} ms untraced")
        try:
            planes = trace_reduce.load_xplane(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = trace_reduce.reduce_trace(planes)
        if run.trace or rehearsal:   # the CPU has no device plane to read
            return
        log(f"trace {attempt} has no annotated pass or no device operation "
            f"in it: {json.dumps(trace_reduce.describe(planes))[:6000]}")


def compare(run: Run, tables: dict, limits: dict) -> dict:
    """Every answer of the window against the plain reference:
    {name: {"value", "limit"}}. A row is wrong if it is missing, surplus,
    or differs in any column that is no float; floats are held to the
    widest relative gap."""
    want_of, rows_wrong, widest = {}, 0, 0.0
    for q, got in run.answers:
        if q not in want_of:
            t0 = time.perf_counter()
            want_of[q] = importlib.import_module(f"reference.{q}").answer(
                tables)
            log(f"reference {q}: {time.perf_counter() - t0:.2f}s")
        want = want_of[q]
        n_want = len(next(iter(want.values())))
        n_got = len(next(iter(got.values()))) if got else 0
        if set(got) != set(want) or n_got != n_want:
            rows_wrong += max(n_got, n_want, 1)
            continue
        for i in range(n_want):
            bad = False
            for col, values in want.items():
                w, g = values[i], got[col][i]
                if isinstance(w, float):
                    gap = abs(g - w) / max(abs(w), 1e-300) \
                        if isinstance(g, (int, float)) else math.inf
                    widest = max(widest, gap)
                elif g != w:
                    bad = True
            rows_wrong += bad
    return {"answers": {"value": len(run.answers), "limit": None},
            "rows_wrong": {"value": rows_wrong, "limit": 0},
            "rel_gap": {"value": widest, "limit": limits["rel_gap"]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=0,
                    help="rehearse on the CPU at about this many lineitem "
                         "rows; the result names the CPU as its device")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_rows > 0

    cell, bench = resolve(args.workload)
    config = load_json(HERE, "configs", f"{cell['config']}.json")
    traffic = load_traffic(cell["traffic"])
    texts = {q: load_query(q) for q in traffic["queries"]}
    wanted = metrics_of(bench, "per_layer" if args.trace else "end_to_end",
                        cell["name"])
    readers = {m["name"]: importlib.import_module(f"metrics.{m['name']}")
               for m in wanted}

    dev, device, peaks = require_device(cell["chips"], rehearsal)
    import scanbytes
    datagen = importlib.import_module(config["generator"])
    from spark_rapids_tpu.runtime import compile_cache
    from spark_rapids_tpu.sql.session import TpuSession

    def compiles() -> int:
        return compile_cache.stats()["xla_compiles"]

    run = Run()
    run.peaks = peaks
    sf = args.rehearse_rows / LINEITEM_ROWS_PER_SF if rehearsal \
        else config["scale_factor"]
    t = time.perf_counter()
    tables = datagen.generate(sf, args.seed)
    rows = {name: tb.num_rows for name, tb in tables.items()}
    schemas = {name: tb.schema for name, tb in tables.items()}
    log(f"generated {rows} in {time.perf_counter() - t:.1f}s")
    run.queries_per_pass = len(traffic["queries"])
    run.pass_bytes = sum(scanbytes.query_bytes(texts[q], schemas, rows)
                         for q in traffic["queries"])
    run.pass_rows = sum(scanbytes.query_rows(texts[q], schemas, rows)
                        for q in traffic["queries"])

    data_dir = data_directory(cell["config"], args.seed, rehearsal)
    sess = TpuSession()
    t = time.perf_counter()
    place(sess, config, tables, data_dir, datagen)
    log(f"placed ({config['placement']}) in {time.perf_counter() - t:.1f}s; "
        f"compile cache {compile_cache.stats()['persistent_dir']}")
    # the window's host keeps only what the reference will read
    named = scanbytes.columns_named(" ".join(texts.values()), schemas)
    tables = {name: tables[name].select(cols) for name, cols in named.items()}

    client = Client(sess, texts, traffic["queries"])
    warm = warm_up(client, compiles)
    log(f"warm passes {[round(w, 4) for w in warm]}")
    stats0 = compile_cache.stats()
    run.setup_compile_s = stats0["xla_compile_ns"] / 1e9
    gc.collect()

    # -- the window ---------------------------------------------------------
    t_window = time.perf_counter()
    run.setup_s = t_window - T0
    while True:
        run.pass_s.append(client.run_pass(run, detail=bool(args.trace)))
        done = time.perf_counter()
        if done - t_window >= args.seconds:
            break
    run.window_s = done - t_window
    run.compiles_in_window = compiles() - stats0["xla_compiles"]
    stats = dev.memory_stats() or {}
    run.peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    log(f"window {run.window_s:.2f}s, {len(run.pass_s)} passes, "
        f"{run.attempted} queries, {run.failed} failed, "
        f"{run.compiles_in_window} compiles")
    if args.trace and run.pass_s:
        traced_passes(client, run, cell["name"], rehearsal)
        if not run.trace and not rehearsal:
            log("no device time could be read from the trace. "
                "No result written.")
            return 3

    # -- the engine's state goes, then the reference runs -------------------
    del client, sess
    gc.collect()
    if rehearsal:
        shutil.rmtree(data_dir, ignore_errors=True)
    compared = compare(run, tables, config["limits"])
    correct = run.attempted > 0 and run.failed == 0 and all(
        c["limit"] is None or c["value"] <= c["limit"]
        for c in compared.values())

    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = run.peak_bytes
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["compared"] = compared
    third = max(len(run.pass_s) // 3, 1)
    log("median pass ms in the window's first, middle and last third: "
        + ", ".join(f"{sorted(part)[len(part) // 2] * 1e3:.1f}" for part in (
            run.pass_s[:third], run.pass_s[third:-third] or run.pass_s,
            run.pass_s[-third:])))
    log(f"samples: {len(run.pass_s)} passes; fallback columns a query "
        f"{sorted(set(run.fallback_columns))}; stage dispatches a query "
        f"{sorted(set(run.stage_dispatches))}")
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
