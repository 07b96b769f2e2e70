"""Device-decode smoke (round 16): the CI gate for the device-side
Parquet decode path.

1. NDS-probe-shaped parity: scan / filter / group-by-agg queries over a
   REAL parquet file (snappy + dictionary + nulls + a string fallback
   column) must be byte-identical with decode.device on and off.
2. Attribution shift: with device decode ON the value decode runs inside
   the fused dispatch — encodedBytes (what crossed the link) and
   decodedBytes (what the kernel materialized) are recorded and the
   host_decode wall share drops against the host path; the plan carries
   DeviceDecodeScanExec and the per-column fallback note.
3. Disabled-path overhead: with decode.device OFF the only new code the
   old path executes is the conf gate at ParquetScan conversion. Same
   count x delta methodology as tools/aqe_smoke.py (end-to-end A/B
   timing is noise-bound on shared CI machines): count the gate's firings
   during a probe drive, measure its per-call cost in a tight loop,
   overhead must stay under --tolerance (2%) of the drive.

4. Run-table expansion alone (ops/pallas_decode.expand_runs: boundary
   marks and a prefix sum, no per-row search): two run tables over
   --expand-rows rows, one run a 512 values (what pyarrow writes) and
   alternating 8-value RLE and bit-packed runs (as many runs as a table
   can hold: rows/8), each expanded on the default device, compared
   value for value with the numpy expansion of the same table, and
   timed (`expand_ms`, median of --reps calls that end in
   block_until_ready).

Usage: python tools/decode_smoke.py [--rows 200000] [--tolerance 0.02]
                                    [--expand-rows 131072]

Here it runs on the CPU backend and no time it prints is a measurement
of the chip; the line names the device it ran on. Through the chip tool
(`--expand-rows 1048576`) `expand_ms` is the chip's.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["XLA_FLAGS"] = _flags

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pyarrow.parquet as pq  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.sql.session import TpuSession  # noqa: E402
from spark_rapids_tpu.sql import functions as F  # noqa: E402
from spark_rapids_tpu.expr.core import col, lit  # noqa: E402


def write_probe_file(tdir: str, rows: int) -> str:
    """A store_sales-shaped slice: dict / plain / bool / nullable
    columns plus one string column that host-falls-back per column."""
    rng = np.random.default_rng(16)
    qty = rng.integers(1, 100, rows).astype(np.int64)
    price = np.round(rng.uniform(1.0, 300.0, rows), 2)
    null_mask = rng.random(rows) < 0.12
    t = pa.table({
        "ss_item_sk": pa.array(rng.integers(0, 200, rows).astype(np.int32)),
        "ss_quantity": pa.array(qty, mask=null_mask),
        "ss_sales_price": pa.array(price, mask=null_mask),
        "ss_promo": pa.array(rng.integers(0, 2, rows).astype(bool)),
        "ss_store_id": pa.array(
            np.array(["s1", "s2", "s3", None], object)[
                rng.integers(0, 4, rows)]),
    })
    path = os.path.join(tdir, "store_sales.parquet")
    pq.write_table(t, path, row_group_size=max(rows // 4, 1000),
                   use_dictionary=["ss_item_sk", "ss_store_id"],
                   compression="snappy", data_page_version="1.0")
    return path


def queries(path):
    return {
        "scan": lambda s: s.read_parquet(path),
        "filter": lambda s: (s.read_parquet(path)
                             .filter(col("ss_quantity") > lit(50))),
        "agg": lambda s: (s.read_parquet(path)
                          .group_by("ss_item_sk")
                          .agg(F.sum(col("ss_sales_price")).alias("rev"),
                               F.count(col("ss_promo")).alias("n"))),
    }


def _sorted(tbl):
    return tbl.sort_by([(c, "ascending") for c in tbl.column_names])


def parity_and_shift(path, result) -> list:
    """Returns a list of failure strings (empty = pass)."""
    fails = []
    qs = queries(path)
    attr = {}
    bytes_seen = {}
    for flag in ("true", "false"):
        sess = TpuSession({"spark.rapids.sql.decode.device.enabled": flag})
        key = "device" if flag == "true" else "host"
        outs = {}
        for name, q in qs.items():
            outs[name] = _sorted(q(sess).collect())
        try:
            a = sess.last_attribution() or {}
            attr[key] = {k: round(v, 4)
                         for k, v in (a.get("buckets") or {}).items() if v}
        except Exception:  # noqa: BLE001 - attribution is advisory
            attr[key] = {}
        snaps = sess.last_metrics()
        bytes_seen[key] = {
            "encoded": sum(v.get("encodedBytes", 0)
                           for v in snaps.values()),
            "decoded": sum(v.get("decodedBytes", 0)
                           for v in snaps.values()),
            "fallback_columns": sum(v.get("numDecodeFallbackColumns", 0)
                                    for v in snaps.values()),
        }
        if flag == "true":
            dev_outs = outs
            stages = qs["filter"](sess).explain("stages")
            if "DeviceDecodeScanExec" not in stages:
                fails.append("device path missing DeviceDecodeScanExec")
            if "host-fallback{ss_store_id:" not in stages:
                fails.append("per-column fallback note missing from explain")
        else:
            host_outs = outs
            stages = qs["filter"](sess).explain("stages")
            if "DeviceDecodeScanExec" in stages:
                fails.append("disabled path still plans DeviceDecodeScanExec")
    for name in qs:
        if not dev_outs[name].equals(host_outs[name]):
            fails.append(f"parity: {name} differs between decode paths")
    result["attribution"] = attr
    result["bytes"] = bytes_seen
    # the structural shift: encoded planes crossed the link on the device
    # path (and are SMALLER than what the kernel materialized), none on
    # the host path, and the string column fell back per column
    if not bytes_seen["device"]["encoded"]:
        fails.append("device path recorded no encodedBytes")
    if bytes_seen["device"]["decoded"] <= bytes_seen["device"]["encoded"]:
        fails.append("decodedBytes <= encodedBytes: decode is not winning "
                     "link bytes")
    if bytes_seen["host"]["encoded"]:
        fails.append("host path recorded encodedBytes")
    if not bytes_seen["device"]["fallback_columns"]:
        fails.append("string column did not host-fall-back per column")
    # the wall-time shift (advisory on CPU sim, recorded for TPU rounds):
    # host_decode no longer holds the value decode on the device path
    d_att, h_att = attr.get("device", {}), attr.get("host", {})
    if d_att and not d_att.get("device_compute", 0.0) > 0:
        fails.append("device path attributed no device_compute")
    result["host_decode_seconds"] = {
        "device": d_att.get("host_decode", 0.0),
        "host": h_att.get("host_decode", 0.0)}
    return fails


def disabled_overhead(path, reps: int) -> dict:
    """Count x delta: the disabled path's only new site is the decode
    conf gate read at ParquetScan conversion."""
    from spark_rapids_tpu import config as C

    off = TpuSession({"spark.rapids.sql.decode.device.enabled": "false"})
    drive = queries(path)["agg"]
    drive(off).collect()  # warm compile caches out of the timed drives

    conf = off.conf
    counts = {"decode.device.enabled": 0}
    orig_get = type(conf).get

    def counting_get(self, entry, *a, **k):
        if getattr(entry, "key", None) == C.DEVICE_DECODE_ENABLED.key:
            counts["decode.device.enabled"] += 1
        return orig_get(self, entry, *a, **k)

    type(conf).get = counting_get
    try:
        drive(off).collect()
    finally:
        type(conf).get = orig_get

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        drive(off).collect()
        best = min(best, time.perf_counter() - t0)

    iters = 100_000
    t0 = time.perf_counter()
    for _ in range(iters):
        conf.get(C.DEVICE_DECODE_ENABLED)
    per_call = (time.perf_counter() - t0) / iters

    added = counts["decode.device.enabled"] * per_call
    return {"drive_best_s": round(best, 6),
            "gate_counts": counts,
            "gate_per_call_ns": round(per_call * 1e9, 1),
            "disabled_overhead_s": round(added, 9),
            "disabled_overhead_pct": round(added / best * 100, 4)}


def expansion_times(rows: int, reps: int, fails: list) -> dict:
    """Section 4: expand_runs alone, against numpy, on the default
    device."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.io import encoded as E
    from spark_rapids_tpu.ops import pallas_decode as PD

    rng = np.random.default_rng(27)
    width = 12

    def pack(vals):
        bits = (vals[:, None] >> np.arange(width)) & 1
        return np.packbits(bits.astype(np.uint8).reshape(-1),
                           bitorder="little").tobytes()

    def table(run_len: int, alternate: bool):
        runs, want = E._Runs(), []
        while runs.total < rows:
            n = min(run_len, rows - runs.total)
            if alternate and len(runs.start) % 2:
                v = int(rng.integers(0, 1 << width))
                runs.add_rle(n, v, width, 0)
                want.append(np.full(n, v, np.int64))
            else:
                vals = rng.integers(0, 1 << width, n)
                runs.add_packed(n, pack(vals), width, 0)
                want.append(vals)
        return runs, np.concatenate(want).astype(np.int32)

    dev = jax.devices()[0]
    out = {"device": f"{dev.platform}:{dev.device_kind}", "rows": rows}
    expand = jax.jit(lambda p: PD.expand_runs(p, "", rows))
    for name, run_len, alternate in (("run_per_512", 512, False),
                                     ("alternating_8", 8, True)):
        runs, want = table(run_len, alternate)
        planes = {k: jnp.asarray(v) for k, v in E._run_planes(runs).items()}
        if not np.array_equal(np.asarray(expand(planes)), want):
            fails.append(f"expand_runs differs from numpy on {name}")
        times = []
        for _ in range(max(reps, 3)):
            t0 = time.perf_counter()
            jax.block_until_ready(expand(planes))
            times.append(time.perf_counter() - t0)
        out[name] = {"runs": len(runs.start),
                     "expand_ms": round(sorted(times)[len(times) // 2]
                                        * 1e3, 3)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.02)
    ap.add_argument("--expand-rows", type=int, default=1 << 17)
    args = ap.parse_args()

    tdir = tempfile.mkdtemp(prefix="decode_smoke_")
    try:
        path = write_probe_file(tdir, args.rows)
        result = {"rows": args.rows,
                  "file_bytes": os.path.getsize(path)}
        fails = parity_and_shift(path, result)
        overhead = disabled_overhead(path, args.reps)
        result.update(overhead)
        result["expand"] = expansion_times(args.expand_rows, args.reps,
                                           fails)
        print(json.dumps(result, sort_keys=True))
        pct = overhead["disabled_overhead_pct"]
        if pct > args.tolerance * 100:
            fails.append(f"disabled-path decode overhead {pct:.3f}% exceeds "
                         f"{args.tolerance * 100:.0f}% of the probe drive")
        if fails:
            for f in fails:
                print("FAIL:", f)
            return 1
        print(f"PASS: decode on/off byte-identical across "
              f"{len(queries(path))} probe queries; encoded "
              f"{result['bytes']['device']['encoded']}B crossed the link "
              f"for {result['bytes']['device']['decoded']}B decoded; "
              f"disabled-path overhead {pct:.4f}% of the drive")
        return 0
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
