"""Time the tiny-bucket aggregate's update program at TPC-H Q1's shape.

Q1 groups 30 M lineitem rows by two dictionary codes (B = 12 buckets)
and keeps eight float sums over five distinct double inputs, three
counts and a count(*). This script builds those planes on the device
from a seed (33,554,432 rows of capacity, 29,999,232 live, as the
resident cell's one bucket) and times, each as one jitted program:

- ``masked``: one masked reduction a bucket and a state
  (`ops/groupby.bucket_agg` with ``matmul_ok``), occupancy by one
  ``any`` a bucket: the path every tiny-bucket aggregate took before;
- ``digits``: `ops/groupby.bucket_digit_agg`, the engine's path: each
  sum's exact split into two int32 planes by XLA, then one Pallas pass
  (`ops/pallas_segsum.bucket_digit_sums`) that forms the digit lanes and
  the one-hot in VMEM and contracts them on the MXU;
- ``xla_scan``: the same lanes contracted by XLA, a scan of batched bf16
  dot_generals (the alternative the kernel was measured against).

It prints one JSON line: the device, each program's median and spread
of ``--reps`` timed calls after a warm call, and each sum's largest
relative gap against ``masked``. ``--compile-only`` instead compiles
every program for a v5e described by the installed libtpu (no chip;
JAX_PLATFORMS=cpu) and prints its memory analysis and custom calls.
``--profile DIR`` adds each program's device ops by self time from a
trace of three calls; ``--tiles`` times ``digits`` at other grid steps.

Usage (on the chip): python tools/bucket_agg_timing.py [--rows 29999232]
    [--capacity 33554432] [--reps 20] [--seed 7] [--programs masked,digits]
    [--tiles 16384,65536] [--profile /tmp/trace]
"""
import argparse
import json
import os
import re
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from spark_rapids_tpu.ops import groupby as G  # noqa: E402
from spark_rapids_tpu.ops import pallas_segsum as PS  # noqa: E402
from spark_rapids_tpu.ops import radix as R  # noqa: E402

SHIP_CUT = 10471  # Q1's l_shipdate <= 1998-09-02, as a day number
SIZES = (4, 3)    # l_returnflag (3 codes + null), l_linestatus (2 + null)
B = SIZES[0] * SIZES[1]


def q1_states(qty, price, disc, tax, ship, rf, ls, nrows):
    """Q1's bucket plane, live mask and update states, as the fused
    stage builds them (the order of Q1's aggregates' state schemas)."""
    cap = qty.shape[0]
    live = (jnp.arange(cap) < nrows) & (ship <= SHIP_CUT)
    bucket = rf * SIZES[1] + ls
    dp = price * (1.0 - disc)
    ch = dp * (1.0 + tax)
    states = [("sum", qty, live), ("sum", price, live), ("sum", dp, live),
              ("sum", ch, live), ("sum", qty, live), ("count", qty, live),
              ("sum", price, live), ("count", price, live),
              ("sum", disc, live), ("count", disc, live),
              ("count_all", None, live)]
    return bucket, live, states


def masked(*planes):
    bucket, live, states = q1_states(*planes)
    occ = jnp.stack([jnp.any(live & (bucket == b)) for b in range(B)])
    outs = [G.bucket_agg(op, v if v is not None else jnp.zeros_like(live,
                         jnp.int64), valid, bucket, B, matmul_ok=True)
            for op, v, valid in states]
    return outs, occ


def digits(*planes):
    bucket, live, states = q1_states(*planes)
    return G.bucket_digit_agg(states, bucket, live, B)


def xla_scan(*planes):
    """The same lanes contracted by XLA: a scan over 2^20-row steps, each
    a batched bf16 dot_general over 2^16-row chunks (f32-exact), the
    alternative the kernel was measured against."""
    bucket, live, states = q1_states(*planes)
    sums = []
    for op, v, valid in states:
        if op == "sum" and not any(v is u for u, _ in sums):
            sums.append((v, valid))
    scales = [R._exponent_scale(jnp.max(jnp.abs(jnp.where(
        valid & jnp.isfinite(v), v, 0.0)))) * 2.0 ** 11
        for v, valid in sums]
    step, sub = 1 << 20, 1 << 16
    P = 1 + 8 * len(sums)

    def body(_, x):
        b, lv, qns = x
        lanes = [lv.astype(jnp.int32)]
        for q, n in qns:
            sp = q >= PS.SPECIAL
            lanes += PS.signed_bytes(jnp.where(sp, 0, q)) \
                + PS.signed_bytes(n) + [
                    (sp & ((q & 1) != 0)).astype(jnp.int32),
                    (sp & ((q & 2) != 0)).astype(jnp.int32)]
        pay = jnp.stack(lanes).astype(jnp.bfloat16).reshape(P, -1, sub)
        oh = (b.reshape(1, -1, sub)
              == jnp.arange(B, dtype=b.dtype)[:, None, None]
              ).astype(jnp.bfloat16)
        part = lax.dot_general(oh, pay, (((2,), (2,)), ((1,), (1,))),
                               preferred_element_type=jnp.float32)
        return None, jnp.sum(part.astype(jnp.int32), axis=0)

    qns = [G.exact_split(v, valid, sc) for (v, valid), sc in zip(sums, scales)]
    xs = (bucket.reshape(-1, step), live.reshape(-1, step),
          [(q.reshape(-1, step), n.reshape(-1, step)) for q, n in qns])
    _, parts = lax.scan(body, None, xs)
    return jnp.sum(parts.astype(jnp.int64), axis=0)


PROGRAMS = {"masked": masked, "digits": digits, "xla_scan": xla_scan}


def make_planes(cap, nrows, seed):
    """Q1's seven input planes on the default device, drawn from `seed`
    in the domains of TPC-H clause 4.2.3 (padding rows past `nrows`)."""
    k = jax.random.split(jax.random.PRNGKey(seed % (1 << 31)), 7)
    qty = jax.random.randint(k[0], (cap,), 1, 51).astype(jnp.float64)
    price = jnp.round(qty * jax.random.uniform(k[1], (cap,), jnp.float64,
                                               900.0, 2099.0), 2)
    disc = jax.random.randint(k[2], (cap,), 0, 11).astype(jnp.float64) / 100
    tax = jax.random.randint(k[3], (cap,), 0, 9).astype(jnp.float64) / 100
    ship = jax.random.randint(k[4], (cap,), 8036, 10562).astype(jnp.int32)
    rf = jax.random.randint(k[5], (cap,), 0, 3).astype(jnp.int32)
    ls = jax.random.randint(k[6], (cap,), 0, 2).astype(jnp.int32)
    return qty, price, disc, tax, ship, rf, ls, jnp.int32(nrows)


def profile(jf, planes, out_dir, calls=3):
    """The device's ops by self time (ms a call, the 12 largest) over
    `calls` traced calls."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import trace_reduce as TR
    with jax.profiler.trace(out_dir):
        for _ in range(calls):
            jax.block_until_ready(jf(*planes))
    planes_ = TR.load_xplane(out_dir)
    ops = {}
    for plane, lines in planes_.items():
        if plane.startswith(TR.DEVICE_PREFIX) and TR.OPS_LINE in lines:
            events = [(n, t, t + d) for n, t, d in lines[TR.OPS_LINE]]
            for k, v in TR._self_times(events).items():
                k = TR.short_name(k)
                ops[k] = ops.get(k, 0.0) + v * 1e3 / calls
    return dict(sorted(ops.items(), key=lambda kv: -kv[1])[:12])


def compile_only(cap):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2").devices[0]
    sh = SingleDeviceSharding(dev)
    # compile what the chip runs: Mosaic, not the CPU's Pallas interpreter
    from spark_rapids_tpu.ops import pallas_kernels as PK
    PK._interpret = lambda: False
    f64 = jax.ShapeDtypeStruct((cap,), jnp.float64, sharding=sh)
    i32 = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=sh)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)
    out = {}
    for name, fn in PROGRAMS.items():
        c = jax.jit(fn).lower(f64, f64, f64, f64, i32, i32, i32, n).compile()
        m = c.memory_analysis()
        calls = sorted(set(re.findall(r'custom_call_target="([^"]+)"',
                                      c.as_text())))
        out[name] = {"temp_gb": m.temp_size_in_bytes / 1e9,
                     "argument_gb": m.argument_size_in_bytes / 1e9,
                     "custom_calls": calls}
    print(json.dumps({"target": dev.device_kind, "capacity": cap,
                      "programs": out}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=29_999_232)
    ap.add_argument("--capacity", type=int, default=1 << 25)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--programs", default=",".join(PROGRAMS),
                    help="comma-separated programs to time (of: "
                         + ", ".join(PROGRAMS) + ")")
    ap.add_argument("--tiles", default="",
                    help="comma-separated rows a grid step to time "
                         "`digits` at besides PS.DIGIT_TILE")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--profile", default="",
                    help="directory: trace three calls of each program "
                         "and print its device ops by self time")
    a = ap.parse_args()
    if a.compile_only:
        return compile_only(a.capacity)
    dev = jax.devices()[0]
    planes = make_planes(a.capacity, a.rows, a.seed)
    report = {"device": {"platform": dev.platform,
                         "kind": dev.device_kind},
              "capacity": a.capacity, "rows": a.rows, "programs": {}}
    results = {}
    runs = [(name, PROGRAMS[name], PS.DIGIT_TILE)
            for name in a.programs.split(",")]
    runs += [(f"digits_tile{int(t)}", digits, int(t))
             for t in a.tiles.split(",") if t]
    engine_tile = PS.DIGIT_TILE
    for name, fn, tile in runs:
        PS.DIGIT_TILE = tile
        jf = jax.jit(lambda *xs, fn=fn: fn(*xs))  # a fresh trace a run
        t0 = time.perf_counter()
        results[name] = jax.block_until_ready(jf(*planes))
        first = time.perf_counter() - t0
        times = []
        for _ in range(a.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(jf(*planes))
            times.append((time.perf_counter() - t0) * 1e3)
        q = statistics.quantiles(times, n=4)
        med = statistics.median(times)
        report["programs"][name] = {"median_ms": med,
                                    "spread": (q[2] - q[0]) / med,
                                    "first_call_s": first}
        if a.profile:
            report["programs"][name]["device_ops_ms"] = profile(
                jf, planes, os.path.join(a.profile, name))
    PS.DIGIT_TILE = engine_tile
    ref_outs, ref_occ = results["masked"]
    for name in [n for n in results if n.startswith("digits")]:
        outs, occ = results[name]
        gaps = []
        for (rv, rvalid), (v, valid) in zip(ref_outs, outs):
            rv, v = np.asarray(rv, np.float64), np.asarray(v, np.float64)
            gaps.append(float(np.max(np.abs(v - rv)
                                     / np.maximum(np.abs(rv), 1e-300))))
            assert (np.asarray(rvalid) == np.asarray(valid)).all(), name
        assert (np.asarray(ref_occ) == np.asarray(occ)).all(), name
        report["programs"][name]["rel_gap_vs_masked"] = max(gaps)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
