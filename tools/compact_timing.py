"""Time the live-row compaction's positions kernel, old form against new.

`ops/kernels._compact_indices` gives the positions of a mask's set rows,
in order. It used to scatter every row of the capacity (the dead ones to
a dump slot); it now packs the mask into 32-row words and scatters one
entry a word (see its docstring). This script keeps the old form inline
and times both, each as one jitted program, at three shapes:

- ``q28_band``: 33,554,432 rows of capacity, 28,800,991 live, some 300 K
  set, into 524,288 slots (TPC-DS query 28's distinct level, a band);
- ``q67_probe``: the same capacity, some 6 M set, into 8,388,608 slots
  (query 67's star-join probe cut of store_sales);
- ``boundary``: 524,288 rows, 10 K set, into 524,288 slots (the group
  keys' boundary compaction in `ops/groupby.gather_group_keys`).

The masks are drawn on the device from ``--seed``. It prints one JSON
line: the device, each shape's median and spread (interquartile range
over the median) of ``--reps`` timed calls after a warm call, for each
form, and whether the two forms' outputs are equal.

Usage (on the chip): python tools/compact_timing.py [--reps 20] [--seed 7]
"""
import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_enable_x64", True)

from spark_rapids_tpu.ops import kernels as K  # noqa: E402
from spark_rapids_tpu.ops.pallas_decode import _cumsum  # noqa: E402

#: name -> (capacity, live rows, set rows wanted, output slots)
SHAPES = {
    "q28_band": (1 << 25, 28_800_991, 300_000, 1 << 19),
    "q67_probe": (1 << 25, 28_800_991, 6_000_000, 1 << 23),
    "boundary": (1 << 19, 1 << 19, 10_000, 1 << 19),
}


@jax.jit(static_argnums=(2,))
def scatter_every_row(mask, num_rows, out_cap):
    """The form before 32-row words: one scatter update a row."""
    cap = mask.shape[0]
    mask = mask & (jnp.arange(cap) < num_rows)
    pos = _cumsum(mask.astype(jnp.int32)) - 1
    scatter_to = jnp.where(mask, pos, out_cap)
    out = jnp.full(out_cap + 1, -1, jnp.int32)
    out = out.at[scatter_to].set(jnp.arange(cap, dtype=jnp.int32),
                                 mode="drop")
    return out[:out_cap]


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t) * 1e3)
    q = statistics.quantiles(times, n=4)
    med = statistics.median(times)
    return {"median_ms": med, "spread": (q[2] - q[0]) / med}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "reps": args.reps, "shapes": {}}
    key = jax.random.PRNGKey(args.seed)
    equal = True
    for name, (cap, live, want, out_cap) in SHAPES.items():
        key, k = jax.random.split(key)
        mask = jax.random.bernoulli(k, want / live, (cap,))
        n = jnp.int64(live)
        same = bool(jnp.array_equal(scatter_every_row(mask, n, out_cap),
                                    K._compact_indices(mask, n, out_cap)))
        equal &= same
        report["shapes"][name] = {
            "capacity": cap, "out_cap": out_cap,
            "set": int(jnp.sum(mask[:live])),
            "old": timed(scatter_every_row, (mask, n, out_cap), args.reps),
            "new": timed(K._compact_indices, (mask, n, out_cap), args.reps),
            "equal": same}
    report["equal"] = equal
    print(json.dumps(report))
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
