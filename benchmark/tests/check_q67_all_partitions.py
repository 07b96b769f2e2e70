"""By hand, on the chip: query 67's ranked rollup BEFORE `rk <= 100`, the
ORDER BY and the LIMIT, against the plain reference, every window partition.

The cell's `correct` compares the hundred rows the query returns. It orders
with nulls first, so they all lie in the NULL i_category partition (the
grand total, and the levels of the few items whose category is null): a
wrong sum, rank or key in one of the ten real categories reaches that
comparison only through the grand total. This runs the text of
queries/q67.sql without its outer filter, sort and limit at the
configuration's size, and holds every group of every level to
reference.q67.rollup and its ranks: keys exactly, sums to the
configuration's rel_gap, and every rank inside what the reference's sums
allow when each may be off by the gap that was read (the query multiplies
and adds doubles where the reference carries cents: two groups of equal
cents tie there and need not here; the cell's generator keeps the hundred
first sums of a partition apart, not the six hundred thousand behind
them). Groups are matched by their keys (the query does not select the
grouping id, so the grand total and the group of items without a category
are both eight NULLs: the sum tells those apart).

    python3 benchmark/tests/check_q67_all_partitions.py --seed 3100000601

One JSON line on stdout; exit 0 only if nothing differs.
"""
import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run as harness  # noqa: E402

CONFIG = "tpcds_store_hbm"


def inner_query() -> str:
    """dw2 of queries/q67.sql: the rollup under the ranked window."""
    text = harness.load_query("q67")
    return text.split("from (", 1)[1].rsplit(") dw2", 1)[0]


def engine_codes(got, reference, names):
    """The engine's key columns as the reference's codes (a null -1)."""
    import pyarrow.compute as pc
    out = []
    for name in reference.KEYS:
        col = got[name].combine_chunks()
        null = np.asarray(col.is_null())
        if name in names:
            code_of = {v: c for c, v in names[name].items()}
            enc = col.dictionary_encode()
            to_ref = np.array([code_of[v] for v in
                               enc.dictionary.to_pylist()] + [-1], np.int64)
            codes = to_ref[np.asarray(enc.indices.fill_null(
                len(to_ref) - 1))]
        else:
            codes = np.asarray(pc.fill_null(col, -1)).astype(np.int64)
        out.append(np.where(null, -1, codes))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--scale-factor", type=float, default=None,
                    help="the configuration's unless given (a CPU try)")
    args = ap.parse_args(argv)
    config = harness.load_json(HERE, "configs", f"{CONFIG}.json")
    datagen = importlib.import_module(config["generator"])
    reference = importlib.import_module("reference.q67")
    import jax
    from spark_rapids_tpu.sql.session import TpuSession
    sf = args.scale_factor or config["scale_factor"]
    tables = datagen.generate(sf, args.seed)
    sess = TpuSession()
    harness.place(sess, config, tables, None, datagen)
    t0 = time.perf_counter()
    got = sess.sql(inner_query()).collect()
    query_s = time.perf_counter() - t0
    del sess

    keys, sums, names, _ = reference.rollup(tables)
    want = [np.maximum(k, -1) for k in keys] + [reference.ranks(keys, sums)]
    have = engine_codes(got, reference, names) + [
        np.asarray(got["rk"].combine_chunks()).astype(np.int64)]
    have_sums = np.asarray(got["sumsales"].combine_chunks())
    line = {"seed": args.seed, "scale_factor": sf,
            "device": jax.devices()[0].device_kind,
            "query_s": query_s, "rows": int(len(have_sums)),
            "rows_reference": int(len(sums))}
    if len(have_sums) == len(sums):
        # group against group: both sides by their keys (the sum parts the
        # few tuples of eight NULLs)
        w = np.lexsort([sums] + want[:-1][::-1])
        h = np.lexsort([have_sums] + have[:-1][::-1])
        keys_wrong = np.zeros(len(sums), np.bool_)
        for a, b in zip(want[:-1], have[:-1]):
            keys_wrong |= a[w] != b[h]
        ref, got_sums = sums[w], have_sums[h]
        gap = float((np.abs(got_sums - ref)
                     / np.maximum(np.abs(ref), 1e-300)).max())
        # a rank is one more than the groups of the partition with a
        # greater sum. The query multiplies and adds doubles where the
        # reference carries cents, so sums agree to `gap` and two groups
        # of equal cents need not tie: a rank is held to what the
        # reference's sums allow when each may be off by `gap`
        part, rk_ref, rk = want[0][w], want[-1][w], have[-1][h]
        up, down = (1 + gap) / (1 - gap), (1 - gap) / (1 + gap)
        least = np.empty(len(ref), np.int64)
        most = np.empty(len(ref), np.int64)
        sizes = []
        for p in np.unique(part):
            rows = np.flatnonzero(part == p)
            asc = np.sort(ref[rows])
            sizes.append(len(rows))
            least[rows] = 1 + len(rows) - np.searchsorted(
                asc, ref[rows] * up, side="right")
            most[rows] = len(rows) - np.searchsorted(
                asc, ref[rows] * down, side="left")
        rank_wrong = (rk < least) | (rk > most)
        decided = least == most
        line.update(
            keys_wrong=int(keys_wrong.sum()), rel_gap=gap,
            ranks_wrong=int(rank_wrong.sum()),
            ranks_the_reference_decides=int(decided.sum()),
            ranks_equal_to_the_reference=int((rk == rk_ref).sum()),
            partitions=len(sizes),
            partition_rows=[int(min(sizes)), int(max(sizes))],
            rows_outside_the_null_partition=int((part >= 0).sum()),
            largest_rank=int(rk_ref.max()))
        line["rows_wrong"] = int((keys_wrong | rank_wrong).sum())
        line["first_wrong"] = [
            {"keys": [int(k[w][i]) for k in want[:-1]], "sum": float(ref[i]),
             "got_sum": float(got_sums[i]), "rk": int(rk[i]),
             "allowed": [int(least[i]), int(most[i])]}
            for i in np.flatnonzero(keys_wrong | rank_wrong)[:5]]
        ok = line["rows_wrong"] == 0 and \
            gap <= config["limits"]["rel_gap"]
    else:
        ok = False
    line["all_partitions_correct"] = ok
    print(json.dumps(line), flush=True)
    out_dir = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "q67_all_partitions.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
