"""The text deployment's own files: the generator (row counts, length
ranges, share of distinct values, the same tables from the same seed, the
share of orders Q13's words match), the engine's byte counter against the
generated column, the six readers, the cell through the harness's CPU
rehearsal, and the control: one comment altered in the reference's copy
alone is not correct."""
import importlib
import json

import pyarrow as pa
import pyarrow.compute as pc
import pytest

import datagen
import datagen_text
import run as harness
import textbytes
from metrics import (hash_agg_ms, join_rows_per_query, like_hbm_roofline,
                     outer_join_ms, string_match_mb_per_query,
                     string_match_ms)
from spark_rapids_tpu.runtime import obs

CELL = "tpch_sf5_text_hbm.q13"
SF = 0.01
SEED = 2**31 + 13
READERS = {"string_match_ms": string_match_ms,
           "string_match_mb_per_query": string_match_mb_per_query,
           "like_hbm_roofline": like_hbm_roofline,
           "outer_join_ms": outer_join_ms,
           "join_rows_per_query": join_rows_per_query,
           "hash_agg_ms": hash_agg_ms}


@pytest.fixture(scope="module")
def tables():
    return datagen_text.generate(SF, SEED)


def test_rows_and_other_columns_are_datagens(tables):
    base = datagen.generate(SF, SEED)
    text = {c for _t, c, _lo, _hi in datagen_text.TEXT_COLUMNS}
    for name, table in tables.items():
        assert table.num_rows == datagen.row_counts(SF)[name]
        assert table.column_names == base[name].column_names
        for column in table.column_names:
            if column not in text:
                assert table[column].equals(base[name][column]), column


def test_text_columns_are_the_specs(tables):
    for table, column, lo, hi in datagen_text.TEXT_COLUMNS:
        col = tables[table][column]
        assert col.type == pa.string() and col.null_count == 0
        length = pc.binary_length(col)
        assert pc.min(length).as_py() == lo and pc.max(length).as_py() == hi
        distinct = len(pc.unique(col)) / len(col)
        # a short substring of a small vocabulary repeats (dbgen's too)
        assert distinct > (0.99 if lo >= 19 else 0.9), (column, distinct)
        # no dictionary on upload: more values than half the rows
        assert len(pc.unique(col)) > max(64, len(col) // 2)


def test_same_seed_same_tables(tables):
    again = datagen_text.generate(SF, SEED)
    other = datagen_text.generate(SF, 5)
    for name in tables:
        assert tables[name].equals(again[name])
        assert tables[name].num_rows == other[name].num_rows
    assert not tables["orders"]["o_comment"].equals(
        other["orders"]["o_comment"])


def test_nothing_is_planted_and_some_orders_match(tables):
    reference = importlib.import_module("reference.q13")
    comments = tables["orders"]["o_comment"].to_pylist()
    share = 1 - reference.kept(comments).mean()
    assert 0.0003 < share < 0.005      # 0.12% at SF1 (the config's assumed)
    words = " ".join(comments).split()
    assert 0.5 < words.count("special") / words.count("furious") < 2
    assert 0.5 < words.count("requests") / words.count("deposits") < 2


def _record(k, seq, t0_ms):
    ms = 1_000_000 * k
    return {"seq": seq, "status": "ok", "t0_ns": t0_ms * 1_000_000,
            "wall_ns": 498_000_000,
            "phases_ns": {"parse": ms, "admit": ms, "plan": ms,
                          "execute": 20 * ms, "fetch": ms, "epilogue": ms,
                          "unspanned": ms},
            "timers_ns": {"stringMatchTime": ms, "joinTime": ms,
                          "stringMatchDeviceTime": 40 * ms,
                          "joinDeviceTime": 8 * ms, "deviceWaitTime": 6 * ms,
                          "aggDeviceTime": 30 * ms},
            "counters": {"keyed_dispatches": 10,
                         "string_match_bytes": 81_900_000 * k,
                         "join_output_rows": 750 * k}}


@pytest.fixture
def traced_run(monkeypatch):
    ring = [_record(k, k, 10_000 + 500 * (k - 1) + 1) for k in (1, 2, 3, 4)]
    monkeypatch.setattr(obs, "recent_queries",
                        lambda n=None: ring if n is None else ring[-n:],
                        raising=False)
    run = harness.Run()
    run.queries_per_pass = 1
    run.peaks = {"hbm_bytes_per_s": 819e9}
    run.trace = {"passes": 4, "busy_s": 1.0, "window_s": 2.0}
    run.ring = ring
    return run


def test_each_reader_takes_the_mean_over_the_traced_queries(traced_run):
    # 204.75 MB a query over 819 GB/s is 0.25 ms, of 100 ms: 0.25 %
    assert {n: m.read(traced_run) for n, m in READERS.items()} == \
        pytest.approx({"string_match_ms": 100.0,
                       "string_match_mb_per_query": 204.75,
                       "like_hbm_roofline": 0.25, "outer_join_ms": 20.0,
                       "join_rows_per_query": 1875.0,
                       "hash_agg_ms": 75.0})
    assert textbytes.match_bytes(204_750_000) == 204_750_000.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_counter_reads_none(traced_run, name):
    """The parent's account: no such counter, no such timer."""
    for r in traced_run.ring:
        r["counters"] = {"keyed_dispatches": 10}
        r["timers_ns"] = {"deviceWaitTime": 1}
    assert READERS[name].read(traced_run) is None
    traced_run.trace = None
    assert READERS[name].read(traced_run) is None


def test_no_stamp_no_roofline(traced_run):
    for r in traced_run.ring:       # the CPU: done before the host looks
        r["timers_ns"]["stringMatchDeviceTime"] = 0
    assert like_hbm_roofline.read(traced_run) is None


def test_the_new_names_are_listed_for_the_cell_alone():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        listed = {m["name"] for m in
                  harness.metrics_of(bench, "per_layer", cell["name"])}
        assert listed & set(READERS) == (set(READERS) if cell["name"] == CELL
                                         else set())


def _drive(capsys, trace, rows=40000, seed=SEED):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "0.5", "--trace", str(trace), "--rehearse-rows",
                       str(rows)])
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_correct(capsys, trace):
    rc, line, err = _drive(capsys, trace)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["compared"]["rows_wrong"]["value"] == 0
    assert line["compared"]["rel_gap"]["value"] == 0    # no float in it
    assert err.rstrip().splitlines()[-1] == "correct: True"
    if trace:   # the real ring behind a made-up trace of the last pass
        run = harness.Run()
        run.queries_per_pass = 1
        run.peaks = {"hbm_bytes_per_s": 819e9}
        [last] = obs.recent_queries(1)
        run.trace = {"passes": 1, "window_s": 1e-3 + 1e-9 * last["wall_ns"]}
        values = {n: m.read(run) for n, m in READERS.items()}
        sf = 40000 / harness.LINEITEM_ROWS_PER_SF
        orders = datagen_text.generate(sf, SEED)["orders"]
        # the counter is the generated column to the byte: its strings'
        # bytes and one 4-byte offset a row and one more
        assert values["string_match_mb_per_query"] * 1e6 == pytest.approx(
            pc.sum(pc.binary_length(orders["o_comment"])).as_py()
            + 4 * (orders.num_rows + 1))
        assert values["join_rows_per_query"] == \
            datagen.row_counts(sf)["customer"]
        # the device timers are there and may read 0 on the CPU, whose
        # small programs are done before the host comes to read them
        assert values["string_match_ms"] >= 0 and values["outer_join_ms"] >= 0
        assert values["hash_agg_ms"] >= 0


def test_an_altered_comment_in_the_references_copy_is_not_correct(
        capsys, monkeypatch):
    """The control in the float32 control's place (Q13's answer holds no
    float): one kept order's comment rewritten to hold the two words, in
    the reference's copy alone, moves one customer from a count to the one
    below it, and the run is not correct."""
    reference = importlib.import_module("reference.q13")
    real = reference.answer

    def altered(tables, *a):
        orders = tables["orders"]
        comments = orders["o_comment"].to_pylist()
        at = int(reference.kept(comments).argmax())     # a kept order
        comments[at] = "a special kind of requests"
        tables = dict(tables, orders=orders.set_column(
            orders.schema.get_field_index("o_comment"), "o_comment",
            pa.array(comments, pa.string())))
        return real(tables, *a)

    monkeypatch.setattr(reference, "answer", altered)
    rc, line, err = _drive(capsys, 0)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["rows_wrong"]["value"] >= 2
    assert "correct: False" in err
