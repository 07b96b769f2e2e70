"""Query 28 on the TPC-DS store-sales star (`tpcds_store_hbm.q28`): the
byte count of its text, the readers of the distinct level's span and
counter, the cell through the harness's CPU rehearsal, and its controls:
the float32 reference, one distinct count off by one and one average
nudged are each not correct."""
import importlib
import json

import numpy as np
import pytest

import run as harness
import scanbytes
import tpcds_datagen
from metrics import distinct_agg_ms, distinct_keys_per_query
from spark_rapids_tpu.runtime import obs

CELL = "tpcds_store_hbm.q28"
SEED = 2**31 + 28
ROWS = 120000          # lineitem rows of the rehearsal: some 57 K of store_sales
READERS = {"distinct_agg_ms": distinct_agg_ms,
           "distinct_keys_per_query": distinct_keys_per_query}


def test_query_bytes_by_hand():
    tables = tpcds_datagen.generate(0.002, SEED)
    schemas = {n: t.schema for n, t in tables.items()}
    rows = tpcds_datagen.row_counts(10)
    text = harness.load_query("q28")
    assert scanbytes.columns_named(text, schemas) == {
        "store_sales": ["ss_quantity", "ss_wholesale_cost", "ss_list_price",
                        "ss_coupon_amt"]}
    # an int of 4 and three doubles of 8, named once for the six bands
    assert scanbytes.query_bytes(text, schemas, rows) == 28 * 28_800_991


def _record(k, seq, t0_ms):
    ms = 1_000_000 * k
    return {"seq": seq, "status": "ok", "t0_ns": t0_ms * 1_000_000,
            "wall_ns": 498_000_000,
            "timers_ns": {"distinctAggTime": ms,
                          "distinctAggDeviceTime": 40 * ms},
            "counters": {"distinct_keys": 60_000 + k,
                         "distinct_input_rows": 1_950_000}}


@pytest.fixture
def traced_run(monkeypatch):
    ring = [_record(k, k, 10_000 + 500 * (k - 1) + 1) for k in (1, 2, 3, 4)]
    monkeypatch.setattr(obs, "recent_queries",
                        lambda n=None: ring if n is None else ring[-n:],
                        raising=False)
    run = harness.Run()
    run.queries_per_pass = 1
    run.trace = {"passes": 4, "busy_s": 1.0, "window_s": 2.0}
    run.ring = ring
    return run


def test_each_reader_takes_the_mean_over_the_traced_queries(traced_run):
    assert {n: m.read(traced_run) for n, m in READERS.items()} == \
        pytest.approx({"distinct_agg_ms": 100.0,
                       "distinct_keys_per_query": 60_002.5})


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_counter_reads_none(traced_run, name):
    """The parent's account: no distinct level, no such span or counter."""
    for r in traced_run.ring:
        r["counters"] = {"keyed_dispatches": 10}
        r["timers_ns"] = {"deviceWaitTime": 1}
    assert READERS[name].read(traced_run) is None


def test_the_new_names_are_listed_for_the_cell_alone():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        listed = {m["name"] for m in
                  harness.metrics_of(bench, "per_layer", cell["name"])}
        assert listed & set(READERS) == (set(READERS) if cell["name"] == CELL
                                         else set())


def _drive(capsys, trace, seed=SEED):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "0.5", "--trace", str(trace), "--rehearse-rows",
                       str(ROWS)])
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


def test_the_cell_rehearses_correct(capsys):
    rc, line, err = _drive(capsys, 1)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["compared"]["rows_wrong"]["value"] == 0
    assert line["compared"]["rel_gap"]["value"] < 1e-13
    assert err.rstrip().splitlines()[-1] == "correct: True"
    # the real ring behind a made-up trace of the last pass
    run = harness.Run()
    run.queries_per_pass = 1
    [last] = obs.recent_queries(1)
    run.trace = {"passes": 1, "window_s": 1e-3 + 1e-9 * last["wall_ns"]}
    values = {n: m.read(run) for n, m in READERS.items()}
    assert values["distinct_agg_ms"] is not None
    # six bands: each band's distinct prices and a NULL price of its own
    assert values["distinct_keys_per_query"] > 6
    assert last["counters"]["distinct_input_rows"] >= \
        values["distinct_keys_per_query"]


def _control(monkeypatch, answer):
    from spark_rapids_tpu.sql.session import TpuSession

    class Control:
        def __init__(self, df):
            self.df = df

        def to_pydict(self):
            return answer(self.df)

    real = TpuSession.sql
    monkeypatch.setattr(TpuSession, "sql",
                        lambda self, text: Control(real(self, text)))


def test_the_float32_control_is_not_correct(capsys, monkeypatch):
    """The reference in float32 in the program's place: out by the
    relative gap alone, every count as it should be."""
    sf = ROWS / harness.LINEITEM_ROWS_PER_SF
    tables = tpcds_datagen.generate(sf, SEED)
    reference = importlib.import_module("reference.q28")
    _control(monkeypatch, lambda df: reference.answer(tables, np.float32))
    rc, line, _ = _drive(capsys, 0)
    assert line["correct"] is False
    assert line["compared"]["rel_gap"]["value"] > \
        3 * line["compared"]["rel_gap"]["limit"]
    assert line["compared"]["rows_wrong"]["value"] == 0


def _distinct_count_off_by_one(answer):
    answer["B3_CNTD"][0] += 1
    return answer


def _nudge_average(answer):
    answer["B5_LP"][0] *= 1 + 1e-9
    return answer


@pytest.mark.parametrize("alter,number",
                         [(_distinct_count_off_by_one, "rows_wrong"),
                          (_nudge_average, "rel_gap")])
def test_an_altered_answer_is_not_correct(capsys, monkeypatch, alter,
                                          number):
    _control(monkeypatch, lambda df: alter(df.to_pydict()))
    rc, line, err = _drive(capsys, 0)
    assert rc == 0 and line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"]
    assert "correct: False" in err
