"""The TPC-DS store-sales star's own files: the generator (row counts,
columns, domains, null rates, the same tables from the same seed), the
byte count of query 67, the readers of the counters and spans the cell
adds, the cell through the harness's CPU rehearsal, and the float32
control."""
import importlib
import json

import numpy as np
import pyarrow.compute as pc
import pytest

import run as harness
import scanbytes
import tpcds_datagen
from metrics import (agg_groups_per_query, agg_ms, expand_rows_per_query,
                     star_join_ms, window_sort_ms)
from spark_rapids_tpu.runtime import obs

CELL = "tpcds_store_hbm.q67"
SF = 0.02
SEED = 2**31 + 67
READERS = {"expand_rows_per_query": expand_rows_per_query,
           "agg_groups_per_query": agg_groups_per_query, "agg_ms": agg_ms,
           "window_sort_ms": window_sort_ms, "star_join_ms": star_join_ms}


@pytest.fixture(scope="module")
def tables():
    return tpcds_datagen.generate(SF, SEED)


def test_row_counts_are_dsdgens():
    assert tpcds_datagen.row_counts(1) == {
        "date_dim": 73_049, "store_sales": 2_880_404, "item": 18_000,
        "store": 12}
    assert tpcds_datagen.row_counts(10) == {
        "date_dim": 73_049, "store_sales": 28_800_991, "item": 102_000,
        "store": 102}
    assert tpcds_datagen.row_counts(100)["store_sales"] == 287_997_024
    three = tpcds_datagen.row_counts(3)
    assert 8_600_000 < three["store_sales"] < 8_700_000
    assert three["item"] % 6 == 0 and three["store"] % 6 == 0


def test_columns_are_the_specs(tables):
    counts = {n: t.num_columns for n, t in tables.items()}
    assert counts == {"store_sales": 23, "date_dim": 28, "item": 22,
                      "store": 29}
    assert tables["store_sales"].column_names[:4] == [
        "ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk", "ss_customer_sk"]
    assert tables["store_sales"].column_names[-1] == "ss_net_profit"
    for table in tables.values():       # scanbytes knows every type
        for field in table.schema:
            assert scanbytes.width_of(field.type) in (4, 8)
    rows = tpcds_datagen.row_counts(SF)
    assert {n: t.num_rows for n, t in tables.items()} == rows


def test_same_seed_same_tables(tables):
    again = tpcds_datagen.generate(SF, SEED)
    other = tpcds_datagen.generate(SF, 5)
    for name in tables:
        assert tables[name].equals(again[name])
        assert tables[name].num_rows == other[name].num_rows
    assert not tables["store_sales"].equals(other["store_sales"])
    assert tables["date_dim"].equals(other["date_dim"])   # the calendar


def _np(table, name):
    return table[name].combine_chunks().to_numpy(zero_copy_only=False)


def test_calendar_and_the_sales_over_it(tables):
    dd, ss = tables["date_dim"], tables["store_sales"]
    sk, seq = _np(dd, "d_date_sk"), _np(dd, "d_month_seq")
    year, moy, qoy = _np(dd, "d_year"), _np(dd, "d_moy"), _np(dd, "d_qoy")
    assert sk[0] == 2_415_022 and sk[-1] == 2_488_070
    assert (np.diff(sk) == 1).all()
    in_seq = (seq >= 1200) & (seq <= 1211)
    assert in_seq.sum() == 366 and set(year[in_seq]) == {2000}
    assert ((moy - 1) // 3 + 1 == qoy).all()
    sold = ss["ss_sold_date_sk"].drop_null().to_numpy()
    assert sold.min() >= 2_450_816 and sold.max() <= 2_452_643
    month = moy[sold - sk[0]]
    share = np.bincount(month, minlength=13)[1:] / len(sold)
    # the November and December bulge: three times a spring month
    assert 2.4 < share[10:].mean() / share[:7].mean() < 3.6
    assert 1.6 < share[7:10].mean() / share[:7].mean() < 2.4
    by_year = np.bincount(year[sold - sk[0]])[1998:2003] / len(sold)
    assert (abs(by_year - 0.2) < 0.02).all()


def test_item_hierarchy_and_keys(tables):
    it, st, ss = tables["item"], tables["store"], tables["store_sales"]
    n_item, n_store = it.num_rows, st.num_rows
    assert list(_np(it, "i_item_sk")) == list(range(1, n_item + 1))
    assert len(pc.unique(it["i_category"].drop_null())) == 10
    assert 60 <= len(pc.unique(it["i_class"].drop_null())) <= 104
    assert len(pc.unique(it["i_item_id"])) == n_item // 2
    assert len(pc.unique(st["s_store_id"])) == n_store // 2
    names = it["i_product_name"].drop_null()
    assert len(pc.unique(names)) == len(names)      # a name an item
    big = tpcds_datagen.item(102_000, np.random.default_rng(1))
    assert 600 <= len(pc.unique(big["i_brand"].drop_null())) <= 1000
    item_sk = _np(ss, "ss_item_sk")
    assert item_sk.min() >= 1 and item_sk.max() <= n_item
    assert ss["ss_item_sk"].null_count == 0
    # a ticket's lines: 8 to 16, one day and store, distinct items
    ticket = _np(ss, "ss_ticket_number")
    sizes = np.bincount(ticket)[1:-1]
    assert sizes.min() >= 8 and sizes.max() <= 16
    first = np.flatnonzero(np.r_[True, np.diff(ticket) != 0])[1]
    assert len(set(item_sk[:first])) == first
    price = _np(ss, "ss_sales_price")
    ok = ~np.isnan(price.astype(float))
    cents = price[ok].astype(float) * 100
    assert (abs(cents - np.rint(cents)) < 1e-6).all() and cents.max() <= 20000


def test_null_rates(tables):
    ss, it = tables["store_sales"], tables["item"]
    for name in ("ss_sold_date_sk", "ss_store_sk", "ss_quantity",
                 "ss_sales_price"):
        assert abs(ss[name].null_count / ss.num_rows - 0.045) < 0.006, name
    big = tpcds_datagen.item(102_000, np.random.default_rng(2))
    for name in ("i_category", "i_class", "i_brand", "i_product_name"):
        assert 0.001 < big[name].null_count / big.num_rows < 0.005, name
    assert it["i_item_sk"].null_count == 0


def test_query_bytes_by_hand(tables):
    schemas = {n: t.schema for n, t in tables.items()}
    rows = tpcds_datagen.row_counts(10)
    text = harness.load_query("q67")
    assert scanbytes.columns_named(text, schemas) == {
        "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                        "ss_quantity", "ss_sales_price"],
        "date_dim": ["d_date_sk", "d_month_seq", "d_year", "d_moy", "d_qoy"],
        "item": ["i_item_sk", "i_brand", "i_class", "i_category",
                 "i_product_name"],
        "store": ["s_store_sk", "s_store_id"]}
    # 3 keys of 8, an int of 4, a double of 8; a key and 4 ints; a key and
    # 4 strings (codes of 4); a key and a string
    assert scanbytes.query_bytes(text, schemas, rows) == \
        36 * 28_800_991 + 24 * 73_049 + 24 * 102_000 + 12 * 102
    assert scanbytes.query_rows(text, schemas, rows) == \
        28_800_991 + 73_049 + 102_000 + 102


def _record(k, seq, t0_ms):
    ms = 1_000_000 * k
    return {"seq": seq, "status": "ok", "t0_ns": t0_ms * 1_000_000,
            "wall_ns": 498_000_000,
            "phases_ns": {"parse": ms, "admit": ms, "plan": ms,
                          "execute": 20 * ms, "fetch": ms, "epilogue": ms,
                          "unspanned": ms},
            "timers_ns": {"aggTime": 3 * ms, "aggDeviceTime": 30 * ms,
                          "windowSortTime": ms, "windowSortDeviceTime": 2 * ms,
                          "joinTime": ms, "joinDeviceTime": 8 * ms,
                          "deviceWaitTime": 6 * ms},
            "counters": {"keyed_dispatches": 10, "expand_rows": 900 * k,
                         "agg_groups": 70 * k}}


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
        pytest.approx({"expand_rows_per_query": 2250.0,
                       "agg_groups_per_query": 175.0, "agg_ms": 75.0,
                       "window_sort_ms": 5.0, "star_join_ms": 20.0})


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_counter_reads_none(traced_run, name):
    """The parent's account: no such counters, no window sort's span."""
    for r in traced_run.ring:
        r["counters"] = {"keyed_dispatches": 10}
        r["timers_ns"] = {"deviceWaitTime": 1}
    assert READERS[name].read(traced_run) is None
    traced_run.trace = None
    assert READERS[name].read(traced_run) is None


def test_the_new_names_are_listed_for_the_cell_alone():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        listed = {m["name"] for m in
                  harness.metrics_of(bench, "per_layer", cell["name"])}
        assert listed & set(READERS) == (set(READERS) if cell["name"] == CELL
                                         else set())


def _drive(capsys, trace, rows=20000, seed=SEED):
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
    assert line["compared"]["rel_gap"]["value"] < 1e-13
    assert err.rstrip().splitlines()[-1] == "correct: True"
    if trace:   # the real ring behind a made-up trace of the last pass
        run = harness.Run()
        run.queries_per_pass = 1
        [last] = obs.recent_queries(1)
        run.trace = {"passes": 1, "window_s": 1e-3 + 1e-9 * last["wall_ns"]}
        values = {n: m.read(run) for n, m in READERS.items()}
        # the device timers are there and may read 0 on the CPU, whose
        # small programs are done before the host comes to read them
        assert all(v is not None and v >= 0 for v in values.values()), values
        assert values["agg_groups_per_query"] > 0
        assert values["expand_rows_per_query"] == 0   # one sort, no copies


def test_the_float32_control_is_not_correct(capsys, monkeypatch):
    """The reference in float32 in the program's place: out by the
    relative gap alone, every key and rank as they should be."""
    from spark_rapids_tpu.sql.session import TpuSession
    sf = 20000 / harness.LINEITEM_ROWS_PER_SF
    tables = tpcds_datagen.generate(sf, SEED)
    reference = importlib.import_module("reference.q67")

    class Control:
        def to_pydict(self):
            return reference.answer(tables, np.float32)

    monkeypatch.setattr(TpuSession, "sql", lambda self, text: Control())
    rc, line, _ = _drive(capsys, 0)
    assert line["correct"] is False
    assert line["compared"]["rel_gap"]["value"] > \
        3 * line["compared"]["rel_gap"]["limit"]
    assert line["compared"]["rows_wrong"]["value"] == 0


def _nudge_sum(answer):
    answer["sumsales"][0] *= 1 + 1e-7
    return answer


def _alter_rank(answer):
    answer["rk"][-1] += 1
    return answer


@pytest.mark.parametrize("alter,number", [(_nudge_sum, "rel_gap"),
                                          (_alter_rank, "rows_wrong")])
def test_an_altered_answer_is_not_correct(capsys, monkeypatch, alter,
                                          number):
    from spark_rapids_tpu.sql.session import TpuSession
    real = TpuSession.sql

    class Altered:
        def __init__(self, df):
            self.df = df

        def to_pydict(self):
            return alter(self.df.to_pydict())

    monkeypatch.setattr(TpuSession, "sql",
                        lambda self, text: Altered(real(self, text)))
    rc, line, err = _drive(capsys, 0)
    assert rc == 0 and line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"]
    assert "correct: False" in err
