"""The harness end to end on the CPU rehearsal flag, the statistics on fixed
pass times, and `correct` coming out false for the control and for a fault."""
import json

import numpy as np
import pytest

import datagen
import run as harness
from metrics import latency_p50, latency_p90, quantile, throughput

ROWS = 6000
SEED = 2**31 + 7
CELLS = [c["name"] for c in harness.load_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]]


def drive(capsys, cell, trace=0, seconds=0.5):
    rc = harness.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       str(seconds), "--trace", str(trace),
                       "--rehearse-rows", str(ROWS)])
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(capsys, cell, trace):
    rc, line, err = drive(capsys, cell, trace)
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]      # no breakdown on a CPU
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"        # never passes for a chip
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    kind = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in harness.metrics_of(bench, kind, cell)}
    assert set(line["metrics"]) <= listed
    if not trace:
        assert set(line["metrics"]) == listed
        assert all(m["value"] > 0 for m in line["metrics"].values())
    else:
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert "query_hbm_roofline" not in line["metrics"]   # nothing to read
    # the window ends on a completed pass: whole passes only
    per_pass = len(harness.load_traffic(cell.split(".")[-1])["queries"])
    assert line["attempted"] % per_pass == 0
    assert line["compared"]["answers"]["value"] == line["attempted"]
    assert err.rstrip().splitlines()[-1] == "correct: True"


def test_statistics_on_fixed_pass_times():
    r = harness.Run()
    r.pass_s = [0.1 * k for k in range(1, 12)]          # 0.1 .. 1.1 s
    r.attempted, r.failed, r.window_s = 22, 2, 8.0
    assert latency_p50.read(r) == pytest.approx(600.0)
    assert latency_p90.read(r) == pytest.approx(1000.0)
    assert throughput.read(r) == pytest.approx(2.5)      # the failed do not count
    assert quantile([1.0, 2.0, 3.0, 10.0], 0.5) == pytest.approx(2.5)
    assert quantile([4.0], 0.9) == 4.0


class _Altered:
    def __init__(self, df, alter):
        self.df, self.alter = df, alter

    def to_pydict(self):
        return self.alter(self.df.to_pydict())


def _break_sql(monkeypatch, alter):
    from spark_rapids_tpu.sql.session import TpuSession
    real = TpuSession.sql
    monkeypatch.setattr(TpuSession, "sql",
                        lambda self, text: _Altered(real(self, text), alter))


def _nudge_float(answer):
    col = "revenue" if "revenue" in answer else "sum_charge"
    answer[col][0] *= 1 + 1e-7
    return answer


def _alter_key(answer):
    col = "l_orderkey" if "l_orderkey" in answer else "count_order"
    if col in answer:
        answer[col][-1] += 1
    else:
        answer["revenue"].append(0.0)                    # a surplus row
    return answer


@pytest.mark.parametrize("alter,number", [(_nudge_float, "rel_gap"),
                                          (_alter_key, "rows_wrong")])
@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_not_correct(capsys, monkeypatch, cell, alter,
                                          number):
    _break_sql(monkeypatch, alter)
    rc, line, err = drive(capsys, cell)
    assert rc == 0 and line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"]
    assert "correct: False" in err


@pytest.mark.parametrize("cell", CELLS)
def test_the_float32_control_is_not_correct(capsys, monkeypatch, cell):
    """The reference in float32 in the program's place: the precision below
    the float64 that the configurations state."""
    import importlib
    from spark_rapids_tpu.sql.session import TpuSession
    sf = ROWS / harness.LINEITEM_ROWS_PER_SF
    tables = datagen.generate(sf, SEED)
    names = {harness.load_query(q): q for q in ("q1", "q3", "q6")}

    class Control:
        def __init__(self, q):
            self.q = q

        def to_pydict(self):
            return importlib.import_module(f"reference.{self.q}").answer(
                tables, np.float32)

    monkeypatch.setattr(TpuSession, "sql",
                        lambda self, text: Control(names[text]))
    rc, line, _ = drive(capsys, cell)
    assert line["correct"] is False
    assert line["compared"]["rel_gap"]["value"] > \
        3 * line["compared"]["rel_gap"]["limit"]
    assert line["compared"]["rows_wrong"]["value"] == 0
