"""A cache placed over a mesh, and the scan-filter-partial-aggregate run
where the shards live (ISSUE 28): placement, one vocabulary a string
column, the sharded aggregate against the benchmark's plain reference
(TPC-H Q1 and Q6 on seeded data), the engine taking the mesh it is given,
and the spans and counters the phase account carries.

The suite conftest forces 8 virtual CPU devices, so the four-device mesh
here is the real shard_map path; the degenerate one-device mesh is the
same machinery over one device.
"""
import importlib
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

import jax

from spark_rapids_tpu import config as C
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.parallel import mesh as MESH
from spark_rapids_tpu.runtime import compile_cache, obs
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSession

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

ON = C.MULTICHIP_ENABLED.key
DEVICES = C.MULTICHIP_DEVICES.key


#: a group for every lineitem row: its partial states are more rows than
#: an exchange passes on unexchanged (4 x spark.rapids.shuffle
#: .coalesceTinyRows), so its merge rides the all_to_all
WIDE_GROUP_BY = ("select l_orderkey, l_linenumber, sum(l_quantity) as q "
                 "from lineitem group by l_orderkey, l_linenumber")


def _mesh_session(n):
    return TpuSession({ON: "true", DEVICES: n})


def _find(node, pred):
    if pred(node):
        return node
    for c in node.children:
        hit = _find(c, pred)
        if hit is not None:
            return hit
    return None


def _device_of(batch):
    c = batch.columns[0]
    arr = c.data["codes"] if c.is_dict else c.data
    return next(iter(arr.devices()))


@pytest.fixture(scope="module")
def tpch():
    import datagen
    import run as harness
    tables = datagen.generate(20_000 / 6_000_000, 28)
    return tables, {n: harness.plain_strings(t) for n, t in tables.items()}


def _place(sess, plain, parts=None):
    frames = {}
    for name, table in plain.items():
        df = sess.create_dataframe(table, parts).cache() if parts \
            else sess.create_dataframe(table).cache()
        df.count()
        sess.create_or_replace_temp_view(name, df)
        frames[name] = df
    return frames


def _agrees(got, want, rel=1e-11):
    assert set(got) == set(want)
    for name, values in want.items():
        assert len(got[name]) == len(values), name
        for g, w in zip(got[name], values):
            if isinstance(w, float):
                assert abs(g - w) <= rel * max(abs(w), 1e-300), (name, g, w)
            else:
                assert g == w, (name, g, w)


# -- (a) Q1 and Q6 over a placed cache equal the plain reference -------------

@pytest.mark.parametrize("devices,parts", [(1, 2), (4, None), (4, 8)])
@pytest.mark.parametrize("query", ["q1", "q6"])
def test_sharded_aggregate_answers_the_plain_reference(tpch, devices, parts,
                                                       query):
    import run as harness
    tables, plain = tpch
    sess = _mesh_session(devices)
    frames = _place(sess, plain, parts)
    got = sess.sql(harness.load_query(query)).to_pydict()
    _agrees(got, importlib.import_module(f"reference.{query}").answer(tables))
    plan = sess._last_exec.tree_string()
    assert f"[sharded n={devices}]" in plan, plan
    agg = _find(sess._last_exec, lambda n: getattr(n, "shard_over", 0))
    assert agg is not None and agg._shard_out, "the update ran unsharded"
    nparts = parts or devices
    assert agg.metrics.metric("shardWaves").value == nparts // devices
    # each partition's arrays live on their own device
    mat = frames["lineitem"].plan.materialized
    assert len(mat) == nparts
    devs = jax.devices()
    for p, part in enumerate(mat):
        assert _device_of(part[0].get_batch()) == devs[p % devices]


def test_fused_chain_over_a_placed_cache_runs_in_place():
    sess = _mesh_session(4)
    rows = 4000
    df = sess.create_dataframe({
        "k": [i % 7 for i in range(rows)],
        "s": ["abc"[i % 3] for i in range(rows)],
        "v": [float(i) for i in range(rows)]}).cache()
    q = df.filter(col("k") != lit(3)).select(
        col("k"), (col("v") * lit(2.0)).alias("v2"))
    got = q.collect().sort_by([("v2", "ascending")])
    assert "ShardedStageExec" in sess._last_exec.tree_string()
    want = [2.0 * i for i in range(rows) if i % 7 != 3]
    assert got["v2"].to_pylist() == want
    rec = obs.recent_queries(1)[0]
    assert rec["counters"]["shard_waves"] == 1
    assert rec["counters"]["mesh_put_bytes"] == 0  # "s" never an operand


# -- (b) one vocabulary a string column across the shards --------------------

def test_one_vocabulary_across_shards_with_nulls_and_an_empty_partition():
    sess = _mesh_session(4)
    # 7 rows over 4 partitions: 2, 2, 2, 1; partition 3 holds only a null
    words = ["pear", "apple", "fig", "pear", "apple", "kiwi", None]
    df = sess.create_dataframe(pa.table({
        "w": pa.array(words, pa.string()),
        "i": pa.array(range(7), pa.int64())}), 4).cache()
    df.count()
    empty = sess.create_dataframe(pa.table({
        "w": pa.array(["a", "b"], pa.string())}), 4).cache()
    empty.count()  # partitions 2 and 3 hold no row
    for frame, expect in ((df, words), (empty, ["a", "b"])):
        shards = [part[0].get_batch() for part in frame.plan.materialized]
        cols = [b.columns[0] for b in shards]
        assert all(c.is_dict for c in cols)
        vocabs = []
        for c in cols:
            off = np.asarray(c.data["dict_offsets"])
            raw = bytes(np.asarray(c.data["dict_bytes"]))
            vocabs.append([raw[off[k]:off[k + 1]].decode()
                           for k in range(len(off) - 1)])
        assert all(v == vocabs[0] for v in vocabs), vocabs
        assert len(set(vocabs[0])) == len(vocabs[0])
        assert len({b.capacity for b in shards}) == 1
        assert len({c.validity is None for c in cols}) == 1
        seen = []
        for b, c in zip(shards, cols):
            codes = np.asarray(c.data["codes"])[:int(b.num_rows)]
            valid = np.ones(len(codes), bool) if c.validity is None \
                else np.asarray(c.validity)[:len(codes)]
            seen += [vocabs[0][k] if ok else None
                     for k, ok in zip(codes, valid)]
        assert seen == expect  # equal strings, equal codes, in every shard
    assert sorted(x for x in df.to_pydict()["w"] if x) == \
        sorted(x for x in words if x)
    got = df.group_by("w").agg(F.count(col("i")).alias("n")).to_pydict()
    assert dict(zip(got["w"], got["n"])) == {
        "pear": 2, "apple": 2, "fig": 1, "kiwi": 1, None: 1}


def test_unpersist_drops_every_shard(tpch):
    _tables, plain = tpch
    sess = _mesh_session(4)
    df = sess.create_dataframe(plain["customer"]).cache()
    n = df.count()
    from spark_rapids_tpu.runtime.memory import get_spill_framework
    fw = get_spill_framework()
    assert fw.device_bytes_held() > 0 and len(df.plan.materialized) == 4
    df.unpersist()
    assert df.plan.materialized is None and fw.device_bytes_held() == 0
    assert df.count() == n  # materializes again, placed again
    assert len(df.plan.placed_on) == 4


def test_a_spilled_shard_comes_back_to_its_own_chip(tpch):
    _tables, plain = tpch
    sess = _mesh_session(4)
    df = sess.create_dataframe(plain["orders"]).cache()
    df.count()
    handle = df.plan.materialized[2][0].handle
    assert handle.spill_to_host() > 0 and handle.tier == "host"
    assert _device_of(handle.get()) == jax.devices()[2]


# -- (c) the engine takes the mesh it is given -------------------------------

class _Dev:
    def __init__(self, platform):
        self.platform = platform


def test_unset_multichip_follows_the_accelerators(monkeypatch):
    conf = TpuSession().conf
    assert conf.get(C.MULTICHIP_ENABLED) is None
    assert not MESH.multichip_on(conf)  # 8 virtual CPU devices: a harness
    monkeypatch.setattr(MESH.jax, "devices", lambda *a: [_Dev("tpu")] * 4)
    assert MESH.multichip_on(conf) and MESH.multichip_devices(conf) == 4
    monkeypatch.setattr(MESH.jax, "devices", lambda *a: [_Dev("tpu")])
    assert not MESH.multichip_on(conf)
    assert MESH.multichip_on(TpuSession({ON: "true"}).conf)
    monkeypatch.setattr(MESH.jax, "devices", lambda *a: [_Dev("tpu")] * 4)
    assert not MESH.multichip_on(TpuSession({ON: "false"}).conf)


@pytest.mark.parametrize("query", ["q1", "q6"])
def test_one_device_default_plans_as_multichip_off(tpch, query):
    import run as harness
    _tables, plain = tpch
    text = harness.load_query(query)
    plans, fps, parts = [], [], []
    for overrides in ({}, {ON: "false"}):
        sess = TpuSession(overrides)
        frames = _place(sess, {"lineitem": plain["lineitem"]})
        sess.sql(text).to_pydict()
        plans.append(sess._last_exec.tree_string())
        fps.append(compile_cache._fp_of(sess.conf))
        parts.append(len(frames["lineitem"].plan.materialized))
        assert frames["lineitem"].plan.placed_on == ()
    assert plans[0] == plans[1] and "sharded" not in plans[0]
    assert fps[0] == fps[1] and "mesh" not in fps[0]
    assert parts == [1, 1]
    on = TpuSession({ON: "true", DEVICES: 1})
    assert compile_cache._fp_of(on.conf)[-3:] == ("mesh", MESH.PART_AXIS, 1)


# -- (d) spans and counters reach the phase account --------------------------

def test_phase_account_carries_the_mesh(tpch):
    import run as harness
    _tables, plain = tpch
    sess = _mesh_session(4)
    _place(sess, {"lineitem": plain["lineitem"]})
    sess.sql(harness.load_query("q1")).to_pydict()
    rec = obs.recent_queries(1)[0]
    assert rec["counters"]["shard_waves"] == 1
    assert rec["counters"]["mesh_put_bytes"] == 0  # consumed in place
    for timer in ("shardDispatchTime", "shardReadbackTime"):
        assert rec["timers_ns"][timer] > 0, timer
    # Q1's sixteen rows of partial state are not exchanged (ISSUE 37)
    assert rec["counters"]["exchange_bypassed"] == 1
    assert "iciExchangeTime" not in rec["timers_ns"]
    assert rec["mesh"]["devices"] == 4
    (rows,) = rec["mesh"]["shard_rows"]
    assert len(rows) == 4 and sum(rows) == plain["lineitem"].num_rows
    # a group a row is more than a coalesced batch holds: the merge of
    # those states still rides the all_to_all
    sess.sql(WIDE_GROUP_BY).to_pydict()
    wide = obs.recent_queries(1)[0]
    assert wide["counters"]["shard_waves"] == 1
    assert wide["counters"]["exchange_bypassed"] == 0
    assert wide["timers_ns"]["iciExchangeTime"] > 0
    # a host-packed wave (no cache under it) counts what it puts
    data = {"g": [i % 5 for i in range(4000)],
            "v": [float(i) for i in range(4000)]}
    (sess.create_dataframe(data, num_partitions=4)
     .filter(col("g") != lit(0))
     .select(col("g"), (col("v") * lit(3.0)).alias("v3")).collect())
    packed = obs.recent_queries(1)[0]
    assert packed["counters"]["shard_waves"] == 1
    assert packed["counters"]["mesh_put_bytes"] > 0
    # a session with no mesh says nothing about one
    plain_sess = TpuSession()
    plain_sess.create_dataframe(data).filter(col("g") != lit(0)).collect()
    off = obs.recent_queries(1)[0]
    assert "mesh" not in off and off["counters"]["shard_waves"] == 0


def test_an_operator_that_knows_no_mesh_still_answers(tpch):
    """A join over placed tables computes on the default device: the
    shards are moved there, counted, and the answer is the unplaced one."""
    import run as harness
    tables, plain = tpch
    text = harness.load_query("q3")
    want = importlib.import_module("reference.q3").answer(tables)
    sess = _mesh_session(4)
    _place(sess, plain)
    _agrees(sess.sql(text).to_pydict(), want)
    assert obs.recent_queries(1)[0]["counters"]["mesh_put_bytes"] > 0


def test_a_profiler_capture_holds_the_sharded_spans(tpch, tmp_path):
    """The dispatch of the SPMD program and the read-back of its partial
    states are spans through trace.py's one resolver: a running
    jax.profiler capture alone names them `rapids.<Exec>.<metric>`."""
    import glob

    from jax.profiler import ProfileData

    import run as harness
    from spark_rapids_tpu.runtime import trace
    _tables, plain = tpch
    sess = _mesh_session(4)
    _place(sess, {"lineitem": plain["lineitem"]})
    # Q1 for the sharded update; the wide group-by for the exchange, which
    # Q1's few rows of state no longer take (ISSUE 37)
    texts = (harness.load_query("q1"), WIDE_GROUP_BY)
    for text in texts:
        sess.sql(text).to_pydict()  # compile outside the capture
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for text in texts:
            sess.sql(text).to_pydict()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    names = {e.name for plane in data.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events
             if e.name.startswith(trace.PROFILER_PREFIX)}
    for span in ("HashAggregateExec.shardDispatchTime",
                 "HashAggregateExec.shardReadbackTime",
                 "ShuffleExchangeExec.iciExchangeTime"):
        assert "rapids." + span in names, (span, sorted(names))
