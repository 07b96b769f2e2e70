"""A hash exchange with nothing to exchange is not executed (ISSUE 37).

Between the halves of one aggregate, where every input batch is already on
the host with a host-int row count and the rows in all fit a coalesced
batch (4 x spark.rapids.shuffle.coalesceTinyRows), `ShuffleExchangeExec`
lays the rows together with numpy as ONE batch of partition 0: a sharded
partial aggregate's read-back (Q1 over four shards: sixteen rows). Held
here: the answers with the bypass, with it out of reach (the bound set to
0) and by a plain reference agree; who never bypasses (a lazy count, a
total over the bound, an exchange under a join); the counter and the
plan's note; and the programs a bypassed Q1 costs.

The suite conftest forces 8 virtual CPU devices: the four-device mesh here
is the real shard_map path over a placed cache, as in test_mesh_cache.py.
"""
import collections
import glob
import importlib
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (ColumnVector, ColumnarBatch,
                                             LazyRowCount, to_arrow)
from spark_rapids_tpu.exec import fuse
from spark_rapids_tpu.exec.tpu_nodes import ShuffleExchangeExec
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.runtime import obs
from spark_rapids_tpu.runtime.obs import phases
from spark_rapids_tpu.sql.session import TpuSession

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

ON = C.MULTICHIP_ENABLED.key
DEVICES = C.MULTICHIP_DEVICES.key
TINY = C.SHUFFLE_COALESCE_TINY_ROWS.key

#: a group for every lineitem row: more rows of partial state than a
#: coalesced batch holds, so the merge rides the all_to_all
WIDE_GROUP_BY = ("select l_orderkey, l_linenumber, sum(l_quantity) as q "
                 "from lineitem group by l_orderkey, l_linenumber")


def _mesh_session(tiny=None):
    conf = {ON: "true", DEVICES: 4}
    if tiny is not None:
        conf[TINY] = tiny
    return TpuSession(conf)


def _exchanges(node):
    found = [node] if isinstance(node, ShuffleExchangeExec) else []
    # an adaptive join builds its exchanges when it runs (exec/adaptive.py)
    chosen = getattr(node, "_chosen", None)
    for c in node.children if chosen is None else [chosen]:
        found.extend(_exchanges(c))
    return found


def _place(sess, name, table):
    df = sess.create_dataframe(table).cache()
    df.count()
    sess.create_or_replace_temp_view(name, df)


def _last():
    return obs.recent_queries(1)[0]


def _agrees(got, want, rel=1e-11):
    assert set(got) == set(want)
    for name, values in want.items():
        assert len(got[name]) == len(values), name
        for g, w in zip(got[name], values):
            if isinstance(w, float):
                assert abs(g - w) <= rel * max(abs(w), 1e-300), (name, g, w)
            else:
                assert g == w, (name, g, w)


@pytest.fixture(scope="module")
def tpch():
    import datagen
    import run as harness
    tables = datagen.generate(20_000 / 6_000_000, 37)
    return tables, harness.plain_strings(tables["lineitem"])


# -- the host assembly (ops/kernels.concat_host_batches) ---------------------

def _vocab():
    return (np.array([0, 1, 3, 6], np.int32),
            np.frombuffer(b"abbccc", np.uint8))


def _host_batch(codes, vals, vocab, mask=None, valid=None, rows=None):
    doff, dby = vocab
    n = len(codes)
    s = ColumnVector(T.StringType(),
                     {"codes": np.asarray(codes, np.int32),
                      "dict_offsets": doff, "dict_bytes": dby},
                     None if valid is None else np.asarray(valid, np.bool_),
                     str_width=3)
    v = ColumnVector(T.Int64Type(), np.asarray(vals, np.int64),
                     np.ones(n, np.bool_), bounds=(min(vals), max(vals)))
    if mask is None:
        return ColumnarBatch([s, v], n if rows is None else rows)
    mask = np.asarray(mask, np.bool_)
    return ColumnarBatch([s, v], int(mask.sum()), mask)


def _rows(batch):
    return to_arrow(batch, ["s", "v"]).to_pylist()


def test_host_concat_lays_live_rows_in_source_order():
    vocab = _vocab()
    parts = [
        # unmasked, two of four slots live, no validity plane
        _host_batch([0, 1, 2, 2], [10, 11, 99, 99], vocab, rows=2),
        # masked: slots 1 and 3 live, slot 3's string NULL
        _host_batch([2, 1, 0, 0], [99, 20, 99, 21], vocab,
                    mask=[False, True, False, True],
                    valid=[True, True, True, False]),
        # a source with no rows
        _host_batch([0, 0], [99, 99], vocab, mask=[False, False]),
        _host_batch([2], [30], vocab)]
    w0 = phases.syncs_now()
    out = K.concat_host_batches(parts, limit=5)
    assert phases.syncs_now() == w0
    assert out.num_rows == 5 and out.row_mask is None
    assert _rows(out) == [{"s": "a", "v": 10}, {"s": "bb", "v": 11},
                          {"s": "bb", "v": 20}, {"s": None, "v": 21},
                          {"s": "ccc", "v": 30}]
    # what concat_batches makes of the same parts, on the device
    assert _rows(out) == _rows(K.compact_batch(K.concat_batches(parts)))
    s, v = out.columns
    assert all(isinstance(p, np.ndarray)
               for p in (s.data["codes"], s.validity, v.data, v.validity))
    assert s.data["dict_offsets"] is vocab[0]
    assert s.data["dict_bytes"] is vocab[1]
    assert s.str_width == 3 and s.dict_unique
    assert v.bounds == (10, 99)  # the union of the parts' host stamps
    assert out.capacity == K.round_capacity(5)
    assert not v.validity[5:].any() and not s.validity[5:].any()


def _lazy(b):
    return ColumnarBatch(b.columns, LazyRowCount(jnp.int32(b.num_rows)),
                         b.row_mask)


def _on_device(b, what):
    s, v = b.columns
    if what == "data":
        v = ColumnVector(v.dtype, jnp.asarray(v.data), v.validity)
    elif what == "validity":
        v = ColumnVector(v.dtype, v.data, jnp.asarray(v.validity))
    elif what == "codes":
        s = ColumnVector(s.dtype, dict(s.data, codes=jnp.asarray(
            s.data["codes"])), s.validity)
    mask = jnp.ones(b.capacity, jnp.bool_) if what == "mask" else b.row_mask
    return ColumnarBatch([s, v], b.num_rows, mask)


def _flat(b):
    flat = ColumnVector(T.StringType(),
                        {"offsets": np.zeros(b.capacity + 1, np.int32),
                         "bytes": np.zeros(8, np.uint8)}, None)
    return ColumnarBatch([flat, b.columns[1]], b.num_rows, b.row_mask)


#: what puts the second of two host batches out of the assembly's reach
_SPOILED = {
    "a_lazy_count": _lazy,
    "a_device_data_plane": lambda b: _on_device(b, "data"),
    "a_device_validity_plane": lambda b: _on_device(b, "validity"),
    "device_codes": lambda b: _on_device(b, "codes"),
    "a_device_mask": lambda b: _on_device(b, "mask"),
    "a_flat_string": _flat,
    # equal bytes, another object: equal strings must stay one code, and
    # only identity says so without reading the planes
    "another_vocabulary": lambda b: _host_batch([2, 0], [3, 4], _vocab()),
}


@pytest.mark.parametrize("why", [*_SPOILED, "over_the_limit", "no_batches"])
def test_host_concat_declines_what_would_cost_a_sync_or_a_program(why):
    vocab = _vocab()
    a = _host_batch([0, 1], [1, 2], vocab)
    b = _host_batch([2, 0], [3, 4], vocab)
    assert K.concat_host_batches([a, b], 4).num_rows == 4  # within reach
    spoiled = _SPOILED.get(why, lambda b: b)(b)
    parts = [] if why == "no_batches" else [a, spoiled]
    w0 = phases.syncs_now()
    assert K.concat_host_batches(
        parts, 3 if why == "over_the_limit" else 4) is None
    assert phases.syncs_now() == w0
    if why == "a_lazy_count":
        assert not spoiled.num_rows.is_materialized


# -- Q1 and Q6 over a placed cache -------------------------------------------

@pytest.mark.parametrize("tiny,bypassed", [(None, 1), (0, 0), (3, 0),
                                           (4, 1)])
def test_q1_answers_the_reference_bypassed_or_exchanged(tpch, tiny,
                                                        bypassed):
    """Sixteen rows of partial state: within 4 x the default 1024 and
    within 4 x 4, over 4 x 3, and out of reach where the option is 0."""
    import run as harness
    tables, lineitem = tpch
    sess = _mesh_session(tiny)
    _place(sess, "lineitem", lineitem)
    got = sess.sql(harness.load_query("q1")).to_pydict()
    _agrees(got, importlib.import_module("reference.q1").answer(tables))
    rec = _last()
    assert rec["counters"]["exchange_bypassed"] == bypassed
    assert rec["counters"]["shard_waves"] == 1
    plan = sess._last_exec.tree_string()
    assert "[sharded n=4]" in plan, plan
    (ex,) = _exchanges(sess._last_exec)
    assert ex.may_bypass
    if bypassed:
        assert "[bypassed: 16 rows on the host]" in plan, plan
        assert ex.metrics.metric("exchangeBypassed").value == 1
        assert ex.metrics.metric("numOutputRows").value == 16
        assert "iciExchangeTime" not in rec["timers_ns"]
        # one batch in partition 0, nothing in the others
        assert [len(p) for p in ex._out] == [1, 0, 0, 0]
        assert ex._out[0][0].coalesced
    else:
        assert "bypassed" not in plan, plan
        assert rec["timers_ns"]["iciExchangeTime"] > 0


def test_q6_has_no_exchange_to_bypass(tpch):
    import run as harness
    tables, lineitem = tpch
    sess = _mesh_session()
    _place(sess, "lineitem", lineitem)
    got = sess.sql(harness.load_query("q6")).to_pydict()
    _agrees(got, importlib.import_module("reference.q6").answer(tables))
    assert _last()["counters"]["exchange_bypassed"] == 0
    assert _exchanges(sess._last_exec) == []
    assert "[sharded n=4]" in sess._last_exec.tree_string()


def test_states_over_the_bound_ride_the_all_to_all(tpch):
    _tables, lineitem = tpch
    sess = _mesh_session()
    _place(sess, "lineitem", lineitem)
    got = sess.sql(WIDE_GROUP_BY).to_pydict()
    rec = _last()
    assert rec["counters"]["exchange_bypassed"] == 0
    assert rec["counters"]["shard_waves"] == 1
    assert rec["timers_ns"]["iciExchangeTime"] > 0
    (ex,) = _exchanges(sess._last_exec)
    assert ex.may_bypass and ex._bypassed_rows is None
    want = collections.defaultdict(float)
    for o, n, q in zip(lineitem["l_orderkey"].to_pylist(),
                       lineitem["l_linenumber"].to_pylist(),
                       lineitem["l_quantity"].to_pylist()):
        want[(o, n)] += q
    assert len(want) > 4 * C.SHUFFLE_COALESCE_TINY_ROWS.default
    have = {(o, n): q for o, n, q in zip(got["l_orderkey"],
                                         got["l_linenumber"], got["q"])}
    assert have.keys() == want.keys()
    assert all(abs(have[k] - want[k]) <= 1e-9 * abs(want[k]) for k in want)


# -- keys and sources the assembly must not trip over ------------------------

ROWS = 4000  # four ranges of a thousand consecutive rows, one a shard


def _small_table():
    idx = np.arange(ROWS)
    names = np.array(["ash", "birch", "cedar"], object)[idx % 3]
    return pa.table({
        "shard": pa.array(idx // (ROWS // 4), pa.int64()),
        # a dictionary string key with NULLs in every shard
        "k": pa.array([None if i % 7 == 0 else names[i] for i in idx]),
        "g": pa.array(idx % 5, pa.int64()),
        "v": pa.array(idx * 0.25),
    })


def _small_reference(table, keep):
    sums = collections.defaultdict(float)
    counts = collections.Counter()
    for sh, k, g, v in zip(*(table[c].to_pylist()
                             for c in ("shard", "k", "g", "v"))):
        if keep(sh):
            sums[(k, g)] += v
            counts[(k, g)] += 1
    return {key: (sums[key], counts[key]) for key in sums}


@pytest.mark.parametrize("case,where,keep,groups", [
    ("dictionary_and_null_keys", "shard >= 0", lambda sh: True, 20),
    ("a_source_with_no_rows", "shard <> 2", lambda sh: sh != 2, 20),
    ("one_source_alone", "shard = 1", lambda sh: sh == 1, 20),
    ("all_sources_empty", "shard > 9", lambda sh: False, 0)])
@pytest.mark.parametrize("tiny", [None, 0])
def test_grouped_answers_agree_bypassed_and_exchanged(case, where, keep,
                                                      groups, tiny):
    table = _small_table()
    sess = _mesh_session(tiny)
    _place(sess, "t", table)
    got = sess.sql(f"select k, g, sum(v) as sv, count(*) as n from t "
                   f"where {where} group by k, g").to_pydict()
    rec = _last()
    assert "[sharded n=4]" in sess._last_exec.tree_string()
    assert rec["counters"]["exchange_bypassed"] == (0 if tiny == 0 else 1)
    want = _small_reference(table, keep)
    assert len(want) == groups
    have = {(k, g): (sv, n) for k, g, sv, n in
            zip(got["k"], got["g"], got["sv"], got["n"])}
    assert len(got["k"]) == len(have)  # no key twice: the merge ran
    assert have.keys() == want.keys()
    for key, (sv, n) in want.items():
        assert have[key][1] == n, key
        assert abs(have[key][0] - sv) <= 1e-11 * max(abs(sv), 1.0), key


# -- who never bypasses -------------------------------------------------------

def test_a_lazy_count_never_bypasses_and_nothing_waits():
    """Tier-1's own case: eight CPU devices and no mesh, so a grouped
    aggregate plans this exchange over partial states whose planes are on
    the device and whose counts are lazy."""
    sess = TpuSession()
    data = {"k": [i % 9 for i in range(3000)],
            "v": [float(i) for i in range(3000)]}
    df = sess.create_dataframe(data, num_partitions=4)
    sess.create_or_replace_temp_view("t", df)
    got = sess.sql("select k, sum(v) as sv from t group by k").to_pydict()
    assert sorted(got["k"]) == list(range(9))
    assert _last()["counters"]["exchange_bypassed"] == 0
    (ex,) = _exchanges(sess._last_exec)
    assert ex.may_bypass and ex._bypassed_rows is None
    assert "bypassed" not in sess._last_exec.tree_string()
    # the same exchange handed a host batch but for its count
    lazy = _lazy(_host_batch([0, 1], [1, 2], _vocab()))
    w0 = phases.syncs_now()
    assert ex._bypass([[lazy], [], [], []]) is None
    assert phases.syncs_now() == w0
    assert not lazy.num_rows.is_materialized
    # and a live stream of batches, which cannot be looked at twice
    host = _host_batch([0, 1], [1, 2], _vocab())
    assert ex._bypass([iter([host])]) is None
    assert ex._bypass([[host], [], [], []])[0][0].num_rows == 2


@pytest.mark.parametrize("adaptive", ["true", "false"],
                         ids=["built_when_it_runs", "planned"])
def test_a_joins_exchanges_never_bypass(adaptive):
    """Both sides of a shuffled join must be co-partitioned: its
    exchanges are built without the planner's leave (by the planner, or
    by the adaptive join once it has measured its build side), and a few
    host rows handed to one are still exchanged."""
    conf = {"spark.rapids.sql.join.broadcastRowThreshold": 1,
            C.ADAPTIVE_ENABLED.key: adaptive,
            C.ADAPTIVE_BROADCAST_BYTES.key: 0}
    rng = np.random.default_rng(3)
    left = pa.table({"k": pa.array(rng.integers(0, 12, 60)),
                     "lv": pa.array(np.arange(60))})
    right = pa.table({"k": pa.array(rng.integers(0, 15, 30)),
                      "rv": pa.array(np.arange(30) * 1.5)})
    sess = TpuSession(conf)
    got = sess.create_dataframe(left, num_partitions=3).join(
        sess.create_dataframe(right, num_partitions=2), on="k",
        how="inner").collect()
    lk, rk = left["k"].to_pylist(), right["k"].to_pylist()
    assert len(got) == sum(lk.count(k) for k in rk)
    assert _last()["counters"]["exchange_bypassed"] == 0
    exchanges = _exchanges(sess._last_exec)
    assert len(exchanges) == 2, sess._last_exec.tree_string()
    host = _host_batch([0, 1], [1, 2], _vocab())
    for ex in exchanges:
        assert not ex.may_bypass and ex._bypassed_rows is None
        assert ex._bypass([[host], []]) is None


# -- what a bypassed Q1 costs -------------------------------------------------

#: keyed programs of a warm Q1 over four shards: the SPMD update, the
#: merge, the evaluate, the sort (15 with the all_to_all: four key hashes,
#: the collective, four merges and four evaluates)
Q1_KEYED_BUDGET = 5


def test_a_bypassed_q1_is_a_handful_of_programs(tpch, tmp_path):
    import run as harness
    _tables, lineitem = tpch
    sess = _mesh_session()
    _place(sess, "lineitem", lineitem)
    text = harness.load_query("q1")
    sess.sql(text).to_pydict()
    keys = []
    fuse.set_dispatch_hook(keys.append)
    try:
        sess.sql(text).to_pydict()
    finally:
        fuse.set_dispatch_hook(None)
    classes = [k[0] for k in keys]
    assert classes[0] == "sharded_stage" and classes[-1] == "sort", classes
    assert not any(c.startswith("ici_") for c in classes), classes
    assert _last()["counters"]["keyed_dispatches"] == len(keys) \
        <= Q1_KEYED_BUDGET
    # every jitted call of a pass, eager jnp among them (the profiler's
    # PjitFunction events on the host, two an execution): 16 here, 1156
    # through the exchange and the four partitions' eager concats
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        sess.sql(text).to_pydict()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                           "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    calls = sum(1 for plane in data.planes for line in plane.lines
                for ev in line.events if ev.name.startswith("PjitFunction("))
    assert len(keys) <= calls <= 6 * len(keys), (len(keys), calls)
