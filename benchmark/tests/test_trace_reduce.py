"""The trace reduction on a synthetic xplane, as plain data."""
import pytest

import trace_reduce as tr

US = 1_000


def planes():
    # window: two passes, 0..100 us and 100..200 us (host clock)
    host = {"main": [("bench.pass", 0, 100 * US), ("bench.pass", 100 * US, 100 * US),
                     ("bench.q1", 0, 100 * US), ("Allocate", 40 * US, 30 * US),
                     ("bench.q6", 100 * US, 100 * US),
                     ("ReadSyncFlag", 150 * US, 50 * US)],
            "other": [("outside", 300 * US, 10 * US)]}
    # device: busy 10..30 (copy nested in fusion.1), 70..100, 100..150; one op
    # straddles the window's end and one lies wholly outside it
    dev = {tr.OPS_LINE: [("fusion.1", 10 * US, 20 * US), ("copy", 20 * US, 10 * US),
                         ("fusion.1", 70 * US, 30 * US), ("fusion.2", 100 * US, 50 * US),
                         ("fusion.2", 199 * US, 10 * US), ("late", 250 * US, 5 * US)],
           tr.MODULES_LINE: [("jit_fn(1)", 10 * US, 20 * US),
                             ("jit_fn(1)", 70 * US, 30 * US),
                             ("jit_less(2)", 100 * US, 50 * US),
                             ("jit_fn(1)", 250 * US, 5 * US)],
           "Steps": [("step", 0, 500 * US)]}
    return {"/host:CPU": host, "/device:TPU:0": dev}


def test_busy_union_and_idle_share():
    r = tr.reduce_trace(planes())
    assert r["passes"] == 2
    assert r["programs"] == 3          # started inside the two passes
    assert r["window_s"] == pytest.approx(200e-6)
    # 20 + 30 + 50 + 1 us; copy, nested in fusion.1, counts once
    assert r["busy_s"] == pytest.approx(101e-6)
    ops = dict(r["device_ops"])
    # fusion.1 ran 10..30 and 70..100; copy, 20..30, is nested in the first
    assert ops["fusion.1"] == pytest.approx(40e-6)
    assert ops["copy"] == pytest.approx(10e-6)
    assert ops["fusion.2"] == pytest.approx(51e-6)    # clipped at the window
    assert "late" not in ops and "step" not in ops


def test_gaps_named_by_innermost_host_event():
    gaps = dict(tr.reduce_trace(planes())["idle_gaps"])
    # 0..10 -> bench.q1; 30..70 -> Allocate (inside bench.q1, over the middle);
    # 150..199 -> ReadSyncFlag (inside bench.q6)
    assert gaps["bench.q1"] == pytest.approx(10e-6)
    assert gaps["Allocate"] == pytest.approx(40e-6)
    assert gaps["ReadSyncFlag"] == pytest.approx(49e-6)
    assert sum(gaps.values()) == pytest.approx(200e-6 - 101e-6)


def test_short_gaps_and_two_devices():
    p = planes()
    p["/device:TPU:1"] = {tr.OPS_LINE: [("fusion.1", 0, 100 * US),
                                       ("fusion.1", 101 * US, 99 * US)]}
    r = tr.reduce_trace(p)
    assert r["busy_s"] == pytest.approx((101e-6 + 199e-6) / 2)   # mean of chips
    assert dict(r["idle_gaps"])["between_ops"] == pytest.approx(1e-6 / 2)


def test_nothing_to_read():
    p = planes()
    assert tr.reduce_trace({"/host:CPU": p["/host:CPU"]}) is None
    assert tr.reduce_trace({"/device:TPU:0": p["/device:TPU:0"]}) is None


def test_names_and_nesting():
    hlo = ("%while.33 = (s32[1048576]{0:T(1024)S(1)}, u32[8,7]{0,1}) "
           "while((s32[1048576]{0}) %tuple.2), condition=%c, body=%b")
    assert tr.short_name(hlo) == "while.33 s32[1048576]"
    assert tr.short_name("%fusion.7 = u8[16]{0:T(1024)} fusion(u8[4]{0} %p)") \
        == "fusion.7 u8[16]"
    assert tr.short_name("fusion.1") == "fusion.1"
    # a while of 100 us holds two body ops of 30 us: 40 us are its own
    p = planes()
    p["/device:TPU:0"][tr.OPS_LINE] = [
        (hlo, 0, 100 * US), ("%body.1 = f32[4]{0} fusion()", 10 * US, 30 * US),
        ("%body.1 = f32[4]{0} fusion()", 50 * US, 30 * US)]
    r = tr.reduce_trace(p)
    ops = dict(r["device_ops"])
    assert ops["while.33 s32[1048576]"] == pytest.approx(40e-6)
    assert ops["body.1 f32[4]"] == pytest.approx(60e-6)
    assert r["busy_s"] == pytest.approx(100e-6)


def test_load_xplane_keeps_every_thread_of_one_name(tmp_path):
    """A recorded trace: every Python thread's line is named "python3", and
    the annotations of each have to survive the reading."""
    import threading

    import jax

    def work(name):
        with jax.profiler.TraceAnnotation(name):
            jax.numpy.ones(8).sum().block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.PASS_ANNOTATION):
            threads = [threading.Thread(target=work, args=(f"side.{i}",))
                       for i in range(3)]
            for t in threads:
                t.start()
            work("main.work")
            for t in threads:
                t.join()
    finally:
        jax.profiler.stop_trace()
    planes = tr.load_xplane(str(tmp_path))
    names = {name for lines in planes.values() for events in lines.values()
             for name, _, _ in events}
    assert {tr.PASS_ANNOTATION, "main.work", "side.0", "side.1",
            "side.2"} <= names
    lines = tr.describe(planes)["/host:CPU"]
    assert all(n > 0 and end > start for n, start, end in lines.values())
