"""Trace reduction on a synthetic trace: busy union, idle share, device
time inside host spans, gap labels, top operations."""
import pytest

import devtrace


def test_merge_and_cover():
    m = devtrace.merge([(0, 1), (0.5, 2), (3, 4), (4, 4.5), (5, 5)])
    assert m == [(0, 2), (3, 4.5)]
    assert devtrace.covered(m, 1, 3.5) == pytest.approx(1.5)
    assert devtrace.gaps(m, -1, 6) == [(-1, 0), (2, 3), (4.5, 6)]


def test_reduce_attributes_busy_time_to_spans_and_labels_gaps():
    ops = [(0.0, 0.4, "fusion.1"), (0.3, 0.5, "fusion.2"),    # admit: 0.5 busy
           (1.0, 1.9, "while.3"),                             # decode: 0.9
           (2.5, 2.6, "fusion.1")]                            # decode: 0.1
    host = [("bench.admit", 0.0, 0.55), ("bench.idle_wait", 0.55, 1.0),
            ("bench.decode_block", 1.0, 2.0), ("bench.decode_block", 2.0, 3.0)]
    r = devtrace.reduce({"/device:TPU:0": ops}, host, (0.0, 3.0))
    assert r["busy_s"] == pytest.approx(1.5)
    assert r["window_s"] == pytest.approx(3.0)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.5)
    assert r["span_busy_s"]["bench.admit"] == pytest.approx(0.5)
    assert r["span_busy_s"]["bench.decode_block"] == pytest.approx(1.0)
    assert r["device_ops"][0] == ["while.3", pytest.approx(0.9)]
    gaps = {round(t, 6): name for name, t in r["idle_gaps"]}
    assert gaps[0.5] == "bench.idle_wait"          # 0.5 .. 1.0
    assert gaps[0.4] == "bench.decode_block"       # 2.6 .. 3.0
    assert gaps[0.6] == "bench.decode_block"       # 1.9 .. 2.5 (midpoint 2.2)


def test_reduce_clips_to_window_and_averages_devices():
    ops0 = [(0.0, 2.0, "a")]
    ops1 = [(1.0, 2.0, "a")]
    r = devtrace.reduce({"d0": ops0, "d1": ops1}, [], (1.0, 3.0))
    assert r["busy_s"] == pytest.approx(1.0)
    assert r["devices"] == 2
    assert r["idle_gaps"][0] == ["none", pytest.approx(1.0)]


def test_self_time_subtracts_nested_operations():
    ops = [(0.0, 1.0, "%while.1 = (s32[]{:T(128)}) while(...)"),
           (0.1, 0.3, "%fusion.2 = bf16[8,4]{1,0} fusion(...)"),
           (0.4, 0.9, "%copy.3 = bf16[8,4]{1,0} copy(...)"),
           (1.0, 1.5, "%fusion.2 = bf16[8,4]{1,0} fusion(...)")]
    got = dict()
    for n, t in devtrace.self_times(ops):
        got[n] = got.get(n, 0.0) + t
    assert got == {"%while.1 = (s32[]": pytest.approx(0.3),
                   "%fusion.2 = bf16[8,4]": pytest.approx(0.7),
                   "%copy.3 = bf16[8,4]": pytest.approx(0.5)}
